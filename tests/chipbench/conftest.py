"""Fixtures of the chip benchmark's CPU tests: the benchmark's own
arithmetic, and whole runs at a size a test can hold, with the harness's
look for a chip skipped."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# the cells' shapes at a size the CPU holds in seconds
SMALL_CONFIG = {
    "tpch-sf25-40p": dict(num_queries=600),
}
SMALL_TRAFFIC = {
    "fit": dict(traces=2),
}


@pytest.fixture
def small_run():
    """``small_run(cell, seed, seconds, tracing)`` runs one cell on the CPU
    at a small size and returns its result object."""
    from chipbench import harness

    def run(cell, seed=3, seconds=0.4, tracing=False):
        bench = harness.load_benchmark()
        r = harness.build_run(cell, seed, seconds, tracing, bench=bench)
        entry = harness.workload_entry(bench, cell)
        r.config.update(SMALL_CONFIG[entry["config"]])
        r.traffic.update(SMALL_TRAFFIC[r.traffic["loop"]])
        return harness.run_cell(r, bench=bench)

    return run
