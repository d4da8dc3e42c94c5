"""Run one cell of the chip benchmark on the machine's TPU.

Usage:
    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The run makes
its data from ``--seed``, sets up and warms every shape it will use,
measures for ``--seconds``, checks what the window produced against the
plain reference, and prints one JSON object as its last line of stdout:
the end-to-end metrics with ``--trace 0``, the per-layer metrics read from
a profiler trace of the window with ``--trace 1``.  Without a TPU, or with
fewer chips than the cell asks for, it exits with code 2 before any
set-up and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}
    if args.workload not in chips:
        print(f"no workload {args.workload!r}", file=sys.stderr)
        return 2
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips[args.workload]:
        print(f"needs {chips[args.workload]} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness
    from repro.compile_cache import enable_compile_cache

    harness.compile_counter()
    print(f"compile cache: {enable_compile_cache()}; JAX up after "
          f"{time.perf_counter() - T_START:.3f} s", flush=True)
    run = harness.build_run(args.workload, args.seed, args.seconds,
                            bool(args.trace), bench=bench)
    harness.emit(harness.run_cell(run, bench=bench, t_start=T_START))
    return 0


if __name__ == "__main__":
    sys.exit(main())
