"""Schema snapshots: the exact key sets of `SimulationResult.summary()` and
of every committed ``BENCH_*.json`` row are FROZEN here.

Downstream consumers (the benchmark CSVs, the README tables, external
dashboards scraping the Prometheus export) key on these names; renaming or
dropping one is a breaking change that must show up in review as an edit
to this file, not as a silent drift.  Adding a key is also caught — extend
the frozen set in the same PR that adds it.
"""

import glob
import json
import os

import pytest

from repro import flags
from repro.core import (
    ALGORITHMS,
    Hypergraph,
    PlacementService,
    Simulator,
    random_workload,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ------------------------------------------------- summary() key snapshots
BASE_KEYS = {
    "algorithm", "avg_span", "max_span", "energy_kj", "shipped_gb", "rf",
    "placement_s", "load_imbalance", "active_machines", "cluster_power_w",
}
LMBR_FIT_KEYS = {
    "fit_moves", "fit_gain_calls", "fit_gain_cache_hits", "fit_gain_fp_hits",
    "fit_peel_pairs", "fit_peel_pins_max", "fit_peel_items",
    "fit_peel_classes", "fit_peel", "fit_gain_cache",
    "fit_lmbr_epochs",
    "fit_cache_hit_rate", "fit_cover_engine", "fit_dense_peel_calls",
    "fit_dense_peel_skips", "fit_int_exact",
}
ONLINE_KEYS = {
    "served_queries", "microbatches", "plan_swaps", "degraded_queries",
    "partitions_down", "repaired_items", "unrepairable_items",
}
DRIFT_KEYS = {"drift_fires", "refits", "windowed_avg_span"}
HEALTH_KEYS = {"alerts_fired", "alerts_resolved"}
MIGRATION_KEYS = {
    "migrations", "migration_copies", "migration_drops", "migration_ticks",
    "migration_done", "migration_transfer_gb", "migration_wasted_gb",
    "migration_max_inflight_gb",
}


@pytest.fixture(autouse=True)
def _reset_flags():
    flags.reset()
    yield
    flags.reset()


def test_offline_summary_exact_keys():
    wl = random_workload(num_items=120, num_queries=300, density=5, seed=4)
    res = Simulator(8, 32).run(wl.hypergraph, ALGORITHMS["lmbr"],
                               name="lmbr", seed=0, max_moves=40)
    assert set(res.summary()) == BASE_KEYS | LMBR_FIT_KEYS


def test_online_summary_exact_keys():
    wl = random_workload(num_items=120, num_queries=300, density=5, seed=4)
    res = Simulator(8, 32).run_online(wl.hypergraph, ALGORITHMS["lmbr"],
                                      name="lmbr", seed=0, max_moves=40)
    assert set(res.summary()) == BASE_KEYS | LMBR_FIT_KEYS | ONLINE_KEYS


def test_online_drift_migration_summary_exact_keys():
    """The maximal summary: drift service armed (drops fit_* — the service
    owns the fitter) plus a paced migration."""
    old = random_workload(num_items=120, num_queries=500, density=6, seed=2)
    new = random_workload(num_items=120, num_queries=500, density=6, seed=9)
    trace = Hypergraph.from_edges(
        [old.hypergraph.edge(e) for e in range(200)]
        + [new.hypergraph.edge(e) for e in range(500)],
        num_nodes=120,
    )
    target = ALGORITHMS["lmbr"](old.hypergraph, 10, 30, seed=1, max_moves=40)
    flags.set_variant("driftw128+driftth1.1+routermb64")
    flags.FLAGS["migration_bandwidth"] = 5.0
    res = Simulator(10, 30).run_online(
        old.hypergraph, ALGORITHMS["hpa"], name="hpa+drift", trace=trace,
        events=[(20, "down", 3), (60, "up", 3), (100, "migrate", target)],
        service=PlacementService("lmbr", seed=0), refit_moves=128, seed=0,
    )
    assert set(res.summary()) == (
        BASE_KEYS | ONLINE_KEYS | DRIFT_KEYS | MIGRATION_KEYS)


def test_online_health_summary_exact_keys():
    """Health monitoring adds exactly the two alert counters (PR 10)."""
    wl = random_workload(num_items=120, num_queries=300, density=5, seed=4)
    flags.set_variant("obscounters+obssnap100+obshealth1")
    res = Simulator(8, 32).run_online(wl.hypergraph, ALGORITHMS["lmbr"],
                                      name="lmbr", seed=0, max_moves=40)
    assert set(res.summary()) == (
        BASE_KEYS | LMBR_FIT_KEYS | ONLINE_KEYS | HEALTH_KEYS)


# ------------------------------------------------ BENCH_*.json row schemas
# union of row keys per committed benchmark artifact (rows within one file
# legitimately differ by section; the union is the stable contract)
BENCH_SCHEMAS = {
    "BENCH_energy.json": {
        "active_machines", "avg_span", "cluster_power_w",
        "durability_copies", "durability_eps", "identical", "items",
        "machine_cut_pct", "mode", "p_loss_max", "partitions", "queries",
        "rf", "seconds", "section", "span_ratio", "tier",
    },
    "BENCH_lmbr.json": {
        "avg_span", "cache_hits", "engine", "gain_calls", "identical",
        "infeasible", "moves", "seconds", "speedup", "tier",
    },
    "BENCH_migration.json": {
        "avg_span", "bit_identical", "copies", "degraded", "done", "drops",
        "engine", "inflight_bound_gb", "max_inflight_gb", "seconds",
        "section", "span_regret", "ticks", "transfer_gb", "wasted_gb",
    },
    "BENCH_online.json": {
        "avg_span", "cold_avg_span", "drift_fires", "engine", "identical",
        "kills", "load_imbalance", "plan_swaps", "qps", "ratio",
        "repaired_items", "restored_coverage", "seconds", "section",
        "speedup", "windowed_avg_span", "worst_ratio",
    },
    "BENCH_obs.json": {
        "avg_span", "events", "gate", "identical", "level", "qps", "ratio",
        "seconds", "section", "series",
    },
    "BENCH_scale.json": {
        "avg_span", "boundary_cost", "boundary_edges", "engine",
        "engine_speedup", "identical", "infeasible", "items", "queries",
        "ratio", "seconds", "section", "shards", "speedup", "tier",
        "workers",
    },
    "BENCH_spans.json": {
        "avg_span", "circuit", "edges", "engine", "seconds", "speedup",
    },
}


def test_bench_artifacts_match_frozen_schemas():
    found = {os.path.basename(p)
             for p in glob.glob(os.path.join(REPO_ROOT, "BENCH_*.json"))}
    unknown = found - set(BENCH_SCHEMAS)
    assert not unknown, f"new BENCH artifacts need a frozen schema: {unknown}"
    for name in sorted(found):
        rows = json.load(open(os.path.join(REPO_ROOT, name)))
        assert rows, f"{name} is empty"
        keys = set()
        for r in rows:
            keys |= set(r)
        assert keys == BENCH_SCHEMAS[name], (
            f"{name} row schema drifted: "
            f"+{sorted(keys - BENCH_SCHEMAS[name])} "
            f"-{sorted(BENCH_SCHEMAS[name] - keys)}"
        )
