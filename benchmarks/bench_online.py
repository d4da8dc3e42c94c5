"""Online serving benchmark: router throughput, drift recovery, failover.

Three sections, one BENCH_online.json:

  * router — serving throughput (queries/sec) on the lmbr-stress trace:
    ``scalar-loop`` (one `cover_for_query` per query, the pre-subsystem
    serving path), ``microbatched`` (`ReplicaRouter`, one batched cover per
    ``router_microbatch`` queries) and ``balanced`` (microbatched + the
    load-aware tie-break).  The microbatched covers are asserted
    BIT-IDENTICAL to the scalar loop (chosen partitions AND per-item replica
    attribution), and the run aborts if the microbatched speedup falls
    under ``ROUTER_GATE`` (8x — see the constant for the calibration).
  * drift — a fig6→shifted-workload splice served through
    `Simulator.run_online` with the drift detector armed: the trigger must
    fire, and the post-refit windowed avg_span must land within 10% of a
    cold LMBR fit on the new workload (asserted).
  * failover — kill EVERY single partition (and a few pairs) of a fitted
    layout, repair through `FailoverManager`, and compare the repaired
    trace avg_span against a from-scratch refit on the surviving
    partitions.  Coverage must be fully restored and every single-kill
    ratio must stay within 15% (asserted).

Emits benchmarks/results/BENCH_online.json; see benchmarks/README.md for
the row schema.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from repro import flags
from repro.core import (
    ALGORITHMS,
    Hypergraph,
    LMBR_STRESS_DEFAULTS,
    Placement,
    PlacementService,
    Simulator,
    cover_for_query,
    lmbr_stress_workload,
    random_workload,
    spans_for_workload,
)
from repro.online import FailoverManager, ReplicaRouter

from .common import emit_csv, save_json

KEYS = [
    "section", "engine", "seconds", "qps", "speedup", "identical",
    "load_imbalance", "avg_span", "kills", "ratio", "worst_ratio",
    "drift_fires", "plan_swaps", "windowed_avg_span", "cold_avg_span",
    "repaired_items", "restored_coverage",
]

# microbatched-router speedup floor.  PR 4 measured 12-18x; the current
# 1-core CI container lands at ~10.6x with a fresh process and 9.3-9.8x
# when bench_lmbr runs first in the same process (verified identical on
# the untouched PR 5 tree, so it is machine drift, not an engine
# regression).  8x keeps real regressions loud without flaking on the
# in-process sequence bench-smoke runs.
ROUTER_GATE = 8.0


# ------------------------------------------------------------------ router
def _router_rows(quick: bool) -> list[dict]:
    wl = lmbr_stress_workload()
    hg = wl.hypergraph
    n = LMBR_STRESS_DEFAULTS["num_partitions"]
    cap = LMBR_STRESS_DEFAULTS["capacity"]
    # serving throughput is layout-independent; a random layout keeps the
    # tier's fit cost out of the serving benchmark
    pl = ALGORITHMS["random"](hg, n, cap, seed=0)
    nq = hg.num_edges

    # exactness gate first (covers AND replica attribution), so the big
    # reference-result list is freed before anything is timed
    router = ReplicaRouter(pl.member)
    batch = router.route_csr(hg.edge_ptr, hg.edge_nodes)
    full_spans = batch.spans
    for e in range(nq):
        chosen, accessed = cover_for_query(hg.edge(e), pl.member)
        assert list(batch.chosen(e)) == chosen, f"query {e} cover diverged"
        cov = batch.cover(e)
        for p, items in zip(chosen, accessed):
            assert np.array_equal(cov[p], items), f"query {e} attribution"

    # paired per-slice timing: each trace slice times the scalar loop and
    # the microbatched router back to back (min-of-2 on BOTH sides, so the
    # measurement is symmetric), which keeps transient CPU contention from
    # hitting only one side of a pair; the reported speedup is the median
    # slice ratio (robust against a slow or fast outlier slice)
    slice_q = 2000
    t_scalar = 0.0
    t_batch = 0.0
    ratios = []
    for lo in range(0, nq, slice_q):
        hi = min(lo + slice_q, nq)
        gc.collect()
        ts = np.inf
        for _ in range(2):
            t0 = time.perf_counter()
            for e in range(lo, hi):
                cover_for_query(hg.edge(e), pl.member)
            ts = min(ts, time.perf_counter() - t0)
        ptr = hg.edge_ptr[lo: hi + 1] - hg.edge_ptr[lo]
        nodes = hg.edge_nodes[hg.edge_ptr[lo]: hg.edge_ptr[hi]]
        tb = np.inf
        for _ in range(2):
            t0 = time.perf_counter()
            batch = router.route_csr(ptr, nodes)
            tb = min(tb, time.perf_counter() - t0)
        t_scalar += ts
        t_batch += tb
        ratios.append(ts / max(tb, 1e-9))
    speedup = float(np.median(ratios))
    if speedup < ROUTER_GATE:
        raise AssertionError(
            f"microbatched router median slice speedup {speedup:.1f}x "
            f"< {ROUTER_GATE}x gate (slices: {[round(r, 1) for r in ratios]})"
        )

    balanced = ReplicaRouter(pl.member, balance=True)
    balanced.route_csr(hg.edge_ptr, hg.edge_nodes)
    balanced.load[:] = 0.0
    t_bal = np.inf
    for _ in range(3):
        gc.collect()
        t0 = time.perf_counter()
        bbatch = balanced.route_csr(hg.edge_ptr, hg.edge_nodes)
        t_bal = min(t_bal, time.perf_counter() - t0)
    balanced.load[:] = 0.0  # report single-trace ledger metrics
    bbatch = balanced.route_csr(hg.edge_ptr, hg.edge_nodes)

    rows = [
        dict(section="router", engine="scalar-loop",
             seconds=round(t_scalar, 3), qps=round(nq / t_scalar),
             speedup=1.0, identical=True,
             avg_span=round(float(full_spans.mean()), 4),
             load_imbalance=None),
        dict(section="router", engine="microbatched",
             seconds=round(t_batch, 3), qps=round(nq / max(t_batch, 1e-9)),
             speedup=round(speedup, 1), identical=True,
             avg_span=round(float(full_spans.mean()), 4),
             load_imbalance=round(router.load_imbalance(), 3)),
        dict(section="router", engine="balanced",
             seconds=round(t_bal, 3), qps=round(nq / max(t_bal, 1e-9)),
             speedup=round(t_scalar / max(t_bal, 1e-9), 1), identical=False,
             avg_span=round(float(bbatch.spans.mean()), 4),
             load_imbalance=round(balanced.load_imbalance(), 3)),
    ]
    return rows


# ------------------------------------------------------------------- drift
def _drift_rows(quick: bool) -> list[dict]:
    n, cap = 40, 50
    fit_moves = 120 if quick else 300
    old = random_workload(1000, 4000, 3, 11, 20, seed=0)
    new = random_workload(1000, 4000, 3, 11, 20, seed=7)
    window = int(flags.FLAGS["drift_window"])
    # splice: a slice of yesterday's traffic, then the shifted workload
    trace = Hypergraph.from_edges(
        [old.hypergraph.edge(e) for e in range(2000)]
        + [new.hypergraph.edge(e) for e in range(new.hypergraph.num_edges)],
        num_nodes=1000,
    )
    sim = Simulator(n, cap)
    res = sim.run_online(
        old.hypergraph, ALGORITHMS["lmbr"], name="lmbr+drift", trace=trace,
        service=PlacementService("lmbr", seed=0), refit_moves=400,
        seed=0, max_moves=fit_moves,
    )
    stats = res.online_stats
    if not stats["drift_fires"]:
        raise AssertionError("drift trigger did not fire on the splice")
    # cold fit on the new workload, judged on the same tail window the
    # detector's windowed avg_span covers
    cold = ALGORITHMS["lmbr"](new.hypergraph, n, cap, seed=0,
                              max_moves=fit_moves)
    tail = trace.subhypergraph_edges(
        np.arange(trace.num_edges - window, trace.num_edges)
    )
    cold_span = float(spans_for_workload(tail, cold).mean())
    ratio = stats["windowed_avg_span"] / cold_span
    if ratio > 1.10:
        raise AssertionError(
            f"post-refit windowed avg_span {stats['windowed_avg_span']:.3f} "
            f"is {ratio:.3f}x the cold fit ({cold_span:.3f}) > 1.10 gate"
        )
    return [dict(
        section="drift", engine="run_online",
        drift_fires=stats["drift_fires"], plan_swaps=stats["plan_swaps"],
        windowed_avg_span=stats["windowed_avg_span"],
        cold_avg_span=round(cold_span, 4), ratio=round(ratio, 4),
    )]


# ---------------------------------------------------------------- failover
def _kill_and_repair(hg, pl, kills, cap):
    """Kill `kills`, repair, return (repaired avg_span, repaired count).

    The wave-batched repair is asserted BIT-IDENTICAL to the retained
    per-item reference (`FailoverManager.repair_reference`) on every kill
    scenario — same copies, same destinations."""
    live = Placement(pl.member.copy(), cap, hg.node_weights)
    fo = FailoverManager(live)
    ref_live = Placement(pl.member.copy(), cap, hg.node_weights)
    fo_ref = FailoverManager(ref_live)
    for p in kills:
        fo.partition_down(p)
        fo_ref.partition_down(p)
    repaired = fo.repair(hg, k=1)
    ref_repaired = fo_ref.repair_reference(hg, k=1)
    if not (np.array_equal(repaired, ref_repaired)
            and (live.member == ref_live.member).all()):
        raise AssertionError(
            f"batched repair diverged from the reference after {kills}"
        )
    if len(fo.uncovered_items()):
        raise AssertionError(f"repair left items uncovered after {kills}")
    live.validate()  # repair must respect capacity
    return float(spans_for_workload(hg, live).mean()), fo.stats


def _surviving_refit_span(hg, n, cap, kills, fit_moves) -> float:
    """From-scratch LMBR fit using only the surviving partitions."""
    cold = ALGORITHMS["lmbr"](hg, n - len(kills), cap, seed=0,
                              max_moves=fit_moves)
    return float(spans_for_workload(hg, cold).mean())


def _failover_rows(quick: bool) -> list[dict]:
    n, cap = 12, 40
    fit_moves = 80 if quick else 200
    wl = random_workload(300, 1200, 3, 11, 8, seed=0)
    hg = wl.hypergraph
    pl = ALGORITHMS["lmbr"](hg, n, cap, seed=0, max_moves=fit_moves)

    rows = []
    ratios = []
    repaired_total = 0
    for p in range(n):  # "any single partition": all of them
        span, stats = _kill_and_repair(hg, pl, [p], cap)
        cold = _surviving_refit_span(hg, n, cap, [p], fit_moves)
        ratios.append(span / cold)
        repaired_total += stats["repaired_items"]
    worst = max(ratios)
    if worst > 1.15:
        raise AssertionError(
            f"single-partition repair worst ratio {worst:.3f} > 1.15 gate"
        )
    rows.append(dict(
        section="failover", engine="repair", kills=1,
        ratio=round(float(np.mean(ratios)), 4), worst_ratio=round(worst, 4),
        repaired_items=repaired_total, restored_coverage=True,
    ))

    pair_ratios = []
    repaired_total = 0
    pairs = [(0, 1), (3, 7), (5, 11)]
    for kills in pairs:
        span, stats = _kill_and_repair(hg, pl, list(kills), cap)
        cold = _surviving_refit_span(hg, n, cap, list(kills), fit_moves)
        pair_ratios.append(span / cold)
        repaired_total += stats["repaired_items"]
    rows.append(dict(
        section="failover", engine="repair", kills=2,
        ratio=round(float(np.mean(pair_ratios)), 4),
        worst_ratio=round(max(pair_ratios), 4),
        repaired_items=repaired_total, restored_coverage=True,
    ))
    return rows


def run(quick: bool = True) -> list[dict]:
    from repro.core.setcover import _accel_backend

    _accel_backend()  # pay JAX's one-time backend start-up outside the timings
    flags.reset()
    rows = []
    rows += _router_rows(quick)
    rows += _drift_rows(quick)
    rows += _failover_rows(quick)
    for r in rows:
        print(f"  {r}", flush=True)
    emit_csv("bench_online", rows, KEYS)
    save_json("BENCH_online", rows)
    return rows


if __name__ == "__main__":
    run(quick=True)
