"""LMBR move-engine benchmark: pure-Python reference peel (the retained
Algorithm 5 oracle, gain cache off) vs the vectorized engine (batched
lockstep peel + epoch-keyed gain cache, the default since PR 3).

Three tiers:

  * fig6-quick — the paper's Random dataset at fig. 6 defaults (bounded
    move budget so the quick gate stays cheap);
  * fig9-quick — the ibm01-like ISPD98 circuit at fig. 9 settings;
  * lmbr-stress — the larger tier (``repro.core.lmbr_stress_workload``)
    the pre-vectorization engine could not finish interactively.  The
    reference runs under a wall-clock budget; blowing it marks the row
    ``infeasible`` and reports the budget as a lower bound.

On the two quick tiers the placements of both engines are asserted
BIT-IDENTICAL (same membership matrix, hence same spans) — the perf rows
are only emitted if exactness holds.  Emits
benchmarks/results/BENCH_lmbr.json; see benchmarks/README.md for the row
schema.

Methodology: every engine starts from the SAME precomputed balanced
HPA assignment (lmbr's own Algorithm-4 warm start, built once per tier and
passed via ``initial=``), and the accelerated span backend is imported
before the first timing — so the rows compare pure move-engine work, not
who pays the partitioner memo or the one-time jax import.
"""

from __future__ import annotations

import signal
import threading
import time

import numpy as np

from repro import flags
from repro.core import (
    ALGORITHMS,
    LMBR_STRESS_DEFAULTS,
    Placement,
    hpa_partition,
    ispd_like_workload,
    lmbr_stress_workload,
    random_workload,
    spans_for_workload,
)

from .common import emit_csv, save_json

# reference wall-clock budget on the stress tier (seconds)
REF_BUDGET_QUICK = 60.0
REF_BUDGET_FULL = 600.0


class _Timeout(Exception):
    pass


def _run_with_budget(fn, budget: float):
    """Run fn() under a SIGALRM budget (main thread only; without signal
    support the budget is not enforced and the call just runs)."""
    if threading.current_thread() is not threading.main_thread():
        return fn(), False

    def _raise(signum, frame):
        raise _Timeout()

    old = signal.signal(signal.SIGALRM, _raise)
    signal.setitimer(signal.ITIMER_REAL, budget)
    done: list = []  # survives a _Timeout that lands after fn() finished
    try:
        done.append(fn())
    except _Timeout:
        pass
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old)
    if done:
        return done[0], False
    return None, True


def _warm_start(hg, n, capacity) -> Placement:
    """lmbr's own Algorithm-4 balanced start, built once per tier so every
    engine times pure move-engine work from an identical placement."""
    bal_cap = min(
        capacity,
        hg.total_node_weight() / n * 1.1 + float(hg.node_weights.max()),
    )
    assign = hpa_partition(hg, n, bal_cap, seed=0, nruns=2)
    pl = Placement.empty(n, hg.num_nodes, capacity, hg.node_weights)
    pl.member[assign, np.arange(hg.num_nodes)] = True
    return pl


def _time_engine(hg, n, capacity, max_moves, initial, variant: str,
                 budget=None):
    """Fit LMBR under a flags variant; returns (placement, seconds, timed_out)."""
    flags.set_variant(variant)
    try:
        t0 = time.perf_counter()
        if budget is None:
            pl = ALGORITHMS["lmbr"](hg, n, capacity, seed=0,
                                    max_moves=max_moves, initial=initial)
            timed_out = False
        else:
            pl, timed_out = _run_with_budget(
                lambda: ALGORITHMS["lmbr"](
                    hg, n, capacity, seed=0, max_moves=max_moves,
                    initial=initial,
                ),
                budget,
            )
        dt = time.perf_counter() - t0
    finally:
        flags.reset()
    return pl, dt, timed_out


def _tier_rows(tier, hg, n, capacity, max_moves, ref_budget=None):
    rows = []
    initial = _warm_start(hg, n, capacity)
    vec_pl, t_vec, _ = _time_engine(
        hg, n, capacity, max_moves, initial, "baseline"
    )
    ref_pl, t_ref, ref_out = _time_engine(
        hg, n, capacity, max_moves, initial, "peelreference+lmbrcache0",
        budget=ref_budget,
    )
    identical = None
    if ref_pl is not None:
        identical = bool((ref_pl.member == vec_pl.member).all())
        if not identical:  # hard gate, -O-proof: never emit diverged rows
            raise AssertionError(
                f"{tier}: vectorized LMBR diverged from reference"
            )
    avg_span = round(float(spans_for_workload(hg, vec_pl).mean()), 4)
    stats = vec_pl.stats or {}
    ref_stats = (ref_pl.stats or {}) if ref_pl is not None else {}
    rows.append(dict(
        tier=tier, engine="reference-peel",
        seconds=round(t_ref, 2), speedup=1.0,
        infeasible=bool(ref_out), identical=identical,
        # a timed-out reference produced no placement: report nothing for it
        avg_span=avg_span if ref_pl is not None else None,
        moves=ref_stats.get("moves"), gain_calls=None, cache_hits=None,
    ))
    base = dict(
        tier=tier, infeasible=False, identical=identical, avg_span=avg_span,
        moves=stats.get("moves"), gain_calls=stats.get("gain_calls"),
    )
    rows.append(dict(
        base, engine="vectorized", seconds=round(t_vec, 2),
        speedup=round(t_ref / max(t_vec, 1e-9), 1),
        cache_hits=stats.get("gain_cache_hits"),
    ))
    # cache ablation: vectorized peel, epoch cache off
    nc_pl, t_nc, _ = _time_engine(
        hg, n, capacity, max_moves, initial, "lmbrcache0"
    )
    if not (nc_pl.member == vec_pl.member).all():
        raise AssertionError(f"{tier}: gain cache changed the placement")
    rows.append(dict(
        base, engine="vectorized-nocache", seconds=round(t_nc, 2),
        speedup=round(t_ref / max(t_nc, 1e-9), 1), cache_hits=0,
    ))
    for r in rows:
        print(f"  {r}", flush=True)
    return rows


def run(quick: bool = True) -> list[dict]:
    from repro.core.setcover import _accel_backend

    _accel_backend()  # pay JAX's one-time backend start-up outside the timings
    rows = []
    # fig6 quick tier: paper Random defaults, bounded move budget
    wl = random_workload(1000, 4000, 3, 11, 20, seed=0)
    rows += _tier_rows("fig6-quick", wl.hypergraph, 40, 50,
                       max_moves=120 if quick else 300)
    # fig9 quick tier: ibm01-like circuit at fig. 9 settings
    wl = ispd_like_workload(num_nodes=12752, seed=0)
    capacity = int(np.ceil(12752 / 20))
    rows += _tier_rows("fig9-quick", wl.hypergraph, 35, capacity,
                       max_moves=60 if quick else 150)
    # stress tier: reference under a wall-clock budget
    wl = lmbr_stress_workload()
    rows += _tier_rows(
        "lmbr-stress", wl.hypergraph,
        LMBR_STRESS_DEFAULTS["num_partitions"],
        LMBR_STRESS_DEFAULTS["capacity"],
        max_moves=LMBR_STRESS_DEFAULTS["max_moves"],
        ref_budget=REF_BUDGET_QUICK if quick else REF_BUDGET_FULL,
    )
    emit_csv("bench_lmbr", rows,
             ["tier", "engine", "seconds", "speedup", "infeasible",
              "identical", "avg_span", "moves", "gain_calls", "cache_hits"])
    save_json("BENCH_lmbr", rows)
    return rows


if __name__ == "__main__":
    run(quick=True)
