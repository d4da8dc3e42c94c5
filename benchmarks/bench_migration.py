"""Live plan migration benchmark: full plan swap under lmbr-stress traffic.

One section, one BENCH_migration.json: the lmbr-stress trace is served
through `Simulator.run_online` while the layout migrates from a random
placement onto a cold LMBR fit — the worst-case "full plan swap" diff
(thousands of copies AND drops).  Two runs:

  * ``instant`` — the legacy atomic hot-swap (``migration_bandwidth`` 0):
    the diff applies between two microbatches, data teleports for free.
    This is the span baseline the paced run's regret is measured against.
  * ``paced`` — the same swap streamed as bandwidth-paced replica
    transfers with union-layout serving (`repro.online.migration`).

Gates (AssertionError aborts the bench):

  * the paced run serves with ZERO degraded queries — union serving never
    loses routability mid-migration;
  * concurrent in-flight bytes never exceed the plan's declared
    ``inflight_bound`` (concurrency x distinct destinations x max copy);
  * the migration completes inside the trace and the final live matrix is
    BIT-IDENTICAL to the target plan (both runs);
  * capacity never exceeds ``capacity * (1 + migration_headroom)``.

``span_regret`` — the paced run's avg served span minus the instant
run's — is reported in the JSON (not gated: it is the price of moving
data at finite bandwidth, the quantity this subsystem exists to expose).

Emits benchmarks/results/BENCH_migration.json; see benchmarks/README.md
for the row schema.
"""

from __future__ import annotations

import time

import numpy as np

from repro.core import (
    ALGORITHMS,
    LMBR_STRESS_DEFAULTS,
    Placement,
    Simulator,
    lmbr_stress_workload,
)
from repro.online import plan_migration

from .common import emit_csv, save_json

KEYS = [
    "section", "engine", "seconds", "avg_span", "degraded", "copies",
    "drops", "transfer_gb", "wasted_gb", "max_inflight_gb",
    "inflight_bound_gb", "ticks", "span_regret", "bit_identical", "done",
]

MIGRATE_AT = 2000  # trace position of the migrate event
HEADROOM = 0.15
CONCURRENCY = 4


def _capture_fit(old: Placement):
    """A fit function returning a copy of ``old`` whose member matrix the
    bench keeps a handle on: `run_online`'s live layout SHARES it, so after
    the run the handle is the final live matrix (the bit-identity gate)."""
    state: dict = {}

    def fit(hg, n, cap, **kw):
        pl = Placement(old.member.copy(), old.capacity, old.node_weights)
        state["member"] = pl.member
        return pl

    return fit, state


def _one_run(sim, hg, old, mplan, engine: str):
    fit, state = _capture_fit(old)
    t0 = time.perf_counter()
    res = sim.run_online(hg, fit, name=f"migration-{engine}",
                         events=[(MIGRATE_AT, "migrate", mplan)])
    dt = time.perf_counter() - t0
    return res, state["member"], dt


def run(quick: bool = True) -> list[dict]:
    from repro.core.setcover import _accel_backend

    _accel_backend()  # pay JAX's one-time backend start-up outside the timings
    wl = lmbr_stress_workload()
    hg = wl.hypergraph
    n = LMBR_STRESS_DEFAULTS["num_partitions"]
    cap = LMBR_STRESS_DEFAULTS["capacity"]
    fit_moves = 300 if quick else LMBR_STRESS_DEFAULTS["max_moves"]

    old = ALGORITHMS["random"](hg, n, cap, seed=0)
    new = ALGORITHMS["lmbr"](hg, n, cap, seed=0, max_moves=fit_moves)
    w = hg.node_weights
    sim = Simulator(n, cap)

    base = plan_migration(old.member, new.member, node_weights=w,
                          bandwidth=0.0, concurrency=CONCURRENCY,
                          headroom=HEADROOM)
    # pace so the swap drains in well under the post-event trace slack
    ticks_left = hg.num_edges - MIGRATE_AT
    bandwidth = max(1.0, np.ceil(
        base.bytes_to_move(w) / (0.5 * ticks_left)
    ))
    paced = plan_migration(old.member, new.member, node_weights=w,
                           bandwidth=float(bandwidth),
                           concurrency=CONCURRENCY, headroom=HEADROOM)
    bound_gb = paced.inflight_bound(w) * sim.item_gb

    rows = []
    spans = {}
    for engine, mplan in (("instant", base), ("paced", paced)):
        res, final_member, dt = _one_run(sim, hg, old, mplan, engine)
        s = res.online_stats
        if not s["migration_done"]:
            raise AssertionError(
                f"{engine} migration did not complete inside the trace "
                f"(bandwidth {mplan.bandwidth}, {mplan.num_copies} copies)"
            )
        if engine == "paced" and s["degraded_queries"]:
            raise AssertionError(
                f"paced migration degraded {s['degraded_queries']} queries"
                " — union serving must never lose routability"
            )
        if s["migration_max_inflight_gb"] > bound_gb + 1e-9:
            raise AssertionError(
                f"in-flight bytes {s['migration_max_inflight_gb']} exceed "
                f"the declared bound {bound_gb}"
            )
        if not np.array_equal(final_member, new.member):
            raise AssertionError(
                f"{engine} final layout is not bit-identical to the target"
            )
        if not (res.loads <= cap * (1.0 + HEADROOM) + 1e-9).all():
            raise AssertionError(f"{engine} run violated the headroom bound")
        spans[engine] = float(res.spans.mean())
        rows.append(dict(
            section="migration", engine=engine, seconds=round(dt, 3),
            avg_span=round(spans[engine], 4),
            degraded=int(s["degraded_queries"]),
            copies=int(s["migration_copies"]),
            drops=int(s["migration_drops"]),
            transfer_gb=s["migration_transfer_gb"],
            wasted_gb=s["migration_wasted_gb"],
            max_inflight_gb=s["migration_max_inflight_gb"],
            inflight_bound_gb=round(bound_gb, 4),
            ticks=int(s["migration_ticks"]),
            span_regret=None, bit_identical=True,
            done=bool(s["migration_done"]),
        ))
    rows[-1]["span_regret"] = round(spans["paced"] - spans["instant"], 4)

    for r in rows:
        print(f"  {r}", flush=True)
    emit_csv("bench_migration", rows, KEYS)
    save_json("BENCH_migration", rows)
    return rows


if __name__ == "__main__":
    run(quick=True)
