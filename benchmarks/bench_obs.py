"""Observability overhead benchmark: the instrumentation must be free.

Three sections, one BENCH_obs.json:

  * identity — the same lmbr-stress serving trace routed under every
    ``obs_level`` ("off", "counters", "trace") must produce BIT-IDENTICAL
    covers (chosen partitions, spans, load ledger).  Observability hooks
    only read state; any divergence is a hard failure.
  * overhead — paired per-slice timing of `ReplicaRouter.route_csr` on the
    lmbr-stress trace, "off" vs each level interleaved (min across rounds
    on every side of a pair, median slice ratio).  Gates:
      - ``counters / off`` median ratio <= ``COUNTERS_GATE`` (1.03 — the
        3% budget from the issue),
      - ``off-hooks``: the disabled hook sequence (one accessor call plus
        ``.active`` checks per microbatch) is timed DIRECTLY in a tight
        loop and bounded against the median microbatch duration at
        <= ``OFF_GATE`` (1.005, the 0.5% budget) — wall-clock pairing on a
        shared CI container cannot resolve 0.5%, the hook loop can; an
        ``off-rerun`` wall-clock row is still reported (ungated) as the
        honest noise floor,
      - ``trace / off`` is reported but ungated (trace mode buys a full
        Chrome timeline; it is allowed to cost).
  * roundtrip — after the counters pass, ``parse_prom_text(to_prom_text())``
    must equal ``snapshot()`` exactly; after the trace pass, the Chrome
    trace JSON must parse and contain the serve.microbatch spans.
  * health — the PR 10 monitoring gates, on a ``run_online`` replay with a
    deterministic three-partition kill and a later repair:
      - ``storm``: the degraded-rate AND load-skew alerts must FIRE within
        one health window (``health_window`` snapshots) of the kill and
        RESOLVE after the repair,
      - ``clean``: the identical monitored replay without faults must fire
        ZERO alerts,
      - the storm replay's serving results (spans, access load) must stay
        bit-identical to the same replay with observability off —
        monitoring observes, it never steers.
    The counters-mode hot-path overhead of the monitoring release stays
    under the same ``COUNTERS_GATE`` as before (health work happens at
    snapshot cadence, not per microbatch).

Emits benchmarks/results/BENCH_obs.json; see benchmarks/README.md for the
row schema.
"""

from __future__ import annotations

import gc
import json
import time

import numpy as np

from repro import flags, obs
from repro.core import ALGORITHMS, LMBR_STRESS_DEFAULTS, lmbr_stress_workload
from repro.online import ReplicaRouter

from .common import emit_csv, save_json

KEYS = [
    "section", "level", "seconds", "qps", "ratio", "gate",
    "identical", "avg_span", "events", "series",
]

# counters-mode serving overhead ceiling (the issue's 3% budget).  The
# registry work per microbatch is two dict lookups, three counter
# increments and one histogram bisect — measured ~0.5-1% on the 1-core CI
# container; 1.03 keeps a regression loud without flaking.
COUNTERS_GATE = 1.03
# "off" budget (0.5%): gated analytically — per-microbatch hook cost from
# a tight loop over the exact disabled-path sequence, divided by the
# median measured microbatch duration.  The wall-clock off-rerun row is
# reported ungated because this container's slice noise floor (~2%) sits
# above the budget.
OFF_GATE = 1.005


def _time_slice(router, ptr, nodes, reps: int = 5) -> float:
    """min-of-``reps`` seconds for one ``route_csr`` slice."""
    ts = np.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        router.route_csr(ptr, nodes)
        ts = min(ts, time.perf_counter() - t0)
    return ts


def _full_route(member: np.ndarray, hg):
    """One whole-trace route on a fresh router (for identity checks)."""
    router = ReplicaRouter(member)
    batch = router.route_csr(hg.edge_ptr, hg.edge_nodes)
    return batch, router.load.copy()


def run(quick: bool = True) -> list[dict]:
    from repro.core.setcover import _accel_backend

    _accel_backend()  # pay JAX's one-time backend start-up outside the timings
    flags.reset()
    obs.reset()

    wl = lmbr_stress_workload()
    hg = wl.hypergraph
    n = LMBR_STRESS_DEFAULTS["num_partitions"]
    cap = LMBR_STRESS_DEFAULTS["capacity"]
    # serving overhead is layout-independent; a random layout keeps the
    # tier's fit cost out of the benchmark (same choice as bench_online)
    pl = ALGORITHMS["random"](hg, n, cap, seed=0)
    nq = hg.num_edges

    slice_q = 1000
    slices = []
    for lo in range(0, nq, slice_q):
        hi = min(lo + slice_q, nq)
        ptr = hg.edge_ptr[lo: hi + 1] - hg.edge_ptr[lo]
        nodes = hg.edge_nodes[hg.edge_ptr[lo]: hg.edge_ptr[hi]]
        slices.append((ptr, nodes))

    rows: list[dict] = []

    # -------------------------------------------------------- identity
    flags.FLAGS["obs_level"] = "off"
    base_batch, base_load = _full_route(pl.member, hg)
    for lvl in ("counters", "trace"):
        flags.FLAGS["obs_level"] = lvl
        obs.reset()
        batch, load = _full_route(pl.member, hg)
        same = (np.array_equal(batch.spans, base_batch.spans)
                and np.array_equal(batch.cover_parts, base_batch.cover_parts)
                and np.array_equal(batch.pin_parts, base_batch.pin_parts)
                and np.array_equal(load, base_load))
        if not same:
            raise AssertionError(f"obs_level={lvl!r} changed routing results")
        rows.append(dict(section="identity", level=lvl, identical=True,
                         avg_span=round(float(batch.spans.mean()), 4)))

    # -------------------------------------------------------- overhead
    # paired per-slice timing: every slice times ALL levels back to back
    # (min-of-5 each), so drift in machine speed between passes cancels
    # out of the ratios; the reported overhead is the median slice ratio
    # (same robustness choice as bench_online's router section)
    levels = ("off", "counters", "off-rerun", "trace")
    rounds = 4
    obs.reset()
    routers = {lvl: ReplicaRouter(pl.member) for lvl in levels}
    flags.FLAGS["obs_level"] = "off"
    for ptr, nodes in slices:  # warm-up: caches, allocator
        routers["off"].route_csr(ptr, nodes)
    per_slice: dict[str, list[float]] = {
        lvl: [np.inf] * len(slices) for lvl in levels}
    for _ in range(rounds):  # min across rounds rides out transient noise
        for i, (ptr, nodes) in enumerate(slices):
            gc.collect()
            for lvl in levels:
                flags.FLAGS["obs_level"] = lvl.replace("-rerun", "")
                t = _time_slice(routers[lvl], ptr, nodes, reps=2)
                per_slice[lvl][i] = min(per_slice[lvl][i], t)
    trace_events = len(obs.tracer().events)

    base_slices = per_slice["off"]
    base_total = float(sum(base_slices))
    gates = {"counters": COUNTERS_GATE, "off-rerun": None, "trace": None}
    rows.append(dict(section="overhead", level="off",
                     seconds=round(base_total, 3),
                     qps=round(nq / max(base_total, 1e-9)), ratio=1.0))

    # "off" gate: time the disabled hook sequence itself (what
    # _route_microbatch pays when obs_level == "off" — one registry()
    # accessor plus two .active checks, its serve.microbatch span and the
    # span engine's cover.batch span) and bound it against the median
    # microbatch duration
    flags.FLAGS["obs_level"] = "off"
    mb = int(flags.FLAGS["router_microbatch"])
    it = 200_000
    t_hook = np.inf
    for _ in range(3):
        gc.collect()
        t0 = time.perf_counter()
        for _ in range(it):
            reg = obs.registry()
            if reg.active:
                pass
            span = obs.tracer().span("serve.microbatch").begin()
            tr = obs.tracer()
            with tr.span("cover.batch", edges=mb) as sp:
                if tr.active:
                    sp.set()
            if reg.active:
                pass
            span.end()
        t_hook = min(t_hook, (time.perf_counter() - t0) / it)
    mb_per_slice = -(-slice_q // mb)
    med_slice = float(np.median(base_slices))
    off_ratio = 1.0 + t_hook * mb_per_slice / max(med_slice, 1e-9)
    if off_ratio > OFF_GATE:
        raise AssertionError(
            f"disabled hooks cost {off_ratio - 1.0:.5f} of a microbatch "
            f"> {OFF_GATE - 1.0} gate ({t_hook * 1e9:.0f} ns/hook)"
        )
    rows.append(dict(section="overhead", level="off-hooks",
                     seconds=round(t_hook * 1e9),  # ns per hook sequence
                     ratio=round(off_ratio, 6), gate=OFF_GATE))
    for lvl in ("counters", "off-rerun", "trace"):
        total = float(sum(per_slice[lvl]))
        ratios = [t / max(b, 1e-9)
                  for t, b in zip(per_slice[lvl], base_slices)]
        med = float(np.median(ratios))
        gate = gates[lvl]
        if gate is not None and med > gate:
            raise AssertionError(
                f"obs_level={lvl!r} median slice overhead {med:.4f}x "
                f"> {gate}x gate (slices: {[round(r, 3) for r in ratios]})"
            )
        rows.append(dict(section="overhead", level=lvl,
                         seconds=round(total, 3),
                         qps=round(nq / max(total, 1e-9)),
                         ratio=round(med, 4), gate=gate,
                         events=trace_events if lvl == "trace" else None))

    # -------------------------------------------------------- roundtrip
    flags.FLAGS["obs_level"] = "counters"
    obs.reset()
    _full_route(pl.member, hg)
    reg = obs.registry()
    snap = reg.snapshot()
    parsed = obs.parse_prom_text(reg.to_prom_text())
    if parsed != snap:
        missing = set(snap) ^ set(parsed)
        raise AssertionError(f"prometheus round-trip diverged: {missing}")
    rows.append(dict(section="roundtrip", level="counters",
                     series=len(snap), identical=True))

    flags.FLAGS["obs_level"] = "trace"
    obs.reset()
    _full_route(pl.member, hg)
    doc = json.loads(obs.tracer().to_chrome_trace())
    micro = [e for e in doc["traceEvents"]
             if e.get("name") == "serve.microbatch"]
    if not micro:
        raise AssertionError("trace mode produced no serve.microbatch spans")
    rows.append(dict(section="roundtrip", level="trace",
                     events=len(doc["traceEvents"]), identical=True))

    # ----------------------------------------------------------- health
    from repro.core import Simulator, random_workload
    from repro.obs import HealthMonitor

    hwl = random_workload(num_items=120, num_queries=4000, density=6, seed=2)
    kill_at, heal_at = 1000, 2500
    storm = [(kill_at, "down", 3), (kill_at, "down", 5),
             (kill_at, "down", 7), (heal_at, "repair", 1),
             (heal_at + 1, "up", 3), (heal_at + 1, "up", 5),
             (heal_at + 1, "up", 7)]
    snap_every, hw = 100, 4
    variant = (f"routermb64+obscounters+obssnap{snap_every}+obshealth1"
               f"+healthw{hw}+healthskew3.0")

    def _health_run(events, monitored: bool):
        flags.set_variant(variant if monitored else "routermb64")
        obs.reset()
        mon = HealthMonitor.from_flags() if monitored else None
        res = Simulator(10, 30).run_online(
            hwl.hypergraph, ALGORITHMS["hpa"], seed=0, events=list(events),
            auto_repair=False, health=mon,
        )
        return res, mon

    res_off, _ = _health_run(storm, monitored=False)
    res_storm, mon_storm = _health_run(storm, monitored=True)
    if not (np.array_equal(res_off.spans, res_storm.spans)
            and np.array_equal(res_off.access_load, res_storm.access_load)):
        raise AssertionError("health monitoring changed serving results")

    # snapshot index of the kill vs of each fire: both alerts must fire
    # within one health window (hw snapshots) of the kill, and resolve
    snap_t = mon_storm.store.series("online_served_queries").times()
    fires = {h["alert"]: h["t"] for h in mon_storm.history
             if h["kind"] == "fire"}
    kill_idx = int((snap_t < kill_at).sum())
    worst_lag = 0
    for rule in ("degraded_rate", "load_skew"):
        if rule not in fires:
            raise AssertionError(f"{rule} did not fire under the storm")
        lag = int((snap_t <= fires[rule]).sum()) - kill_idx
        worst_lag = max(worst_lag, lag)
        if lag > hw:
            raise AssertionError(
                f"{rule} fired {lag} snapshots after the kill "
                f"> {hw} (one health window)"
            )
        if mon_storm.alerts[rule].state != "ok":
            raise AssertionError(f"{rule} never resolved after the repair")
    rows.append(dict(section="health", level="storm", identical=True,
                     events=len(mon_storm.history), ratio=worst_lag,
                     gate=hw, series=len(snap_t)))

    _, mon_clean = _health_run([], monitored=True)
    if mon_clean.history:
        raise AssertionError(
            f"clean run fired alerts: {mon_clean.history}"
        )
    rows.append(dict(section="health", level="clean", identical=True,
                     events=0, series=mon_clean.stats["checks"]))

    flags.reset()
    obs.reset()

    for r in rows:
        print(f"  {r}", flush=True)
    emit_csv("bench_obs", rows, KEYS)
    save_json("BENCH_obs", rows)
    return rows


if __name__ == "__main__":
    run(quick=True)
