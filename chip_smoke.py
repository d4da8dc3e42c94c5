"""Bring-up smoke run of the fit -> serve path on one TPU chip.

Drives the placement engine through the entry points a user calls —
`PlacementService("lmbr").fit`, then `Simulator.run_online` (which serves
through `ReplicaRouter`) — on the web-scale tier (`WEB_SCALE_DEFAULTS`:
256 partitions, 2-8-pin queries, 2% cross-cluster seam, power-law
clusters), generated from ``--seed`` and cut in scale only.  Every phase
runs twice, once with the device paths pinned and once with the host
engine pinned, and the two must agree bit for bit:

  * fit:   span-gain / cover-round-loop / lockstep-peel device paths
           (``span_backend="pallas"``, ``span_round_backend="device"``,
           ``lmbr_peel="pallas"``) against numpy / numpy / vector: identical
           member matrices and avg_span, and fit spans equal to the
           per-query greedy cover reference on a sample;
  * serve: the trace streamed with a partition down/up pair, the gain
           rounds on the Pallas span-gain kernel against numpy: identical
           spans, pin attributions and serving counters.

The engine counters must show that each pinned device path ran: the
cover round loop and the lockstep-peel kernel in the device fit, the
span-gain kernel in the device serve.  No exception is caught; any failed
check exits non-zero.  The wall times printed are smoke timings of one
run, not benchmark results.

Size: the tier's 100k items cut to ITEMS (capacity and cluster count cut
with them), a fit on the first FIT_QUERIES queries and a stream of
SERVE_QUERIES.  On one v5e chip the whole run took 329 s at 10k / 50k /
200k and about 505 s at 15k / 75k / 300k, both fits included (the fit
grows about 1.5x per 1.5x of items and fit queries), so 15k / 75k / 300k
is the largest such cut that stays within ten minutes.

Needs a TPU: with no accelerator it exits non-zero before any phase.
Usage: ``python chip_smoke.py [--seed S]``.  The last line of stdout is
one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))

FIT_DEVICE = dict(span_backend="pallas", span_round_backend="device",
                  lmbr_peel="pallas")
FIT_HOST = dict(span_backend="numpy", span_round_backend="numpy",
                lmbr_peel="vector")
SERVE_DEVICE = dict(span_backend="pallas", span_round_backend="numpy")
SERVE_HOST = dict(span_backend="numpy", span_round_backend="numpy")
ITEMS = 15_000
FIT_QUERIES = 75_000
SERVE_QUERIES = 300_000
REFERENCE_SAMPLE = 2000  # fit queries checked against greedy_set_cover


class CompileClock:
    """Sums JAX's backend-compile durations and persistent-cache hits."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits


def require(ok: bool, what: str) -> None:
    """A smoke check; unlike `assert` it also runs under ``python -O``."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated trace and of the fit")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = _parse(argv)
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    clock = CompileClock(jax)
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    run_phases(args.seed, clock)
    print(f"peak device bytes in use: "
          f"{dev.memory_stats()['peak_bytes_in_use']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


def run_phases(seed: int, clock: CompileClock) -> None:
    import numpy as np

    from repro import flags
    from repro.core import (
        WEB_SCALE_DEFAULTS,
        Hypergraph,
        Placement,
        PlacementService,
        Simulator,
        greedy_set_cover,
        web_scale_workload,
    )
    from repro.core import hpa
    from repro.core.setcover import batched_spans_csr, engine_counters

    # ---------------------------------------------------------- workload
    ws = WEB_SCALE_DEFAULTS
    cut = ws["num_items"] / ITEMS
    n = ws["num_partitions"]
    capacity = ws["capacity"] / cut
    clusters = max(1, round(ws["num_clusters"] / cut))
    trace = web_scale_workload(
        num_items=ITEMS, num_queries=SERVE_QUERIES,
        num_clusters=clusters, seed=seed,
    ).hypergraph
    fit_queries = [trace.edge(e) for e in range(FIT_QUERIES)]
    ptr = trace.edge_ptr[: FIT_QUERIES + 1]
    nodes = trace.edge_nodes[: ptr[-1]]
    fit_hg = Hypergraph(ptr, nodes, trace.node_weights,
                        trace.edge_weights[: FIT_QUERIES])
    headroom = n * capacity / trace.total_node_weight()
    print(
        f"workload: web-scale tier cut {cut:g}x in items "
        f"({ws['num_items']} -> {ITEMS}) and clusters "
        f"({ws['num_clusters']} -> {clusters}); fit on the first "
        f"{FIT_QUERIES} queries, serve {trace.num_edges} "
        f"(tier: {ws['num_queries']}); {n} partitions, capacity "
        f"{capacity:g} ({headroom:.2f}x the item weight); "
        f"{ws['min_query']}-{ws['max_query']}-pin queries, "
        f"cross-cluster seam {ws['cross_frac']}; seed {seed}"
    )

    def phase(name, setup, fn):
        flags.reset()
        flags.FLAGS.update(setup)
        c0, e0 = clock.snapshot(), engine_counters()
        t0 = time.perf_counter()
        out = fn()
        dt = time.perf_counter() - t0
        c1, e1 = clock.snapshot(), engine_counters()
        flags.reset()
        delta = {k: e1[k] - e0[k] for k in e0 if e1[k] != e0[k]}
        print(f"phase {name}: {dt:.3f} s wall (smoke timing, not a "
              f"benchmark); compile {c1[0] - c0[0]:.3f} s over "
              f"{c1[1] - c0[1]} programs, {c1[2] - c0[2]} cache hits; "
              f"engine counters {delta}")
        return out, delta

    # --------------------------------------------------------------- fit
    def fit():
        with hpa.fresh_partition_cache():
            plan = PlacementService("lmbr", seed=seed).fit(
                fit_queries, ITEMS, n, capacity
            )
        spans = batched_spans_csr(ptr, nodes, plan.member)
        return plan, spans

    (plan_d, spans_d), eng_fd = phase("fit-device", FIT_DEVICE, fit)
    (plan_h, spans_h), eng_fh = phase("fit-host", FIT_HOST, fit)
    st = plan_d.stats
    print("fit-device lmbr stats: " + ", ".join(
        f"{k}={st[k]}" for k in ("peel", "moves", "gain_calls", "peel_pairs",
                                 "dense_peel_calls", "dense_peel_skips",
                                 "int_exact")))
    require(st["peel"] == FIT_DEVICE["lmbr_peel"], "peel backend not pinned")
    require(st["int_exact"] is True, "web-scale weights left the f32 domain")
    require(st["dense_peel_calls"] > 0, "the lockstep-peel kernel never ran")
    require(st["dense_peel_skips"] == 0, "a pinned device peel was skipped")
    require(eng_fd.get("device_buckets", 0) > 0, "cover round loop never ran")
    require(eng_fd.get("host_buckets", 0) == 0, "a fit bucket ran on host")
    require(eng_fh.get("device_buckets", 0) == 0
            and plan_h.stats["dense_peel_calls"] == 0,
            "the host fit reached a device path")
    require(np.array_equal(plan_d.member, plan_h.member), "fit members differ")
    require(np.array_equal(spans_d, spans_h), "fit spans differ")
    avg_d, avg_h = float(spans_d.mean()), float(spans_h.mean())
    require(avg_d == avg_h, "fit avg_span differs")
    ref = [len(greedy_set_cover(q, plan_d.member))
           for q in fit_queries[:REFERENCE_SAMPLE]]
    require(np.array_equal(spans_d[:REFERENCE_SAMPLE], ref),
            "fit spans differ from the per-query greedy cover reference")
    print(f"fit: members identical ({plan_d.member.sum()} replicas, "
          f"rf {plan_d.member.sum() / ITEMS:.4f}), avg_span "
          f"{avg_d!r} on both engines, first {REFERENCE_SAMPLE} spans equal "
          f"the greedy cover reference")

    # ------------------------------------------------------------- serve
    # take down the partition holding the most single-copy items, so the
    # outage strands items and failover has to repair them
    single = plan_d.member & (plan_d.member.sum(axis=0) == 1)
    down = int(np.argmax(single.sum(axis=1)))
    nq = trace.num_edges
    events = [(nq // 3, "down", down), (2 * nq // 3, "up", down)]

    def serve():
        def fitted(hg, num_partitions, cap, **_):
            return Placement(plan_d.member.copy(), cap, hg.node_weights)

        return Simulator(n, capacity).run_online(
            fit_hg, fitted, name="lmbr-fitted", trace=trace, events=events,
        )

    res_d, eng_sd = phase("serve-device", SERVE_DEVICE, serve)
    res_h, eng_sh = phase("serve-host", SERVE_HOST, serve)
    kernel_rounds = f"{SERVE_DEVICE['span_backend']}_gain_rounds"
    require(eng_sd.get(kernel_rounds, 0) > 0, "the span-gain kernel never ran")
    require(eng_sd.get("numpy_gain_rounds", 0) == 0,
            "a device serve gain round ran on numpy")
    require(eng_sh.get(kernel_rounds, 0) == 0,
            "the host serve reached the kernel")
    require(np.array_equal(res_d.spans, res_h.spans), "served spans differ")
    require(np.array_equal(res_d.pin_parts, res_h.pin_parts),
            "pin attributions differ")
    require(res_d.online_stats == res_h.online_stats,
            "serving counters differ")
    served = res_d.online_stats["served_queries"]
    require(served + res_d.online_stats["degraded_queries"] == nq,
            "the serving ledger lost queries")
    print(f"serve: {served} queries served, "
          f"{res_d.online_stats['degraded_queries']} degraded, "
          f"{res_d.online_stats['repaired_items']} items repaired after "
          f"partition {down} went down; spans (avg {res_d.avg_span!r}) and "
          f"{len(res_d.pin_parts)} pin attributions identical on both "
          f"engines")
    s, c, h = clock.snapshot()
    print(f"compile total: {s:.3f} s over {c} programs, {h} persistent "
          f"cache hits")


if __name__ == "__main__":
    sys.exit(main())
