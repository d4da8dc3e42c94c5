"""Cold fits back to back: each step is ``PlacementService("lmbr").fit`` of
one trace, inside a fresh HPA partition memo (so no fit reuses another's
partition), then the program's spans of the fitted plan over the same
trace (``batched_spans_csr``), as a user reads a plan's average span.  The
mix's ``traces`` distinct traces, generated from the seed, are cycled; the
window runs whole fits until ``--seconds`` have passed.

The check covers every plan of the window: capacity and placement by the
reference's own arithmetic, the program's spans against the per-query
greedy cover, and the average span's excess over 1 against the
configuration's ``excess_span`` limit (a plan LMBR did not replicate
reads several times the limit)."""

from __future__ import annotations

import time

import numpy as np

from ..gen import lists_to_csr, reference
from ..harness import Check

STREAM_WARM = 9
STREAM_TRACE0 = 10


def _trace(run, stream: int):
    cfg, gen = run.config, run.gen
    with run.annotate("generate"):
        lists = gen.query_lists(cfg, run.seed, stream, int(cfg["num_queries"]))
    ptr, nodes = lists_to_csr(lists)
    return lists, ptr, nodes


def _fit(run, lists, ptr, nodes):
    from repro.core import PlacementService, hpa
    from repro.core.setcover import batched_spans_csr

    cfg = run.config
    with run.annotate("fit"), hpa.fresh_partition_cache():
        plan = PlacementService("lmbr", seed=run.seed).fit(
            lists, int(cfg["num_items"]), int(cfg["num_partitions"]),
            float(cfg["capacity"]), node_weights=run.state["weights"])
        spans = batched_spans_csr(ptr, nodes, plan.member)
    return plan, spans


def setup(run) -> None:
    from repro.core.setcover import batched_spans_csr

    cfg = run.config
    t0 = time.perf_counter()
    run.state["weights"] = run.gen.node_weights(cfg, run.seed)
    run.state["traces"] = [_trace(run, STREAM_TRACE0 + i)
                           for i in range(int(run.traffic["traces"]))]
    warm = _trace(run, STREAM_WARM)
    t1 = time.perf_counter()
    plan, _ = _fit(run, *warm)
    # the span engine's accelerated gain rounds pad the active queries to
    # a power of two: warm each padded size a trace of this length can use
    size = len(warm[1]) - 1
    while size >= 1:
        batched_spans_csr(warm[1][: size + 1], warm[2][: warm[1][size]],
                          plan.member)
        size //= 2
    run.note(f"fit: {len(run.state['traces'])} traces of "
             f"{cfg['num_queries']} queries, warm fit made "
             f"{plan.stats['moves']} moves; setup parts: traces "
             f"{t1 - t0:.3f} s, warm fit and spans "
             f"{time.perf_counter() - t1:.3f} s")


def window(run) -> None:
    traces = run.state["traces"]
    fits = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < run.seconds:
        t = len(fits) % len(traces)
        plan, spans = _fit(run, *traces[t])
        fits.append((t, plan.member, spans, plan.stats))
    elapsed = time.perf_counter() - t0
    run.window.update(fits=len(fits), elapsed_s=elapsed, attempted=len(fits),
                      plans=fits)
    moves = [f[3]["moves"] for f in fits]
    run.note(f"fit loop: {len(fits)} cold fits in {elapsed:.3f} s, "
             f"moves {min(moves)}-{max(moves)}, replication factor "
             f"{np.mean([f[1].sum() / f[1].shape[1] for f in fits]):.4f}")


def check(run):
    cfg, st = run.config, run.state
    weights = st["weights"]
    over = unplaced = span_bad = 0
    excess = 0.0
    failed = 0
    for t, member, spans, _ in run.window["plans"]:
        lists = st["traces"][t][0]
        member_t = np.ascontiguousarray(member.T)
        ref = np.array([len(reference.greedy_cover(q, member_t))
                        for q in lists])
        o = reference.over_capacity(member, weights, float(cfg["capacity"]))
        u = reference.unplaced(member, weights)
        s = (int((np.asarray(spans) != ref).sum())
             if len(spans) == len(ref) else len(ref))
        x = float(ref.mean()) - 1.0
        over, unplaced, span_bad = over + o, unplaced + u, span_bad + s
        excess = max(excess, x)
        failed += bool(o or u or s or x > cfg["limits"]["excess_span"])
    run.window["failed"] = failed
    return [
        Check("over_capacity", over, 0),
        Check("unplaced", unplaced, 0),
        Check("span_mismatch", span_bad, 0),
        Check("excess_span", excess, float(cfg["limits"]["excess_span"])),
    ]
