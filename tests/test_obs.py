"""Tests for the observability layer (repro.obs): metrics registry,
structured tracer, level selection, hot-path instrumentation, and the
"observation changes nothing" contract."""

import json

import numpy as np
import pytest

from repro import flags, obs
from repro.core import (
    ALGORITHMS,
    Hypergraph,
    PlacementService,
    Simulator,
    hpa,
    random_workload,
)
from repro.obs import (
    NULL_REGISTRY,
    NULL_TRACER,
    Registry,
    Tracer,
    parse_prom_text,
)


@pytest.fixture(autouse=True)
def _clean_obs():
    flags.reset()
    obs.reset()
    yield
    flags.reset()
    obs.reset()


# ---------------------------------------------------------------- registry
def test_counter_and_labels():
    reg = Registry()
    reg.inc("queries_total")
    reg.inc("queries_total", 4.0)
    reg.inc("queries_total", backend="device")
    snap = reg.snapshot()
    assert snap["queries_total"] == 5.0
    assert snap['queries_total{backend="device"}'] == 1.0


def test_gauge_set_and_add():
    reg = Registry()
    reg.set("inflight", 3.0)
    reg.gauge("inflight").add(-1.0)
    assert reg.snapshot()["inflight"] == 2.0


def test_gauge_vector_live_reference_copied_at_snapshot():
    reg = Registry()
    load = np.zeros(3)
    reg.gauge_vector("part_load").set(load)
    load[1] = 7.0  # mutate AFTER set: snapshot must see the live value
    snap = reg.snapshot()
    assert snap['part_load{index="1"}'] == 7.0
    assert snap['part_load{index="0"}'] == 0.0


def test_histogram_cumulative_buckets():
    reg = Registry()
    h = reg.histogram("lat", buckets=(0.001, 0.01, 0.1))
    for x in (0.0005, 0.005, 0.005, 0.05, 5.0):
        h.observe(x)
    snap = reg.snapshot()
    assert snap['lat_bucket{le="0.001"}'] == 1.0
    assert snap['lat_bucket{le="0.01"}'] == 3.0
    assert snap['lat_bucket{le="0.1"}'] == 4.0
    assert snap['lat_bucket{le="+Inf"}'] == 5.0
    assert snap["lat_count"] == 5.0
    assert snap["lat_sum"] == pytest.approx(5.0605)


def test_kind_conflict_rejected():
    reg = Registry()
    reg.inc("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")


def test_prom_text_round_trip_exact():
    reg = Registry()
    reg.inc("a_total", 3.5)
    reg.inc("a_total", 1.0, shape="B64.N128")
    reg.set("g", -0.125)
    reg.gauge_vector("vec").set([1.0, 2.0])
    reg.observe("h", 0.0123)
    reg.observe("h", 7.7)
    snap = reg.snapshot()
    assert parse_prom_text(reg.to_prom_text()) == snap
    # TYPE lines present once per metric family
    text = reg.to_prom_text()
    assert text.count("# TYPE a_total counter") == 1
    assert "# TYPE h histogram" in text


def test_null_registry_is_inert():
    assert NULL_REGISTRY.active is False
    NULL_REGISTRY.inc("x", 5.0)
    NULL_REGISTRY.observe("y", 1.0)
    NULL_REGISTRY.gauge_vector("z").set([1.0])
    assert NULL_REGISTRY.snapshot() == {}
    assert NULL_REGISTRY.to_prom_text() == ""


# ------------------------------------------------------------------ tracer
def test_span_nesting_by_containment():
    tr = Tracer()
    with tr.span("outer", k=1):
        with tr.span("inner"):
            pass
    inner, outer = tr.events  # inner exits (and records) first
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert outer["ph"] == "X" and inner["ph"] == "X"
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert outer["args"] == {"k": 1, "id": 1, "parent": None, "fit": None}
    assert inner["args"] == {"id": 2, "parent": 1, "fit": None}


def test_instant_and_counter_events():
    tr = Tracer()
    tr.event("drift.fire", ratio=1.3)
    tr.counter("online", served=10, inflight=2)
    kinds = [e["ph"] for e in tr.events]
    assert kinds == ["i", "C"]
    assert tr.events[0]["s"] == "t"
    assert tr.events[1]["args"] == {"served": 10, "inflight": 2}


def test_chrome_trace_and_jsonl_serialise():
    tr = Tracer()
    with tr.span("a"):
        pass
    tr.event("b")
    doc = json.loads(tr.to_chrome_trace())
    assert doc["displayTimeUnit"] == "ms"
    assert [e["name"] for e in doc["traceEvents"]] == ["a", "b"]
    lines = tr.to_jsonl().splitlines()
    assert len(lines) == 2
    assert json.loads(lines[0])["name"] == "a"


def test_spans_filter_and_clear():
    tr = Tracer()
    with tr.span("x"):
        pass
    tr.event("x")
    assert len(tr.spans("x")) == 1
    assert len(tr.spans()) == 1
    tr.clear()
    assert tr.events == [] and tr.spans() == []


def test_null_tracer_is_inert():
    assert NULL_TRACER.active is False
    with NULL_TRACER.span("x"):
        pass
    NULL_TRACER.event("y")
    assert NULL_TRACER.events == ()
    assert json.loads(NULL_TRACER.to_chrome_trace()) == {"traceEvents": []}


def test_begin_end_set_and_exception_unwinding():
    tr = Tracer()
    outer = tr.span("outer", a=1).begin()
    with pytest.raises(RuntimeError):
        with tr.span("mid"):
            tr.span("left.open").begin()  # never ended: an exception
            raise RuntimeError("boom")
    tr.event("mark")
    outer.set(b=2)
    outer.end(c=3)
    mid, mark, out = tr.events
    assert mid["args"]["parent"] == out["args"]["id"]
    assert mark["ph"] == "i" and mark["args"]["parent"] == out["args"]["id"]
    assert {k: out["args"][k] for k in "abc"} == {"a": 1, "b": 2, "c": 3}
    assert tr._stack == []  # the span left open ended with its parent
    # the null span takes the same calls
    sp = NULL_TRACER.span("x").begin()
    sp.set(k=1)
    sp.end(k=2)


def test_fit_number_and_complete_parent():
    tr = Tracer()
    with tr.span("service.fit"):
        with tr.span("fit.lmbr") as lm:
            t_in = lm.t0 + 1e-9
        with tr.span("service.refit"):  # nested request: same fit number
            pass
    with tr.span("service.fit") as second:
        import time
        tr.complete("late", second.t0 - 1.0, time.perf_counter())
        tr.complete("inner", t_in + 1.0, time.perf_counter())
    tr.complete("outside", 0.0, 1.0)
    ev = {e["name"] + str(e["args"]["fit"]): e["args"] for e in tr.events}
    assert ev["fit.lmbr1"]["fit"] == 1 and ev["service.refit1"]["fit"] == 1
    assert ev["service.fit2"]["parent"] is None
    # a complete event's parent is the innermost span open before its t0
    assert ev["lateNone"]["parent"] is None
    assert ev["inner2"]["parent"] == ev["service.fit2"]["id"]
    assert ev["outsideNone"]["parent"] is None
    ids = [e["args"]["id"] for e in tr.events]
    assert len(set(ids)) == len(ids)


def test_counter_events_carry_only_their_series():
    tr = Tracer()
    with tr.span("s"):
        tr.counter("online", served=1)
    assert tr.events[0]["args"] == {"served": 1}


# --------------------------------------------------- level selection / flags
def test_level_selection():
    assert obs.registry() is NULL_REGISTRY
    assert obs.tracer() is NULL_TRACER
    flags.FLAGS["obs_level"] = "counters"
    assert obs.registry().active and obs.tracer() is NULL_TRACER
    flags.FLAGS["obs_level"] = "trace"
    assert obs.registry().active and obs.tracer().active


def test_obs_flag_variants():
    flags.set_variant("obstrace")
    assert flags.FLAGS["obs_level"] == "trace"
    flags.set_variant("obscounters")
    assert flags.FLAGS["obs_level"] == "counters"
    flags.set_variant("obsoff")
    assert flags.FLAGS["obs_level"] == "off"
    flags.set_variant("obssnap100")
    assert flags.FLAGS["obs_snapshot_every"] == 100
    with pytest.raises(ValueError):
        flags.set_variant("obsbogus")
    with pytest.raises(ValueError):
        flags.set_variant("obssnap-5")


def test_timed_always_measures_trace_only_when_tracing():
    with obs.timed("work") as t:
        sum(range(1000))
    assert t.seconds > 0.0
    assert obs.tracer().spans() == []  # off: no event recorded
    flags.FLAGS["obs_level"] = "trace"
    with obs.timed("work", stage="x") as t:
        pass
    spans = obs.tracer().spans("work")
    assert len(spans) == 1
    assert spans[0]["args"] == {"stage": "x", "id": 1, "parent": None,
                                "fit": None}
    assert t.seconds >= 0.0


# --------------------------------------------- observation changes nothing
def _summary_no_wall_clock(res):
    return {k: v for k, v in res.summary().items() if k != "placement_s"}


def test_off_vs_trace_bit_identical_fit_and_serve():
    wl = random_workload(num_items=120, num_queries=300, density=5, seed=4)
    sim = Simulator(8, 32)

    base = sim.run_online(wl.hypergraph, ALGORITHMS["lmbr"], name="lmbr",
                          seed=0, max_moves=40)
    base_plan = _small_fit()
    for level in ("counters", "trace"):
        flags.FLAGS["obs_level"] = level
        obs.reset()
        traced = sim.run_online(wl.hypergraph, ALGORITHMS["lmbr"],
                                name="lmbr", seed=0, max_moves=40)
        assert np.array_equal(base.spans, traced.spans)
        assert np.array_equal(base.access_load, traced.access_load)
        assert _summary_no_wall_clock(base) == _summary_no_wall_clock(traced)
        plan = _small_fit()
        assert np.array_equal(plan.member, base_plan.member)
        assert plan.stats == base_plan.stats
    # and the traced run actually produced spans
    assert obs.tracer().spans("fit.lmbr")
    assert obs.tracer().spans("serve.microbatch")


# ------------------------------------------------- the fit's span tree
FIT_TREE = {
    "service.fit": {"fit.lmbr"},
    "fit.lmbr": {"fit.hpa", "lmbr.init", "lmbr.gain", "lmbr.refresh"},
    "fit.hpa": {"fit.hpa.coarsen", "fit.hpa.refine"},
    "lmbr.init": {"cover.batch"},
    "lmbr.refresh": {"cover.batch"},
}


def _small_fit():
    wl = random_workload(num_items=120, num_queries=300, density=5, seed=4)
    qs = [wl.hypergraph.edge(e) for e in range(wl.hypergraph.num_edges)]
    with hpa.fresh_partition_cache():
        return PlacementService("lmbr", seed=0).fit(qs, 120, 8, 32)


def _tree_edges(events):
    """(parent name, child name) pairs of the tracer's events."""
    by_id = {e["args"]["id"]: e for e in events}
    return {(by_id[e["args"]["parent"]]["name"], e["name"])
            for e in events if e["args"]["parent"] is not None}


def test_fit_span_tree_and_parents_agree_with_containment():
    flags.FLAGS["obs_level"] = "trace"
    obs.reset()
    plan = _small_fit()
    ev = obs.tracer().spans()
    by_id = {e["args"]["id"]: e for e in ev}
    got: dict = {}
    for a, b in _tree_edges(ev):
        got.setdefault(a, set()).add(b)
    assert got == FIT_TREE
    (fit,) = obs.tracer().spans("service.fit")
    assert fit["args"]["moves"] == plan.stats["moves"] > 0
    assert fit["args"]["gain_calls"] == plan.stats["gain_calls"] > 0
    for e in ev:
        assert e["args"]["fit"] == 1
        p = by_id.get(e["args"]["parent"])
        if p is None:
            assert e is fit
            continue
        assert p["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= p["ts"] + p["dur"] + 1e-3
    # the ids give the tree containment gives
    stripped = [{**e, "args": {}} for e in ev]
    assert obs.critical_path(stripped)[0].name == "service.fit"
    assert ([n.name for n in obs.critical_path(ev)]
            == [n.name for n in obs.critical_path(stripped)])
    gain = obs.tracer().spans("lmbr.gain")
    assert sum(g["args"]["pairs"] for g in gain) == plan.stats["gain_calls"]
    assert (sum(g["args"]["cache_hits"] for g in gain)
            == plan.stats["gain_cache_hits"] + plan.stats["gain_fp_hits"])
    for c in obs.tracer().spans("cover.batch"):
        assert c["args"]["edges"] > 0 and c["args"]["host_rounds"] > 0
        assert c["args"]["accel_gain_rounds"] == 0  # CPU sizes stay numpy


def test_spans_mirror_into_the_profiler_trace(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    flags.FLAGS["obs_level"] = "trace"
    obs.reset()
    with jax.profiler.trace(str(tmp_path)):
        _small_fit()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    names = {e["name"] for e in obs.tracer().spans()}
    host = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events if ev.name in names]
    count = {n: sum(h[0] == n for h in host) for n in names}
    assert count == {n: len(obs.tracer().spans(n)) for n in names}
    # nesting by containment on the profiler's clock = the tracer's tree
    host.sort(key=lambda h: (h[1], -h[2]))
    edges, stack = set(), []
    for name, s, e in host:
        while stack and s >= stack[-1][2]:
            stack.pop()
        if stack:
            edges.add((stack[-1][0], name))
        stack.append((name, s, e))
    assert edges == _tree_edges(obs.tracer().spans())


def test_fresh_jit_inside_a_span_records_one_compile():
    import jax

    flags.FLAGS["obs_level"] = "trace"
    obs.reset()
    tr = obs.tracer()

    def fresh_program(x):
        return x * 3.0 + 17.25

    with tr.span("outer"):
        with tr.span("compiles") as sp:
            jax.jit(fresh_program)(np.ones(7, np.float32))
    compiles = tr.spans("jit.compile")
    assert len(compiles) == 1
    assert compiles[0]["args"]["parent"] == sp.id
    assert "fresh_program" in compiles[0]["args"]["program"]
    flags.FLAGS["obs_level"] = "counters"  # the listener checks the level
    jax.jit(lambda x: x - 5.5)(np.ones(3, np.float32))
    assert len(obs._TRACER.spans("jit.compile")) == 1


# ------------------------------------------- end-to-end acceptance trace
def test_full_lifecycle_trace_and_prom_round_trip():
    """fit -> serve -> outage -> drift refit -> paced migration, traced:
    the Chrome trace must cover fit phases, router microbatches, the drift
    refit, and EVERY migration transfer; the registry must round-trip
    through the Prometheus text format."""
    old = random_workload(num_items=120, num_queries=500, density=6, seed=2)
    new = random_workload(num_items=120, num_queries=500, density=6, seed=9)
    trace = Hypergraph.from_edges(
        [old.hypergraph.edge(e) for e in range(200)]
        + [new.hypergraph.edge(e) for e in range(500)],
        num_nodes=120,
    )
    target = ALGORITHMS["lmbr"](old.hypergraph, 10, 30, seed=1, max_moves=40)
    flags.set_variant("driftw128+driftth1.1+routermb64+obstrace+obssnap100")
    flags.FLAGS["migration_bandwidth"] = 5.0
    obs.reset()
    sim = Simulator(10, 30)
    res = sim.run_online(
        old.hypergraph, ALGORITHMS["hpa"], name="hpa+drift", trace=trace,
        events=[(20, "down", 3), (60, "up", 3), (100, "migrate", target)],
        service=PlacementService("lmbr", seed=0), refit_moves=128, seed=0,
    )
    s = res.summary()
    tr = obs.tracer()

    # fit phases: hpa coarsen/refine under the top-level fit span
    assert tr.spans("fit.place") and tr.spans("fit.hpa")
    assert tr.spans("fit.hpa.coarsen") and tr.spans("fit.hpa.refine")
    # serving: one complete event per routed microbatch
    assert len(tr.spans("serve.microbatch")) > 0
    # drift fired and the refit was traced
    assert s["drift_fires"] >= 1
    assert tr.spans("drift.refit") and tr.spans("fit.lmbr")
    # failover events
    names = [e["name"] for e in tr.events]
    assert "failover.down" in names and "failover.up" in names
    # every migration transfer landed as a complete event
    assert s["migrations"] >= 1
    assert len(tr.spans("migration.transfer")) == s["migration_copies"]
    # periodic snapshots emitted as counter events
    snaps = [e for e in tr.events
             if e["ph"] == "C" and e["name"] == "online.snapshot"]
    assert len(snaps) >= 1
    assert s["served_queries"] >= 100  # snapshots had a chance to fire

    # the whole thing is valid Chrome trace JSON
    doc = json.loads(tr.to_chrome_trace())
    assert {e["name"] for e in doc["traceEvents"]} >= {
        "fit.hpa", "serve.microbatch", "migration.transfer"}

    # registry round-trips through the text exposition exactly
    reg = obs.registry()
    snap = reg.snapshot()
    assert snap["migration_copies_total"] == s["migration_copies"]
    assert snap["router_plan_swaps_total"] == s["plan_swaps"]
    assert parse_prom_text(reg.to_prom_text()) == snap


def test_migration_stats_canonical_only():
    """Executor stats carry ONLY the canonical migration_-prefixed keys;
    the deprecated bare transferred/wasted aliases (scheduled for removal
    after one release in PR 9) are gone."""
    from repro.online.migration import MigrationExecutor, plan_migration

    from repro.core.setcover import Placement

    wl = random_workload(num_items=80, num_queries=200, density=5, seed=1)
    src = ALGORITHMS["hpa"](wl.hypergraph, 8, 24, seed=0)
    dst = ALGORITHMS["lmbr"](wl.hypergraph, 8, 24, seed=0, max_moves=30)
    plan = plan_migration(src.member, dst.member,
                          wl.hypergraph.node_weights, bandwidth=4.0)
    live = Placement(src.member.copy(), 24, wl.hypergraph.node_weights)
    ex = MigrationExecutor(plan, live)
    while not ex.done:
        ex.advance(1)
    assert "transferred" not in ex.stats
    assert "wasted" not in ex.stats
    assert ex.stats["migration_transferred"] > 0.0
    assert ex.stats["migration_wasted"] >= 0.0


# ------------------------------------------------- prom exposition edge cases
def test_prom_label_value_escaping_round_trip():
    reg = Registry()
    reg.inc("esc_total", 1.0, path=r"C:\tmp\x")          # backslash
    reg.inc("esc_total", 2.0, msg='he said "hi"')        # quote
    reg.inc("esc_total", 3.0, text="line1\nline2")       # newline
    reg.inc("esc_total", 4.0, q="a b c")                 # spaces
    text = reg.to_prom_text()
    assert r'path="C:\\tmp\\x"' in text
    assert r'msg="he said \"hi\""' in text
    assert r'text="line1\nline2"' in text
    assert "\nline2" not in text.replace(r"\n", "")  # stays one line
    assert parse_prom_text(text) == reg.snapshot()


def test_prom_empty_registry_round_trip():
    reg = Registry()
    assert reg.snapshot() == {}
    assert reg.to_prom_text() == ""
    assert parse_prom_text("") == {}
    assert parse_prom_text(reg.to_prom_text()) == reg.snapshot()


def test_prom_histogram_inf_bucket_and_boundary():
    reg = Registry()
    h = reg.histogram("edge_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)   # first bucket
    h.observe(0.1)    # boundary: bisect_left counts it IN le="0.1"
    h.observe(50.0)   # beyond the last bound: +Inf only
    snap = reg.snapshot()
    assert snap['edge_seconds_bucket{le="0.1"}'] == 2.0
    assert snap['edge_seconds_bucket{le="1.0"}'] == 2.0  # cumulative
    assert snap['edge_seconds_bucket{le="+Inf"}'] == 3.0
    assert snap["edge_seconds_count"] == 3.0
    assert snap["edge_seconds_sum"] == 50.15
    assert parse_prom_text(reg.to_prom_text()) == snap
