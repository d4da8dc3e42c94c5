"""Health monitoring layer: time-series store, SLO engine, trace analytics.

Covers the PR 10 tentpole — `repro.obs.timeseries` windowed aggregations,
`repro.obs.health` alert state machine / anomaly detection / run_online
integration, `repro.obs.analyze` span-tree analytics and the
tools/obs_report.py CLI — plus the router load-gauge rebind regression.
The observation-changes-nothing contract (monitored serving bit-identical
to off) is asserted here AND gated by benchmarks/bench_obs.py's health
section.
"""

import subprocess
import sys
import os

import numpy as np
import pytest

from repro import flags, obs
from repro.core import ALGORITHMS, Simulator, random_workload
from repro.obs import (
    HealthMonitor,
    SLORule,
    SeriesRing,
    TimeSeriesStore,
    aggregate_spans,
    build_span_tree,
    critical_path,
    load_events,
    render_report,
    top_slowest,
)
from repro.online import ReplicaRouter

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_obs():
    flags.reset()
    obs.reset()
    yield
    flags.reset()
    obs.reset()


# ---------------------------------------------------------- TimeSeriesStore
def test_series_ring_wraparound_chronological():
    r = SeriesRing(4)
    for i in range(6):
        r.append(float(i), float(i * 10))
    assert len(r) == 4
    assert r.values().tolist() == [20.0, 30.0, 40.0, 50.0]
    assert r.times().tolist() == [2.0, 3.0, 4.0, 5.0]
    assert r.values(2).tolist() == [40.0, 50.0]
    assert r.last() == 50.0


def test_series_ring_rejects_tiny_capacity():
    with pytest.raises(ValueError, match="capacity"):
        SeriesRing(1)


def test_store_delta_rate_need_two_samples():
    st = TimeSeriesStore()
    st.record("c", 0.0, 5.0)
    assert st.delta("c", 4) is None
    assert st.rate("c", 4) is None
    assert st.delta("missing", 4) is None
    st.record("c", 2.0, 25.0)
    assert st.delta("c", 4) == 20.0
    assert st.rate("c", 4) == 10.0  # per unit of the ingest time axis
    assert st.last("c") == 25.0


def test_store_windowed_aggregations():
    st = TimeSeriesStore(capacity=8)
    for t, v in enumerate([1.0, 3.0, 2.0, 6.0]):
        st.record("g", float(t), v)
    assert st.mean("g") == 3.0
    assert st.vmin("g") == 1.0
    assert st.vmax("g") == 6.0
    assert st.mean("g", 2) == 4.0
    # ewma: newest weighted alpha, seeded at the oldest sample
    assert st.ewma("g", alpha=0.5) == pytest.approx(
        0.5 * 6.0 + 0.5 * (0.5 * 2.0 + 0.5 * (0.5 * 3.0 + 0.5 * 1.0)))


def test_store_ingest_and_vector_delta():
    st = TimeSeriesStore()
    st.ingest({'load{index="0"}': 10.0, 'load{index="2"}': 5.0}, t=0.0)
    st.ingest({'load{index="0"}': 40.0, 'load{index="2"}': 6.0}, t=1.0)
    d = st.vector_delta("load", 4)
    # index 1 never reported: zero-filled; ordering is by index
    assert d.tolist() == [30.0, 0.0, 1.0]
    assert st.vector_delta("absent", 4).tolist() == []


def test_histogram_quantile_from_registry_snapshots():
    flags.FLAGS["obs_level"] = "counters"
    reg = obs.registry()
    reg.histogram("lat_seconds", buckets=(0.1, 0.25, 0.5, 1.0))
    for v in (0.05, 0.05, 0.15):
        reg.observe("lat_seconds", v)
    st = TimeSeriesStore()
    st.ingest(reg.snapshot(), t=0.0)
    for v in (0.3, 0.3, 0.3, 0.3):
        reg.observe("lat_seconds", v)
    st.ingest(reg.snapshot(), t=1.0)
    # whole-run: 7 observations, p50 interpolates inside the 0.25-0.5
    # bucket: 0.25 + 0.25 * (3.5 - 3) / 4
    q_all = st.histogram_quantile("lat_seconds", 0.5)
    assert q_all == pytest.approx(0.28125)
    # windowed delta: only the four 0.3s -> p50 at the bucket midpoint
    q_win = st.histogram_quantile("lat_seconds", 0.5, n=2)
    assert q_win == pytest.approx(0.375)
    assert st.histogram_quantile("lat_seconds", 0.0, n=2) >= 0.0
    with pytest.raises(ValueError, match="quantile"):
        st.histogram_quantile("lat_seconds", 1.5)


def test_histogram_quantile_inf_bucket_reports_highest_finite_bound():
    flags.FLAGS["obs_level"] = "counters"
    reg = obs.registry()
    reg.histogram("big_seconds", buckets=(0.1, 1.0)).observe(50.0)
    st = TimeSeriesStore()
    st.ingest(reg.snapshot(), t=0.0)
    assert st.histogram_quantile("big_seconds", 0.99) == 1.0
    assert st.histogram_quantile("empty_seconds", 0.5) is None


# ------------------------------------------------------- alert state machine
def _const_rule(name, values, **kw):
    """Rule whose value function replays `values` per evaluate() call."""
    it = iter(values)
    return SLORule(name, lambda store: next(it), ">", 5.0, **kw)


def test_alert_fires_first_breach_resolves_after_hysteresis():
    m = HealthMonitor([_const_rule("r", [1, 9, 9, 1, 9, 1, 1, 1],
                                   resolve_after=2)])
    for t in range(8):
        m.evaluate(float(t))
    kinds = [(h["kind"], h["t"]) for h in m.history]
    # fires at t=1; the lone clear at t=3 is cancelled by the breach at
    # t=4; two consecutive clears (t=5,6) resolve
    assert kinds == [("fire", 1.0), ("resolve", 6.0)]
    assert m.stats["alerts_fired"] == 1 and m.stats["alerts_resolved"] == 1
    assert m.alerts["r"].fires == 1 and m.alerts["r"].resolves == 1


def test_alert_fire_after_requires_consecutive_breaches():
    m = HealthMonitor([_const_rule("r", [9, 1, 9, 9, 9], fire_after=3)])
    for t in range(5):
        m.evaluate(float(t))
    assert [h["t"] for h in m.history if h["kind"] == "fire"] == [4.0]


def test_none_rule_values_freeze_the_state_machine():
    m = HealthMonitor([_const_rule("r", [9, None, None, 1, 1],
                                   resolve_after=2)])
    for t in range(5):
        m.evaluate(float(t))
    # fire at t=0; Nones neither clear nor re-breach; resolve needs the
    # two real clears at t=3,4
    assert [(h["kind"], h["t"]) for h in m.history] == [
        ("fire", 0.0), ("resolve", 4.0)]


def test_monitor_rejects_duplicate_rule_names():
    r = SLORule("dup", lambda s: 0.0, ">", 1.0)
    with pytest.raises(ValueError, match="duplicate"):
        HealthMonitor([r, SLORule("dup", lambda s: 0.0, ">", 1.0)])


def test_unknown_op_raises():
    with pytest.raises(ValueError, match="op"):
        SLORule("r", lambda s: 0.0, ">=", 1.0).breached(2.0)


def test_on_alert_callback_and_obs_surfacing():
    flags.FLAGS["obs_level"] = "trace"
    seen = []
    m = HealthMonitor([_const_rule("r", [9, 1, 1], resolve_after=2)],
                      on_alert=lambda a, firing: seen.append(
                          (a.name, firing, a.state)))
    for t in range(3):
        m.evaluate(float(t))
    assert seen == [("r", True, "firing"), ("r", False, "ok")]
    snap = obs.registry().snapshot()
    assert snap["health_alerts_fired_total"] == 1.0
    assert snap["health_alerts_resolved_total"] == 1.0
    assert snap["health_alerts_active"] == 0.0
    names = [e["name"] for e in obs.tracer().events]
    assert "alert.fire" in names and "alert.resolve" in names


def test_alert_surfacing_is_noop_when_obs_off():
    # monitor used standalone with obs off: transitions still recorded in
    # history/stats, registry and tracer untouched
    m = HealthMonitor([_const_rule("r", [9])])
    m.evaluate(0.0)
    assert m.stats["alerts_fired"] == 1
    assert obs.registry().snapshot() == {}
    assert obs.tracer().events == ()


# ------------------------------------------------------- anomaly detection
def test_ewma_zscore_anomaly_fires_on_regime_change():
    vals = [10.0] * 8 + [100.0, 100.0, 10.0, 10.0, 10.0]
    m = HealthMonitor([_const_rule("flat", vals, resolve_after=2)],
                      anomaly_z=3.0, anomaly_warmup=5)
    for t in range(len(vals)):
        m.evaluate(float(t))
    fired = [h["alert"] for h in m.history if h["kind"] == "fire"]
    # the absolute rule fires too (100 > 5); the anomaly alert must fire
    # on the jump and resolve once the EWMA re-adapts
    assert "flat_anomaly" in fired
    anomaly = m.alerts["flat_anomaly"]
    assert anomaly.threshold == 3.0
    assert anomaly.state == "ok"  # re-adapted after the jump


def test_anomaly_respects_warmup():
    vals = [10.0, 99.0, 10.0, 99.0]
    m = HealthMonitor([_const_rule("r", vals)], anomaly_z=0.1,
                      anomaly_warmup=10)
    for t in range(len(vals)):
        m.evaluate(float(t))
    assert "r_anomaly" not in m.alerts  # never armed inside warmup


# ------------------------------------------------------------- from_flags
def test_from_flags_builds_enabled_rules_only():
    flags.set_variant("obscounters+obssnap50+obshealth1+healthp990.25"
                      "+healthbacklog5.0")
    m = HealthMonitor.from_flags()
    names = {r.name for r in m.rules}
    assert names == {"span_slo", "degraded_rate", "load_skew",
                     "latency_p99", "migration_backlog"}
    flags.set_variant("obscounters+obssnap50+obshealth1+healthspan0"
                      "+healthdeg0+healthskew0")
    assert {r.name for r in HealthMonitor.from_flags().rules} == set()


def test_from_flags_validates_window_and_hysteresis():
    flags.FLAGS["health_window"] = 1
    with pytest.raises(ValueError, match="health_window"):
        HealthMonitor.from_flags()
    flags.reset()
    flags.FLAGS["health_hysteresis"] = 0
    with pytest.raises(ValueError, match="health_hysteresis"):
        HealthMonitor.from_flags()


def test_variant_spellings_round_trip():
    flags.set_variant("obshealth1+healthw16+healthhyst4+healthspan2.0"
                      "+healthp990.5+healthdeg0.1+healthskew5.0"
                      "+healthbacklog2.5+healthz3.0")
    F = flags.FLAGS
    assert F["obs_health"] is True
    assert F["health_window"] == 16
    assert F["health_hysteresis"] == 4
    assert F["health_span_slo"] == 2.0
    assert F["health_p99_slo"] == 0.5
    assert F["health_degraded_slo"] == 0.1
    assert F["health_skew_slo"] == 5.0
    assert F["health_backlog_slo"] == 2.5
    assert F["health_anomaly_z"] == 3.0
    with pytest.raises(ValueError, match="health_window"):
        flags.set_variant("healthw1")


# ------------------------------------------------- run_online integration
def test_run_online_health_requires_obs_and_snapshots():
    wl = random_workload(num_items=60, num_queries=200, density=5, seed=0)
    sim = Simulator(8, 24)
    flags.FLAGS["obs_health"] = True  # obs still off
    with pytest.raises(ValueError, match="obs_level"):
        sim.run_online(wl.hypergraph, ALGORITHMS["hpa"], seed=0)
    flags.FLAGS["obs_level"] = "counters"  # snapshots still 0
    with pytest.raises(ValueError, match="obs_snapshot_every"):
        sim.run_online(wl.hypergraph, ALGORITHMS["hpa"], seed=0)


def test_run_online_health_storm_fires_and_is_bit_identical(
        fault_injected_run):
    wl = random_workload(num_items=120, num_queries=3000, density=6, seed=2)
    sim = Simulator(10, 30)
    base, base_events = fault_injected_run(
        sim, wl.hypergraph, ALGORITHMS["hpa"], fault_seed=3, num_events=6,
        seed=0, auto_repair=False)

    flags.set_variant("obscounters+obssnap100+obshealth1+healthw4")
    obs.reset()
    fired = []
    mon = HealthMonitor.from_flags()
    res, _ = fault_injected_run(
        sim, wl.hypergraph, ALGORITHMS["hpa"], fault_seed=3, num_events=6,
        seed=0, auto_repair=False, health=mon,
        on_alert=lambda a, f: fired.append((a.name, f)))

    # observation-changes-nothing: monitored serving is bit-identical
    assert np.array_equal(base.spans, res.spans)
    assert np.array_equal(base.loads, res.loads)
    assert np.array_equal(base.access_load, res.access_load)
    s = res.summary()
    assert s["alerts_fired"] == mon.stats["alerts_fired"]
    assert s["alerts_resolved"] == mon.stats["alerts_resolved"]
    # the randomized storm degrades traffic without repair: the
    # degraded-rate SLO must have fired, via the callback too
    assert s["degraded_queries"] > 0
    assert any(h["alert"] == "degraded_rate" and h["kind"] == "fire"
               for h in mon.history)
    assert ("degraded_rate", True) in fired
    # monitor saw the span gauge and its baseline was pinned by the fit
    assert mon.baseline_span is not None and mon.baseline_span > 0
    assert mon.store.vmax("online_span_sum") > 0
    # span ratio hovered near 1.0 (no drift injected)
    span_alert = mon.alerts["span_slo"]
    assert span_alert.last_value is not None
    assert span_alert.last_value < 1.5


def test_run_online_clean_replay_fires_nothing():
    wl = random_workload(num_items=100, num_queries=1500, density=5, seed=7)
    flags.set_variant("obscounters+obssnap100+obshealth1+healthw4")
    obs.reset()
    mon = HealthMonitor.from_flags()
    res = Simulator(8, 24).run_online(wl.hypergraph, ALGORITHMS["hpa"],
                                      seed=0, health=mon)
    s = res.summary()
    assert s["alerts_fired"] == 0 and s["alerts_resolved"] == 0
    assert mon.history == []
    assert mon.stats["checks"] > 0


def test_run_online_flags_armed_monitor_without_explicit_instance():
    wl = random_workload(num_items=80, num_queries=800, density=5, seed=1)
    flags.set_variant("obscounters+obssnap100+obshealth1")
    obs.reset()
    res = Simulator(8, 24).run_online(wl.hypergraph, ALGORITHMS["hpa"],
                                      seed=0)
    s = res.summary()
    assert s["alerts_fired"] == 0 and s["alerts_resolved"] == 0
    # without obs_health the keys stay out of the summary
    flags.set_variant("obscounters+obssnap100")
    obs.reset()
    s2 = Simulator(8, 24).run_online(wl.hypergraph, ALGORITHMS["hpa"],
                                     seed=0).summary()
    assert "alerts_fired" not in s2


# --------------------------------------------- router load-gauge rebinding
def test_fresh_router_rebinds_load_gauge_at_construction():
    flags.FLAGS["obs_level"] = "counters"
    obs.reset()
    wl = random_workload(num_items=60, num_queries=300, density=5, seed=0)
    pl = ALGORITHMS["random"](wl.hypergraph, 6, 24, seed=0)
    r1 = ReplicaRouter(pl.member)
    r1.route_csr(wl.hypergraph.edge_ptr, wl.hypergraph.edge_nodes)
    assert sum(v for k, v in obs.registry().snapshot().items()
               if k.startswith("router_partition_load{")) > 0
    # a FRESH router must immediately own the exported gauge — before the
    # fix the gauge kept pointing at r1's ledger until r2's first batch
    r2 = ReplicaRouter(pl.member)
    vec = [v for k, v in sorted(obs.registry().snapshot().items())
           if k.startswith("router_partition_load{")]
    assert vec == [0.0] * 6
    assert r2.load.sum() == 0.0


def test_mid_run_migrate_swap_keeps_load_gauge_live():
    """Regression for the ISSUE satellite: after a mid-run ("migrate", ...)
    plan swap the exported gauge must track the router's live ledger."""
    wl = random_workload(num_items=100, num_queries=1200, density=5, seed=4)
    target = ALGORITHMS["lmbr"](wl.hypergraph, 8, 30, seed=1, max_moves=30)
    flags.set_variant("obscounters+obssnap100+routermb64")
    obs.reset()
    res = Simulator(8, 30).run_online(
        wl.hypergraph, ALGORITHMS["hpa"], seed=0,
        events=[(600, "migrate", target)],
    )
    snap = obs.registry().snapshot()
    vec = [snap[f'router_partition_load{{index="{i}"}}'] for i in range(8)]
    assert res.summary()["plan_swaps"] >= 1
    assert vec == [float(x) for x in res.access_load]


# ------------------------------------------------------------- analytics
def _x(name, ts, dur, tid=0, **args):
    return {"name": name, "ph": "X", "ts": float(ts), "dur": float(dur),
            "pid": 0, "tid": tid, "args": args}


def test_span_tree_containment_and_self_time():
    events = [
        _x("child.b", 50, 20),
        _x("grand", 12, 5),
        _x("child.a", 10, 30),
        _x("root", 0, 100),
        _x("async.transfer", 90, 50),   # partial overlap: parentless
    ]
    roots = build_span_tree(events)
    assert [r.name for r in roots] == ["root", "async.transfer"]
    root = roots[0]
    assert [c.name for c in root.children] == ["child.a", "child.b"]
    assert [c.name for c in root.children[0].children] == ["grand"]
    assert root.self_time == 100 - 30 - 20
    assert root.children[0].self_time == 30 - 5
    assert roots[1].parent is None and roots[1].children == []


def test_span_tree_separate_tids_do_not_nest():
    events = [_x("a", 0, 100, tid=0), _x("b", 10, 20, tid=1)]
    roots = build_span_tree(events)
    assert sorted(r.name for r in roots) == ["a", "b"]


def test_aggregate_and_critical_path_and_top_slowest():
    events = [
        _x("fit.place", 0, 100),
        _x("fit.hpa", 5, 80),
        _x("fit.hpa.refine", 10, 60),
        _x("serve.microbatch", 150, 9, queries=3),
        _x("serve.microbatch", 160, 5, queries=3),
        _x("serve.microbatch", 170, 12, queries=2),
    ]
    agg = aggregate_spans(events)
    assert agg["serve.microbatch"]["count"] == 3
    assert agg["serve.microbatch"]["total_us"] == 26.0
    assert agg["serve.microbatch"]["max_us"] == 12.0
    assert agg["fit.place"]["self_us"] == 20.0
    path = critical_path(events)
    assert [n.name for n in path] == ["fit.place", "fit.hpa",
                                      "fit.hpa.refine"]
    slow = top_slowest(events, k=2)
    assert [e["dur"] for e in slow] == [12.0, 9.0]
    assert critical_path([]) == []


def _xid(name, ts, dur, sid, parent):
    return _x(name, ts, dur, id=sid, parent=parent, fit=None)


def test_span_tree_uses_explicit_parent_ids():
    # containment would nest "child" under "transfer", which encloses it
    # in time; their recorded parent is "root"
    events = [
        _xid("child", 10, 20, 2, 1),
        _xid("transfer", 5, 40, 3, 1),
        _xid("root", 0, 100, 1, None),
        _xid("orphan", 200, 5, 4, 99),   # parent not in the trace: a root
    ]
    roots = build_span_tree(events)
    assert [r.name for r in roots] == ["root", "orphan"]
    assert [c.name for c in roots[0].children] == ["child", "transfer"]
    assert all(c.parent is roots[0] for c in roots[0].children)


def test_critical_path_roots_at_service_fit_without_fit_place():
    events = [
        _xid("service.fit", 0, 100, 1, None),
        _xid("fit.lmbr", 1, 98, 2, 1),
        _xid("fit.hpa", 2, 30, 3, 2),
        _xid("lmbr.gain", 40, 50, 4, 2),
        _xid("cover.batch", 300, 400, 5, None),
    ]
    path = critical_path(events)
    assert [n.name for n in path] == ["service.fit", "fit.lmbr", "lmbr.gain"]
    assert [n.name for n in critical_path(events, "cover.batch")] == [
        "cover.batch"]
    assert "critical path (service.fit)" in render_report(events)


def test_load_events_jsonl_and_chrome_json_agree():
    flags.FLAGS["obs_level"] = "trace"
    obs.reset()
    tr = obs.tracer()
    with tr.span("outer"):
        with tr.span("inner"):
            pass
    tr.event("mark")
    assert load_events(tr.to_jsonl()) == load_events(tr.to_chrome_trace())
    assert load_events("") == []
    assert load_events('{"name": "solo", "ph": "X", "ts": 0, "dur": 1}') \
        == [{"name": "solo", "ph": "X", "ts": 0, "dur": 1}]


def test_render_report_sections():
    events = [
        _x("fit.place", 0, 100),
        _x("serve.microbatch", 150, 9, queries=3),
        {"name": "alert.fire", "ph": "i", "ts": 155.0, "pid": 0, "tid": 0,
         "args": {"rule": "degraded_rate", "value": 0.5, "threshold": 0.02}},
    ]
    out = render_report(events, {"router_served_queries_total": 3.0,
                                 "health_alerts_fired_total": 1.0})
    assert "== trace ==" in out
    assert "critical path (fit.place)" in out
    assert "slowest serve.microbatch" in out
    assert "rule=degraded_rate" in out
    assert "router_served_queries_total" in out


def test_obs_report_cli_on_committed_fixtures():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "obs_report.py"),
         os.path.join(REPO_ROOT, "tools", "fixtures", "tiny_trace.jsonl"),
         "--prom",
         os.path.join(REPO_ROOT, "tools", "fixtures", "tiny_prom.txt")],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr
    assert "== trace ==" in proc.stdout
    assert "== metrics ==" in proc.stdout
    assert "alert.fire" in proc.stdout  # the fixture run fired alerts


def test_obs_report_cli_missing_file_fails_cleanly():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "obs_report.py"),
         os.path.join(REPO_ROOT, "does_not_exist.jsonl")],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "cannot load trace" in proc.stderr
