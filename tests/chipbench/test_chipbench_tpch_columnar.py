"""The TPC-H column-store deployment (``tpch-sf1000-col-40p``): its
generator against the specification's facts, the readers this cell adds,
and whole runs of ``tpchcol40-fit`` on the CPU at a small size (a sound
run is correct; the control and every planted fault make it incorrect)."""

import datetime
import types

import numpy as np
import pytest

from chipbench import controls, harness
from chipbench.gen import tpch_columnar as gen

CELL = "tpchcol40-fit"
BENCH = harness.load_benchmark()
CFG = harness.load_config(BENCH, "tpch-sf1000-col-40p")


def small_run(seed=3, seconds=0.4, tracing=False, num_queries=400):
    bench = harness.load_benchmark()
    r = harness.build_run(CELL, seed, seconds, tracing, bench=bench)
    r.config.update(num_queries=num_queries)
    r.traffic.update(traces=2)
    return harness.run_cell(r, bench=bench)


def test_schema_and_cardinalities():
    assert len(gen.SCHEMA) == 8
    assert sum(len(c) for c in gen.SCHEMA.values()) == 61
    sf = CFG["scale_factor"]
    assert gen.table_rows(CFG, "LINEITEM") == 6_000_000 * sf
    assert gen.table_rows(CFG, "ORDERS") == 1_500_000 * sf
    assert gen.table_rows(CFG, "PARTSUPP") == 800_000 * sf
    assert gen.table_rows(CFG, "PART") == 200_000 * sf
    assert gen.table_rows(CFG, "CUSTOMER") == 150_000 * sf
    assert gen.table_rows(CFG, "SUPPLIER") == 10_000 * sf
    assert gen.table_rows(CFG, "NATION") == 25
    assert gen.table_rows(CFG, "REGION") == 5
    for cols, _, _ in gen.TEMPLATES.values():
        for c in cols:
            assert c in dict(gen.SCHEMA[gen.table_of(c)]), c


def test_months_follow_the_dates():
    li = gen.month_rows(CFG, "LINEITEM")
    od = gen.month_rows(CFG, "ORDERS")
    assert (len(li), len(od)) == (84, 80)
    assert li.sum() == pytest.approx(gen.table_rows(CFG, "LINEITEM"))
    assert od.sum() == pytest.approx(gen.table_rows(CFG, "ORDERS"))
    # orders: a whole 31-day month against 1998-08's two days
    per_day = gen.table_rows(CFG, "ORDERS") / 2406
    assert od[0] == pytest.approx(31 * per_day)
    assert od[-1] == pytest.approx(2 * per_day)
    # line items ship 1..121 days after the order: none on 1992-01-01
    assert li[0] == pytest.approx(
        gen.table_rows(CFG, "LINEITEM") / 2406 / 121 * sum(range(31)))
    assert (li > 0).all()


def test_items_sizes_and_capacity():
    lay = gen.layout(CFG)
    w = gen.node_weights(CFG, 2**31 + 5)
    assert np.array_equal(w, lay["sizes"])
    assert len(w) == CFG["num_items"]
    assert gen.capacity(CFG) == pytest.approx(CFG["capacity"], rel=1e-12)
    assert w.sum() == pytest.approx(
        CFG["fill"] * CFG["target_min_partitions"] * CFG["capacity"])
    assert w.max() <= CFG["capacity"] * CFG["chunk_max_share"]
    assert (w > 0).all()
    # chunks are the smallest power of two that keeps each under 1/8
    first = lay["first"]
    assert first["L_COMMENT"][1] == 84 and first["O_COMMENT"][1] == 80
    assert first["PS_COMMENT"][1] == 16 and first["N_NAME"][1] == 1
    whole = gen.table_rows(CFG, "PARTSUPP") * 123.5 / 1e9
    assert whole / 8 > CFG["capacity"] / 8 >= whole / 16


def test_templates_read_the_months_their_dates_allow():
    sets = gen.access_sets(CFG)
    lay = gen.layout(CFG)
    assert len(sets) == sum(t[1] for t in gen.TEMPLATES.values())

    def months(q, col):
        s, n = lay["first"][col]
        return sorted(int(v) - s for v in q if s <= v < s + n)

    # Q1 at DELTA 60: ship dates up to 1998-10-02, months 1992-01..1998-10
    assert months(sets["Q1", 0], "L_TAX") == list(range(82))
    assert len(sets["Q1", 0]) == 7 * 82
    # Q3 on 1995-03-31: orders up to 1995-03, line items from 1995-04
    m95 = 12 * 3 + 2
    assert months(sets["Q3", 30], "O_ORDERDATE") == list(range(m95 + 1))
    assert months(sets["Q3", 30], "L_SHIPDATE") == list(range(m95 + 1, 84))
    assert months(sets["Q3", 0], "L_SHIPDATE") == list(range(m95, 84))
    # Q6 on 1993: twelve months of four columns; Q12 prunes nothing
    assert months(sets["Q6", 0], "L_DISCOUNT") == list(range(12, 24))
    assert len(sets["Q6", 0]) == 48
    assert months(sets["Q12", 0], "L_SHIPMODE") == list(range(84))
    # Q14 reads one month, Q4 three months of orders and all line items
    assert months(sets["Q14", 59], "L_PARTKEY") == [71]
    assert months(sets["Q4", 0], "O_ORDERDATE") == [12, 13, 14]
    assert months(sets["Q4", 0], "L_COMMITDATE") == list(range(84))
    # unpartitioned columns: every chunk
    assert len(months(sets["Q2", 0], "PS_SUPPLYCOST")) == 1
    assert len(months(sets["Q16", 0], "S_COMMENT")) == 1
    assert len(months(sets["Q13", 0], "C_CUSTKEY")) == 1
    assert datetime.date.fromordinal(gen._month_start(71)) == \
        datetime.date(1997, 12, 1)


@pytest.mark.parametrize("stream", [10, 2**40 + 3])
def test_traces_deterministic_by_seed(stream):
    a = gen.query_lists(CFG, 2**31 + 11, stream, 600)
    b = gen.query_lists(CFG, 2**31 + 11, stream, 600)
    c = gen.query_lists(CFG, 2**31 + 12, stream, 600)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(np.array_equal(x, y) for x, y in zip(a, c))
    for q in a:
        assert np.array_equal(q, np.unique(q)) and not q.flags.writeable
        assert 0 <= q[0] and q[-1] < CFG["num_items"]
    # repeated access sets: far fewer distinct sets than instances
    assert len({q.tobytes() for q in a}) < 300


def _x(name, dur_us, words):
    return {"name": name, "ph": "X", "ts": 0.0, "dur": float(dur_us),
            "args": {"words": words, "edges": 4, "backend": "host"}}


def test_wide_cover_reader():
    read = harness.load_metric("wide_cover_s_per_fit.fit").read
    spans = [_x("cover.bucket", 3e6, 1), _x("cover.bucket", 2e6, 2),
             _x("cover.bucket", 4e6, 11), _x("cover.batch", 9e6, 5)]
    run = types.SimpleNamespace(spans=spans, window={"fits": 2})
    assert read(run) == pytest.approx(3.0)
    run.spans = spans[:1]
    assert read(run) is None
    run.spans, run.window = spans, {"fits": 0}
    assert read(run) is None


def test_fit_edges_reader():
    read = harness.load_metric("fit_edges_per_fit.fit").read
    plans = [(0, None, None, {"edges": 230}), (1, None, None, {"edges": 226})]
    assert read(types.SimpleNamespace(window={"plans": plans})) == 228
    # a program that does not report its edges: nothing to read
    old = [(0, None, None, {"moves": 3})]
    assert read(types.SimpleNamespace(window={"plans": old})) is None
    assert read(types.SimpleNamespace(window={})) is None


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_sound_run_is_correct(seed):
    res = small_run(seed=seed)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"fit_s", "setup_s"}


def test_traced_run_reads_the_cells_metrics():
    res = small_run(tracing=True)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert 0 < m["fit_edges_per_fit.fit"]["value"] < 400
    assert m["wide_cover_s_per_fit.fit"]["value"] > 0
    assert m["cover_s_per_fit.fit"]["value"] >= \
        m["wide_cover_s_per_fit.fit"]["value"]
    assert m["gain_calls_per_fit.fit"]["value"] > 0
    assert "span_gain_roofline.fit" not in m


@pytest.mark.parametrize("fault", ("fit_control",) + controls.FIT_FAULTS)
def test_fit_faults_fail(monkeypatch, fault):
    controls.install(fault, monkeypatch)
    res = small_run()
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0


def test_half_trace_is_caught_by_the_whole_trace_check(monkeypatch):
    controls.install("half_trace", monkeypatch)
    res = small_run()
    assert res["checks"]["partial_trace"]["value"] == res["attempted"] > 0
    assert res["failed"] == res["attempted"]


def test_program_without_a_query_count_is_refused(monkeypatch):
    """A program whose plans do not count their queries (this repository
    before the merge) fails at set-up, before any trace is generated."""
    from repro.core import placement_service

    PS = placement_service.PlacementService
    fit = PS.fit

    def uncounted(self, *a, **kw):
        plan = fit(self, *a, **kw)
        plan.stats.pop("queries")
        return plan

    monkeypatch.setattr(PS, "fit", uncounted)
    monkeypatch.setattr(gen, "query_lists", None)  # never reached
    with pytest.raises(SystemExit, match="whole trace"):
        small_run()
