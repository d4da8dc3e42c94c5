"""The readers of the program's spans: each on a hand-built
``run.spans``, each with nothing to read, and all four in a traced run."""

import types

import pytest

from chipbench import harness

SPAN_READERS = {
    "hpa_s_per_fit.fit": "fit.hpa",
    "lmbr_gain_s_per_fit.fit": "lmbr.gain",
    "cover_s_per_fit.fit": "cover.batch",
}


def _x(name, dur_us, sid, parent):
    return {"name": name, "ph": "X", "ts": 0.0, "dur": float(dur_us),
            "pid": 0, "tid": 0,
            "args": {"id": sid, "parent": parent, "fit": 1}}


def _run(spans, fits=2):
    return types.SimpleNamespace(spans=spans, window={"fits": fits})


def _hand_spans(name):
    # two outermost spans of `name` (one at the root, one under another
    # span) and one nested in the first, which must not count twice
    return [
        _x(name, 3e6, 1, None),
        _x(name, 1e6, 2, 1),
        _x("fit.lmbr", 9e6, 3, None),
        _x(name, 2e6, 4, 3),
        _x("other", 7e6, 5, None),
        {"name": name, "ph": "i", "ts": 0.0, "pid": 0, "tid": 0,
         "args": {"id": 6, "parent": None, "fit": 1}},
    ]


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_span_reader_sums_outermost_spans_per_fit(metric):
    read = harness.load_metric(metric).read
    assert read(_run(_hand_spans(SPAN_READERS[metric]))) == pytest.approx(
        2.5)
    assert read(_run([_x("other", 1e6, 1, None)])) is None
    assert read(_run([])) is None
    assert read(_run(_hand_spans(SPAN_READERS[metric]), fits=0)) is None


def test_span_reader_without_ids_counts_every_span():
    # the spans of a tracer that records no ids or parents
    spans = [{"name": "fit.hpa", "ph": "X", "ts": 0.0, "dur": 4e6,
              "pid": 0, "tid": 0, "args": {"k": 40}}] * 3
    read = harness.load_metric("hpa_s_per_fit.fit").read
    assert read(_run(spans, fits=3)) == pytest.approx(4.0)


def test_compiles_in_window_counts_compile_events():
    read = harness.load_metric("compiles_in_window.fit").read
    spans = [_x("service.fit", 5.0, 1, None), _x("jit.compile", 1.0, 2, 1),
             _x("jit.compile", 1.0, 3, None)]
    assert read(_run(spans)) == 2
    assert read(_run(spans[:1])) == 0
    assert read(_run([])) is None
    # spans without ids: that tracer records no compilations
    assert read(_run([{"name": "fit.hpa", "ph": "X", "ts": 0.0, "dur": 1.0,
                       "args": {}}])) is None


def test_traced_run_reads_the_span_metrics(small_run):
    res = small_run("tpch40-fit", tracing=True)
    assert res["correct"]
    m = res["metrics"]
    for name in SPAN_READERS:
        assert m[name]["value"] > 0, name
    assert m["compiles_in_window.fit"]["value"] >= 0
