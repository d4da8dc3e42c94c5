"""Whole runs on the CPU at a small size, with the harness's look for a
chip skipped: a sound run is correct, and the control and every planted
fault make ``correct`` false."""

import pytest

from chipbench import controls


@pytest.mark.parametrize("seed", [3, 2**31 + 17])
def test_sound_run_is_correct(small_run, seed):
    res = small_run("tpch40-fit", seed=seed)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"fit_s", "setup_s"}


def test_traced_run_reads_per_layer_metrics(small_run):
    res = small_run("tpch40-fit", tracing=True)
    assert res["correct"]
    m = res["metrics"]
    assert m["gain_calls_per_fit.fit"]["value"] > 0
    assert 0 <= m["idle_share.fit"]["value"] <= 100
    # no TPU op on the CPU: the kernel's roofline finds nothing to read
    assert "span_gain_roofline.fit" not in m
    assert res["device"]["window_s"] > 0
    assert "setup_s" not in m and "fit_s" not in m


@pytest.mark.parametrize("fault", ("fit_control",) + controls.FIT_FAULTS)
def test_fit_faults_fail(small_run, monkeypatch, fault):
    controls.install(fault, monkeypatch)
    res = small_run("tpch40-fit")
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0
