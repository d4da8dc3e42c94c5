"""``BENCHMARK.json`` and the files it names: every cell finds its
configuration, traffic mix, loop and metric readers, and every name keeps
to the contract's characters."""

import json
import os
import re

import pytest

from chipbench import harness

BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(harness.ROOT, p))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_names_existing_files(cell):
    w = harness.workload_entry(BENCH, cell)
    assert w["chips"] == 1
    cfg = harness.load_config(BENCH, w["config"])
    traffic = harness.load_traffic(w["traffic"])
    for d, name in (("gen", cfg["generator"]), ("loops", traffic["loop"])):
        assert os.path.isfile(os.path.join(harness.HERE, d, f"{name}.py"))
    assert len(w["why"]) <= 200
    e2e = [m["name"] for m in harness.cell_metrics(BENCH, cell, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.cell_metrics(BENCH, cell, "per_layer")


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_has_a_reader(metric):
    assert callable(harness.load_metric(metric).read)


def test_names_units_and_references():
    names = CELLS + [c["name"] for c in BENCH["configs"]] + [
        m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in BENCH["workloads"]]:
        assert NAME.match(n), n
    for c in BENCH["configs"]:
        assert c["file"].startswith("chipbench/configs/")
        for text in (c["source"], c["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text
        for k in c["reduced"]:
            assert NAME.match(k) and not k.endswith(("_dim", "_rank")), k
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            reported = harness.cell_metrics(BENCH, cell, "end_to_end")
            assert m["moves"] in [x["name"] for x in reported]
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] == "host_clock"
