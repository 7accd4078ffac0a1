"""``BENCHMARK.json`` against the benchmark contract, and the files it
names."""

import json
import os
import re

import pytest

import _paths
from harness.spec import Bench

DOC = json.load(open(os.path.join(_paths.ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = {w["name"] for w in DOC["workloads"]}


def test_top_level_keys():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert DOC["paths"] == ["bench"]
    assert DOC["command"] == ["python3", "bench/run.py"]
    assert 1 <= DOC["run_seconds"] <= 51


def test_names_units_and_lines():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in DOC[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
            for k in ("why", "layer", "source"):
                if k in e:
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")


def test_cells_name_real_configs_and_traffic():
    bench = Bench(_paths.ROOT)
    configs = {c["name"] for c in DOC["configs"]}
    for w in DOC["workloads"]:
        assert w["config"] in configs
        assert w["chips"] == 1
        bench.traffic(w["traffic"])
        assert bench.config(w["config"])["schema"] in ("ycsb", "tpcc")
    assert {w["config"] for w in DOC["workloads"]} == configs


def test_bounds():
    for m in DOC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    bench = Bench(_paths.ROOT)
    e2e = {m["name"] for m in bench.end_to_end(cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = bench.per_layer(cell)
    assert layers
    for m in layers:
        assert m["moves"] in e2e
        bench.reader(m["name"])


def test_per_layer_metrics_name_one_layer_and_known_cells():
    e2e = {m["name"] for m in DOC["end_to_end"]}
    for m in DOC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= CELLS
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_peaks_refuse_an_unknown_device():
    bench = Bench(_paths.ROOT)
    assert bench.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError):
        bench.peaks("cpu")
