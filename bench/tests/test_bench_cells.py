"""Every cell's phases end to end at a tiny size on the CPU: load, warm-up,
window, settle, crash, recovery and the reference's comparison."""

import json

import pytest

import _tiny

CELLS = [w["name"] for w in json.load(
    open(_tiny._paths.ROOT + "/BENCHMARK.json"))["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct(cell):
    result, lines = _tiny.run(cell)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0
    assert list(result)[-1] == "checks"
    assert all(line.startswith("check ") for line in lines)
    assert "setup_s" in result["metrics"]
    for m in result["metrics"].values():
        assert m["value"] > 0


def test_no_compile_inside_the_window():
    said = []
    bench = _tiny.Bench(_tiny._paths.ROOT)
    _tiny.runner.run(bench, "ycsb_zipf_lat", 5, 1.0, False,
                     _tiny.time.perf_counter(),
                     scale=_tiny.SCALE["ycsb_zipf_lat"], device=_tiny.CPU,
                     say=said.append)
    assert said[0]["compiles"]["window"] == {"traces": 0,
                                             "compiles_or_cache_loads": 0}


def test_traced_run_reports_host_side_per_layer_metrics():
    result, _ = _tiny.run("tpcc_lat", trace=True)
    assert result["correct"]
    m = result["metrics"]
    # device_trace metrics need a TPU plane; the rest read the host
    for name in ("gen_lag_p99_ms", "retries_per_ack",
                 "log_bytes_per_user_byte", "recover_replay_s"):
        assert name in m
    assert m["log_bytes_per_user_byte"]["value"] > 1.0
