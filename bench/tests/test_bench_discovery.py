"""A configuration, a traffic mix and a per-layer metric added as files
under ``bench/`` plus ``BENCHMARK.json`` entries, with no file edited, are
found by name and run."""

import json
import os
import shutil

import _paths
import _tiny


def test_new_config_mix_and_metric_are_picked_up(tmp_path):
    shutil.copytree(_paths.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    os.symlink(os.path.join(_paths.ROOT, "src"), tmp_path / "src")
    doc = json.load(open(os.path.join(_paths.ROOT, "BENCHMARK.json")))
    bench = tmp_path / "bench"

    cfg = json.load(open(bench / "configs" / "ycsb_wo_10m.json"))
    cfg["rows"] = 2000
    (bench / "configs" / "ycsb_small.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "ycsb_trickle.json").write_text(json.dumps({
        "arrival": "open", "rate_per_s": 100, "max_batch": 8,
        "queue_capacity": 64, "latency_budget_s": 0.001, "warm_max_cut": 8,
        "txn": {"key_dist": "uniform"}}))
    (bench / "metrics" / "acked_share.py").write_text(
        "def read(run):\n"
        "    return 100.0 * run.window.acked.mean()\n")
    doc["configs"].append({"name": "ycsb_small", "source": "test",
                           "file": "bench/configs/ycsb_small.json",
                           "reduced": ["rows"], "why": "test"})
    doc["workloads"].append({"name": "small_trickle", "config": "ycsb_small",
                             "traffic": "ycsb_trickle", "chips": 1,
                             "why": "test"})
    for m in doc["end_to_end"]:
        if m["name"] in ("commit_p50_ms", "recover_s"):
            m["workloads"].append("small_trickle")
    doc["per_layer"].append({
        "name": "acked_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "commit_p50_ms", "workloads": ["small_trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    scale = {"traffic": {}}
    result, _ = _tiny.run("small_trickle", root=str(tmp_path), scale=scale,
                          seconds=1.0)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"setup_s", "commit_p50_ms",
                                      "recover_s"}
    traced, _ = _tiny.run("small_trickle", root=str(tmp_path), scale=scale,
                          seconds=1.0, trace=True)
    assert traced["metrics"]["acked_share"]["value"] == 100.0
