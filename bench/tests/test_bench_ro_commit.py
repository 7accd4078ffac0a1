"""``ro_commit_passed_pct``: ``None`` where the scheduler has no read-only
commit counters (as on a program that lacks them) or no read-only commit;
the exact share where it has them, from hand-built stats and from a stepped
scheduler whose read-only ticket passes a blocked RMW; and a traced tiny
run of the cell that lists it."""

import json
import os
from types import SimpleNamespace

import pytest

import _paths
import _tiny
from harness.spec import Bench
from repro.core import EngineConfig
from repro.db.batch import TxnSpec
from repro.db.ycsb import key_of
from repro.serve import ACKED, GroupCommitScheduler, ServeConfig, SingleBackend

BENCH = Bench(_paths.ROOT)
READ = BENCH.reader("ro_commit_passed_pct")


@pytest.mark.parametrize("stats,want", [
    ({"ro_commits": 8, "ro_commits_passed": 6}, 75.0),
    ({"ro_commits": 3, "ro_commits_passed": 0}, 0.0),
    ({"ro_commits": 0, "ro_commits_passed": 0}, None),
    ({"acked": 5, "acked_read_only": 2}, None),   # no counter
])
def test_reads_the_share_of_passed_read_only_commits(stats, want):
    got = READ(SimpleNamespace(stats=stats))
    assert got == (pytest.approx(want) if want is not None else None)


def test_reads_a_schedulers_counters(tmp_path):
    k, d = key_of(1), key_of(2)
    cfg = EngineConfig(n_buffers=2, device_kind="ssd",
                       device_dir=str(tmp_path))
    be = SingleBackend.make("vectorized", n_workers=2, cfg=cfg)
    for key in (k, d):
        be.table.insert(key, b"v0")
    sched = GroupCommitScheduler(be, ServeConfig(max_batch=8))
    assert READ(SimpleNamespace(stats=sched.stats())) is None
    m = sched.submit(TxnSpec(reads=[k], writes=[(k, b"m")]))  # worker 0
    sched.step(tick_parts=[1])                      # buffer 0 held
    rs = [sched.submit(TxnSpec(reads=[d])) for _ in range(4)]
    sched.step(tick_parts=[1])
    assert all(r.status == ACKED for r in rs)
    # workers 1, 0, 1, 0: the two on worker 0 passed m
    assert READ(SimpleNamespace(stats=sched.stats())) == pytest.approx(50.0)
    sched.run_until_drained()
    assert m.status == ACKED


def test_listed_only_in_the_read_only_cell():
    with open(os.path.join(_paths.ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    (m,) = [m for m in doc["per_layer"] if m["name"] == "ro_commit_passed_pct"]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
            m["workloads"]) == ("%", "higher", "program_counter", "commit",
                                "commit_p50_ms", ["ycsbf_zipf_lat"])


def test_traced_run_reports_it():
    result, _ = _tiny.runner.run(
        BENCH, "ycsbf_zipf_lat", 2**31 + 23, 1.5, True,
        _tiny.time.perf_counter(), scale=_tiny.SCALE["ycsbf_zipf_lat"],
        device=_tiny.CPU, say=lambda obj: None)
    assert result["correct"], result["checks"]
    assert 0.0 <= result["metrics"]["ro_commit_passed_pct"]["value"] <= 100.0
