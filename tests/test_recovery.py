"""§5 recovery: RSNe computation, last-writer-wins, ww-past-RSNe, torn tails,
checkpoints, parallel == sequential."""

import os

import pytest

from repro.core import (
    CheckpointDaemon,
    EngineConfig,
    PoplarEngine,
    Txn,
    Worker,
    decode_records,
    recover,
)
from repro.core.recovery import compute_rsne
from repro.core.txn import LogRecord


class Cell:
    def __init__(self, ssn=0):
        self.ssn = ssn


def _engine(n=2, tmp=None):
    cfg = EngineConfig(n_buffers=n, device_kind="null", device_dir=str(tmp) if tmp else None)
    return PoplarEngine(cfg)


def test_rsne_is_min_of_device_frontiers():
    recs = [
        [LogRecord(3, 1, False, []), LogRecord(7, 2, False, [])],
        [LogRecord(5, 3, False, [])],
    ]
    assert compute_rsne(recs) == 5


def test_rsne_empty_device_pins_zero():
    recs = [[LogRecord(9, 1, False, [])], []]
    assert compute_rsne(recs) == 0


def test_wr_beyond_rsne_not_replayed():
    """Durable RAW-carrying records beyond RSNe were provably uncommitted —
    replaying them could expose reads of lost writes (scenario c)."""
    e = _engine()
    w0, w1 = Worker(e, 0), Worker(e, 1)
    a, b = Cell(), Cell()
    t0 = Txn(tid=1, write_set=[("a", b"base")])
    w0.run(t0, [], [a])
    e.quiesce([0, 1], timeout=5)
    # t1 (wr) goes to buffer 1 and IS flushed; its predecessor t2 in buffer 0
    # is NOT flushed -> crash
    t2 = Txn(tid=2, write_set=[("a", b"lost")])
    w0.run(t2, [], [a])  # buffer 0, stays in memory
    t1 = Txn(tid=3, read_set=[("a", a.ssn)], write_set=[("b", b"dirty")])
    w1.run(t1, [a], [b])
    e.buffers[1].force_establish()
    e.buffers[1].flush_ready(e.devices[1])
    # crash now: device0 has t0 (+heartbeats <= t0.ssn), device1 has t1
    st = recover(e.devices)
    assert st.get(b"a") == b"base"      # t2 lost (never durable)
    assert st.get(b"b") is None         # t1 durable but > RSNe -> skipped
    assert st.n_skipped_uncommitted >= 1


def test_ww_beyond_rsne_is_replayed():
    """Write-only records commit on their own DSN, so they replay even past
    RSNe (§5)."""
    e = _engine()
    w0, w1 = Worker(e, 0), Worker(e, 1)
    a, b = Cell(), Cell()
    t0 = Txn(tid=1, write_set=[("a", b"1")])
    w0.run(t0, [], [a])
    e.quiesce([0, 1], timeout=5)
    # ww txn in buffer 1 flushed; buffer 0 frontier stays behind
    t1 = Txn(tid=2, write_set=[("b", b"2")])
    w1.run(t1, [], [b])
    # another record in buffer 0 NOT flushed keeps RSNe at t0-era
    t2 = Txn(tid=3, write_set=[("a", b"unflushed")])
    w0.run(t2, [], [a])
    e.buffers[1].force_establish()
    e.buffers[1].flush_ready(e.devices[1])
    assert e.drain(1) == 1 and t1.committed   # ww commit: own DSN only
    st = recover(e.devices)
    assert st.rsne < t1.ssn                   # t1 is beyond RSNe...
    assert st.get(b"b") == b"2"               # ...but still recovered


def test_last_writer_wins_across_devices():
    e = _engine()
    w0, w1 = Worker(e, 0), Worker(e, 1)
    x = Cell()
    vals = []
    for i in range(6):
        w = (w0, w1)[i % 2]
        t = Txn(tid=10 + i, write_set=[("x", f"v{i}".encode())])
        w.run(t, [], [x])
        vals.append(t)
    e.quiesce([0, 1], timeout=5)
    st = recover(e.devices)
    assert st.get(b"x") == b"v5"
    # parallel and sequential recovery agree
    st2 = recover(e.devices, parallel=False)
    assert st.data == st2.data


def test_torn_tail_truncated(tmp_path):
    e = _engine(tmp=tmp_path)
    w0, w1 = Worker(e, 0), Worker(e, 1)
    a = Cell()
    for i in range(4):
        w0.run(Txn(tid=1 + i, write_set=[("a", f"v{i}".encode())]), [], [a])
    e.quiesce([0, 1], timeout=5)
    # corrupt the tail of device 0's log (torn write)
    p = e.devices[0].path
    e.devices[0].close()
    with open(p, "r+b") as f:
        f.seek(-3, os.SEEK_END)
        f.truncate()
    data = open(p, "rb").read()
    recs = decode_records(data)
    assert len(recs) >= 1           # intact prefix survives
    assert recs[-1].writes[0][1] != b"v3"  # torn record dropped


def test_checkpoint_plus_log_recovery(tmp_path):
    e = _engine(tmp=tmp_path)
    w0, w1 = Worker(e, 0), Worker(e, 1)
    cells = {f"k{i}": Cell() for i in range(20)}
    for i in range(20):
        w0.run(Txn(tid=1 + i, write_set=[(f"k{i}", f"a{i}".encode())]), [], [cells[f"k{i}"]])
    e.quiesce([0, 1], timeout=5)

    ck = CheckpointDaemon(str(tmp_path / "ckpt"), n_threads=2, m_files=2,
                          csn_fn=lambda: e.commit.csn)
    parts = [
        [(f"k{i}".encode(), f"a{i}".encode(), cells[f"k{i}"].ssn) for i in range(10)],
        [(f"k{i}".encode(), f"a{i}".encode(), cells[f"k{i}"].ssn) for i in range(10, 20)],
    ]
    ck.run_once(parts)

    # post-checkpoint writes
    for i in range(5):
        w1.run(Txn(tid=100 + i, write_set=[(f"k{i}", f"b{i}".encode())]), [], [cells[f"k{i}"]])
    e.quiesce([0, 1], timeout=5)

    st = recover(e.devices, checkpoint_dir=str(tmp_path / "ckpt"))
    assert st.rsns > 0
    for i in range(5):
        assert st.get(f"k{i}".encode()) == f"b{i}".encode()
    for i in range(5, 20):
        assert st.get(f"k{i}".encode()) == f"a{i}".encode()


def test_checkpoint_elr_validation_times_out():
    ck = CheckpointDaemon("/tmp/_ck_nonexistent_ok", n_threads=1, m_files=1,
                          csn_fn=lambda: 0)
    with pytest.raises(TimeoutError):
        ck.run_once([[(b"k", b"v", 99)]], validate_timeout=0.05)


def _sealed_devices(n_devices=2, n_records=3000, per_segment=1000):
    """Devices holding sealed segments of 1,000 one-write records each and
    a tail, enough lanes for the fused scan's compiled path."""
    from repro.core.storage import DeviceSpec, StorageDevice

    devs = []
    for d in range(n_devices):
        dev = StorageDevice(DeviceSpec.null(), clock="virtual")
        for lo in range(0, n_records, per_segment):
            txns = []
            for i in range(lo, min(lo + per_segment, n_records)):
                t = Txn(tid=i, write_set=[(f"k{i % 700}", b"v%d" % i)])
                t.ssn = d * n_records + i + 1
                txns.append(t)
            dev.write(b"".join(t.encode() for t in txns))
            if lo + per_segment < n_records:
                dev.seal(txns[-1].ssn)
        devs.append(dev)
    return devs


@pytest.mark.parametrize("parallel", [False, True])
def test_fused_recovery_splits_its_replay_time(parallel):
    devs = _sealed_devices()
    st = recover(devs, mode="pallas", parallel=parallel)
    rep = st.report
    assert rep.fused
    split = (rep.fused_wait_s, rep.fused_scan_s, rep.fused_apply_s)
    assert all(x >= 0 for x in split) and rep.fused_scan_s > 0
    assert sum(split) <= rep.replay_s
    assert st.data == recover(devs, mode="scalar").data
    other = recover(devs, mode="vectorized").report
    assert (other.fused_wait_s, other.fused_scan_s,
            other.fused_apply_s) == (0.0, 0.0, 0.0)
