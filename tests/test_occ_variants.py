"""OCC (§4.4) + engine-variant semantics: serializability under contention,
ELR correctness, variant constraint levels (Table 1)."""

import threading

import pytest

from repro.core import EngineConfig, PoplarEngine, Txn, Worker, recover
from repro.core.variants import CentrEngine, NvmDEngine, SiloEngine
from repro.db import OCCWorker, Table


def _poplar(n=2):
    return PoplarEngine(EngineConfig(n_buffers=n, device_kind="null", flush_interval=1e-3))


def test_occ_read_validation_abort():
    """A txn whose read set changed during validation must abort."""
    table = Table()
    table.insert("x", b"0")
    eng = _poplar()
    w0 = OCCWorker(table, eng, 0)
    w1 = OCCWorker(table, eng, 1)
    cell = table.get("x")

    # interleave manually: w0 reads x, then w1 commits a write to x,
    # then w0 validates -> ssn changed -> abort
    seen_ssn = cell.ssn
    assert w1.execute(reads=[], writes=[("x", b"1")]) is not None
    # emulate w0's read-set validation against the stale ssn
    assert cell.ssn != seen_ssn


def test_occ_concurrent_counter_serializable():
    """N threads increment a counter via RMW txns; committed increments must
    equal the final counter value (lost-update freedom under OCC)."""
    table = Table()
    table.insert("ctr", (0).to_bytes(8, "little"))
    eng = _poplar()
    eng.start()
    n_workers, per = 4, 60
    commits = [0] * n_workers

    def loop(i):
        w = OCCWorker(table, eng, i)
        for _ in range(per):
            while True:
                cell = table.get("ctr")
                val = int.from_bytes(cell.value[:8], "little")
                t = w.execute(reads=["ctr"], writes=[("ctr", (val + 1).to_bytes(8, "little"))])
                if t is not None:
                    commits[i] += 1
                    break
            w.drain()

    threads = [threading.Thread(target=loop, args=(i,)) for i in range(n_workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    eng.quiesce(range(n_workers), timeout=30)
    eng.stop()
    final = int.from_bytes(table.get("ctr").value[:8], "little")
    assert final == sum(commits) == n_workers * per

    # crash-recover: the recovered counter must equal the live value
    st = recover(eng.devices)
    assert int.from_bytes(st.get(b"ctr")[:8], "little") == final


def test_elr_reader_commits_after_writer():
    """Early lock release: a reader of pre-committed data must not commit
    before its writer (strictness via SSN ordering + CSN)."""
    table = Table()
    table.insert("a", b"0")
    table.insert("b", b"0")
    # a huge flush interval keeps drain()'s inline null-device logger tick
    # from auto-flushing between steps on a slow CI machine — the "nothing
    # flushed yet" assertions below need flushing pinned to quiesce()
    eng = PoplarEngine(
        EngineConfig(n_buffers=2, device_kind="null", flush_interval=60.0)
    )
    w0 = OCCWorker(table, eng, 0)
    w1 = OCCWorker(table, eng, 1)
    t_writer = w0.execute(reads=[], writes=[("a", b"W")])
    # reader observes the (pre-committed, ELR-released) write immediately
    t_reader = w1.execute(reads=["a"], writes=[("b", b"R")])
    assert t_writer.ssn < t_reader.ssn
    # drain with nothing flushed: neither commits
    assert eng.drain(0) == 0 and eng.drain(1) == 0
    eng.quiesce([0, 1], timeout=10)
    assert t_writer.committed and t_reader.committed
    assert t_writer.t_commit <= t_reader.t_commit


def test_nvmd_tracks_war_in_gsn():
    """NVM-D's GSN updates read tuples (WAR tracked) — Poplar's SSN doesn't."""

    class Cell:
        def __init__(self):
            self.ssn = 0

    nv = NvmDEngine(n_workers=2, n_devices=2, device_kind="null")
    nv.register_worker(0)
    a = Cell()
    t = Txn(tid=1, read_set=[("a", 0)], write_set=[("b", b"x")])
    t.worker_id = 0
    nv.allocate(t, [a], [Cell()])
    assert a.ssn == t.ssn  # read tuple got the GSN

    pop = _poplar()
    pop.register_worker(0)
    a2 = Cell()
    t2 = Txn(tid=2, read_set=[("a", 0)], write_set=[("b", b"x")])
    t2.worker_id = 0
    pop.allocate(t2, [a2], [Cell()])
    assert a2.ssn == 0     # WAR untracked (recoverability)


def test_silo_epoch_commit():
    eng = SiloEngine(EngineConfig(n_buffers=2, device_kind="null"), epoch_interval=3600)
    w0 = Worker(eng, 0)

    class Cell:
        def __init__(self):
            self.ssn = 0

    t = Txn(tid=1, write_set=[("a", b"1")])
    w0.run(t, [], [Cell()])
    # flush everything: txn still cannot commit until the epoch advances
    eng.logger_tick(0, force=True)
    eng.logger_tick(1, force=True)
    assert eng.drain(0) == 0
    eng.advance_epoch()
    eng.logger_tick(0, force=True)
    eng.logger_tick(1, force=True)
    assert eng.drain(0) == 1 and t.committed


def test_centr_total_order():
    eng = CentrEngine(EngineConfig(device_kind="null"))
    w = Worker(eng, 0)

    class Cell:
        def __init__(self):
            self.ssn = 0

    ssns = []
    for i in range(5):
        t = Txn(tid=i + 1, write_set=[(f"k{i}", b"v")])
        w.run(t, [], [Cell()])
        ssns.append(t.ssn)
    assert ssns == sorted(ssns) and len(set(ssns)) == 5  # strict total order


class _Cell:
    def __init__(self):
        self.ssn = 0


def _centr():
    eng = CentrEngine(EngineConfig(device_kind="null", flush_interval=60.0))
    return eng, (lambda: None), (lambda: eng.logger_tick(0, force=True))


def _silo():
    eng = SiloEngine(EngineConfig(n_buffers=2, device_kind="null"),
                     epoch_interval=3600)

    def release():
        eng.advance_epoch()
        for i in range(2):
            eng.logger_tick(i, force=True)
    return eng, (lambda: None), release


def _nvmd():
    eng = NvmDEngine(n_workers=2, n_devices=2, device_kind="null")
    blocker = Txn(tid=99, write_set=[("z", b"z")])
    blocker.worker_id = 1

    def hold():
        eng.allocate(blocker, [], [_Cell()])   # in flight on device 1
    return eng, hold, (lambda: eng.publish(blocker))


@pytest.mark.parametrize("make", [_centr, _silo, _nvmd],
                         ids=["centr", "silo", "nvmd"])
def test_baselines_commit_read_only_txns_under_their_own_rule(make):
    """A baseline's read-only txn waits on that engine's one watermark
    (CENTR: the single buffer's DSN; SILO: the persistent epoch; NVM-D: the
    durable GSN), is counted as pending meanwhile, and commits once the
    watermark passes it, no earlier than the writer whose version it read."""
    eng, hold, release = make()
    w0, w1 = Worker(eng, 0), Worker(eng, 1)
    a = _Cell()
    writer = Txn(tid=1, write_set=[("a", b"1")])
    w0.run(writer, [], [a])
    hold()
    ro = Txn(tid=2, read_set=[("a", a.ssn)])
    w1.run(ro, [a], [])
    assert ro.ssn >= writer.ssn
    assert eng.drain(0) == 0 and eng.drain(1) == 0
    assert eng.queues[1].pending() == 1 and not ro.committed
    release()
    eng.quiesce([0, 1], timeout=10)
    assert writer.committed and ro.committed
    assert writer.t_commit <= ro.t_commit
    assert eng.queues[0].pending() == eng.queues[1].pending() == 0
