"""Commit protocol (§4.3): Qww, Qwr and the read-only heap, DSN/CSN
watermarks, heartbeats, and a randomized drain against a plain oracle."""

import random
import sys
import threading

import pytest

from repro.core import EngineConfig, PoplarEngine, Txn, Worker


class Cell:
    def __init__(self, ssn=0):
        self.ssn = ssn


def _engine(n=2):
    # a huge flush interval pins all flushing/heartbeating to the explicit
    # force-ticks these tests issue: with the conftest's sub-ms default, a
    # slow CI machine can let drain()'s inline null-device logger tick
    # auto-heartbeat between steps and commit Qwr txns before the
    # "not yet committed" assertions run
    return PoplarEngine(
        EngineConfig(n_buffers=n, device_kind="null", flush_interval=60.0)
    )


def test_qww_commits_on_own_dsn_only():
    """A write-only txn commits as soon as its own buffer's DSN covers it,
    even if the other buffer never flushed (scenario d/f freedom)."""
    e = _engine()
    w0, w1 = Worker(e, 0), Worker(e, 1)
    a, b = Cell(), Cell()
    t0 = Txn(tid=1, write_set=[("a", b"1")])
    w0.run(t0, [], [a])
    # put something unflushed in buffer 1 so its DSN stays behind
    t1 = Txn(tid=2, write_set=[("b", b"2")])
    w1.run(t1, [], [b])
    # flush ONLY buffer 0
    e.buffers[0].force_establish()
    e.buffers[0].flush_ready(e.devices[0])
    assert w0.drain() == 1 and t0.committed
    assert not t1.committed


def test_qwr_waits_for_csn():
    """A RAW-carrying txn cannot commit until every buffer's DSN passes its
    SSN (scenario c prevention)."""
    e = _engine()
    w0, w1 = Worker(e, 0), Worker(e, 1)
    a, b = Cell(), Cell()
    t0 = Txn(tid=1, write_set=[("a", b"1")])
    w0.run(t0, [], [a])          # ssn 1 in buffer 0 (NOT flushed)
    t1 = Txn(tid=2, read_set=[("a", a.ssn)], write_set=[("b", b"2")])
    w1.run(t1, [a], [b])         # ssn 2 in buffer 1, RAW on t0
    # flush only buffer 1: t1's record durable but its predecessor is not
    e.buffers[1].force_establish()
    e.buffers[1].flush_ready(e.devices[1])
    e.commit.advance_csn()
    assert w1.drain() == 0 and not t1.committed
    # flush buffer 0: its DSN reaches t0.ssn=1 but CSN=min(1, dsn1) < t1.ssn,
    # so t1 still waits (CSN is conservative)...
    e.buffers[0].force_establish()
    e.buffers[0].flush_ready(e.devices[0])
    e.commit.advance_csn()
    assert w0.drain() == 1 and t0.committed  # t0's own-buffer commit is fine
    assert w1.drain() == 0 and not t1.committed
    # ...until the idle buffer 0 heartbeats up to the global frontier
    e.logger_tick(0, force=True)
    e.commit.advance_csn()
    assert w1.drain() == 1 and t1.committed


def test_read_only_commits_via_csn():
    e = _engine()
    w0, w1 = Worker(e, 0), Worker(e, 1)
    a = Cell()
    t0 = Txn(tid=1, write_set=[("a", b"1")])
    w0.run(t0, [], [a])
    ro = Txn(tid=2, read_set=[("a", a.ssn)])
    w1.run(ro, [a], [])
    assert ro.ssn == t0.ssn  # read-only: ssn = base, no +1
    e.quiesce([0, 1], timeout=5)
    assert ro.committed


def test_read_only_commits_past_a_blocked_rmw_of_its_worker():
    """A read-only txn whose SSN is covered by the CSN commits although an
    older RMW of the same worker, above the CSN, still sits in Qwr; one that
    read that RMW's version waits for it."""
    e = _engine()
    w0 = Worker(e, 0)
    Worker(e, 1)
    a, b, c = Cell(), Cell(), Cell()
    rmw = Txn(tid=1, read_set=[("a", a.ssn)], write_set=[("b", b"1")])
    w0.run(rmw, [a], [b])          # ssn 1 in buffer 0, not flushed
    old = Txn(tid=2, read_set=[("c", c.ssn)])
    w0.run(old, [c], [])           # a durable version: ssn 0
    new = Txn(tid=3, read_set=[("b", b.ssn)])
    w0.run(new, [b], [])           # rmw's version: ssn 1
    assert (old.ssn, new.ssn, rmw.ssn) == (0, 1, 1)
    assert e.commit.advance_csn() == 0
    assert w0.drain() == 1 and old.committed
    assert not rmw.committed and not new.committed
    q = e.queues[0]
    assert (q.ro_commits, q.ro_commits_passed, q.pending()) == (1, 1, 2)
    # buffer 0 flushes and the idle buffer 1 heartbeats: the CSN covers both
    for _ in range(2):
        for i in range(2):
            e.logger_tick(i, force=True)
    assert e.commit.advance_csn() >= 1
    assert w0.drain() == 2 and rmw.committed and new.committed
    assert rmw.t_commit <= new.t_commit
    # Qwr was empty when new committed: nothing left to pass
    assert (q.ro_commits, q.ro_commits_passed, q.pending()) == (2, 1, 0)


@pytest.mark.parametrize("seed", range(8))
def test_drain_commits_exactly_what_the_watermarks_cover(seed):
    """Random writes, RMWs and reads on several workers, random partial
    flushes (with heartbeats) and drains.  After every drain round the
    committed set is exactly {read-only or RMW with ssn <= CSN} ∪
    {write-only with ssn <= DSN(own buffer)}: nothing commits early or late.
    The read-only counters match the oracle's count of read-only commits,
    and of those made while an older RMW of the same worker stayed queued."""
    rng = random.Random(seed)
    n_buf = rng.choice([2, 3])
    n_workers = rng.choice([n_buf, 2 * n_buf])
    e = PoplarEngine(EngineConfig(n_buffers=n_buf, device_kind="ssd",
                                  flush_interval=60.0))
    workers = [Worker(e, w) for w in range(n_workers)]
    cells = [Cell() for _ in range(12)]
    open_txns = []             # (push index, worker, txn), not yet committed
    n_push = n_ro = n_passed = 0

    def is_ro(t):
        return t.buffer_id < 0

    def covered(t, csn):
        if is_ro(t) or t.read_set:
            return t.ssn <= csn
        return t.ssn <= e.buffers[t.buffer_id].dsn

    def drain_round():
        nonlocal n_ro, n_passed
        order = list(range(n_workers))
        rng.shuffle(order)
        for w in order:
            workers[w].drain()
        csn = e.commit.csn
        assert csn == min(b.dsn for b in e.buffers)
        for _, _, t in open_txns:
            assert not (t.committed and not covered(t, csn)), "early"
            assert t.committed or not covered(t, csn), "late"
        blocked = {}           # worker -> push index of its oldest queued RMW
        for i, w, t in open_txns:
            if not t.committed and not is_ro(t) and t.read_set:
                blocked.setdefault(w, i)
        for i, w, t in open_txns:
            if t.committed and is_ro(t):
                n_ro += 1
                n_passed += w in blocked and blocked[w] < i
        open_txns[:] = [x for x in open_txns if not x[2].committed]
        qs = e.queues.values()
        assert sum(q.ro_commits for q in qs) == n_ro
        assert sum(q.ro_commits_passed for q in qs) == n_passed

    for step in range(rng.randrange(60, 160)):
        r = rng.random()
        if r < 0.6:
            w = rng.randrange(n_workers)
            kind = rng.choice(["wo", "rmw", "ro", "ro"])
            reads = rng.sample(range(len(cells)), rng.randrange(1, 3))
            write = rng.randrange(len(cells))
            t = Txn(tid=step + 1)
            if kind != "wo":
                t.read_set = [(f"k{j}", cells[j].ssn) for j in reads]
            if kind != "ro":
                t.write_set = [(f"k{write}", b"v%d" % step)]
            workers[w].run(t, [cells[j] for j in reads] if kind != "wo" else [],
                           [cells[write]] if kind != "ro" else [])
            open_txns.append((n_push, w, t))
            n_push += 1
        elif r < 0.8:
            for i in rng.sample(range(n_buf), rng.randrange(0, n_buf + 1)):
                e.logger_tick(i, force=True)
        else:
            drain_round()
    # every buffer flushes and heartbeats to the frontier: all commit
    for _ in range(2):
        for i in range(n_buf):
            e.logger_tick(i, force=True)
    drain_round()
    assert not open_txns
    assert n_ro > 0


def test_read_only_counts_hold_under_concurrent_drains():
    """More worker threads than cores run RMWs and reads and drain their
    own queues while the logger threads drain every queue too (committer
    assist), under a short switch interval: every txn commits once, and
    each read-only commit is counted once."""
    e = PoplarEngine(EngineConfig(n_buffers=2, device_kind="null"))
    n_threads, per_thread = 6, 150
    txns = [[] for _ in range(n_threads)]

    def work(w):
        worker = Worker(e, w)
        cells = [Cell() for _ in range(4)]
        for i in range(per_thread):
            c = cells[i % 4]
            if i % 2:
                t = Txn(tid=w * per_thread + i + 1, read_set=[("r", c.ssn)])
                worker.run(t, [c], [])
            else:
                t = Txn(tid=w * per_thread + i + 1, read_set=[("r", c.ssn)],
                        write_set=[(f"k{w}.{i % 4}", b"v")])
                worker.run(t, [c], [c])
            txns[w].append(t)
            worker.drain()

    for w in range(n_threads):
        e.register_worker(w)
    e.start()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(w,))
                   for w in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        e.quiesce(range(n_threads), timeout=30)
    finally:
        sys.setswitchinterval(old)
        e.stop()
    assert all(t.committed for ts in txns for t in ts)
    qs = [e.queues[w] for w in range(n_threads)]
    assert sum(q.ro_commits for q in qs) == n_threads * per_thread // 2
    assert all(q.pending() == 0 for q in qs)
    assert e.txn_committed == n_threads * per_thread


def test_heartbeat_unblocks_idle_buffer():
    """An idle lane must not pin the CSN forever (liveness — see
    engine._emit_heartbeat)."""
    e = _engine()
    w0, w1 = Worker(e, 0), Worker(e, 1)
    a, b = Cell(), Cell()
    # only worker 0 (buffer 0) does writes; buffer 1 stays idle
    t0 = Txn(tid=1, write_set=[("a", b"1")])
    w0.run(t0, [], [a])
    t1 = Txn(tid=3, read_set=[("a", a.ssn)], write_set=[("b", b"2")])
    t1.worker_id = 0
    e.allocate(t1, [a], [b])
    from repro.core import ssn as ssn_mod

    ssn_mod.writeback(t1.ssn, [b])
    e.publish(t1)
    # logger ticks must heartbeat buffer 1 past t1.ssn
    for i in range(2):
        e.logger_tick(i, force=True)
    for i in range(2):
        e.logger_tick(i, force=True)
    assert e.commit.csn >= t1.ssn
    assert e.drain(0) == 2
    assert t1.committed


def test_csn_is_min_of_dsns():
    e = _engine(3)
    workers = [Worker(e, i) for i in range(3)]
    cells = [Cell() for _ in range(3)]
    for i, w in enumerate(workers):
        w.run(Txn(tid=10 + i, write_set=[(f"k{i}", b"v")]), [], [cells[i]])
    # flush buffers 0 and 2 only
    for i in (0, 2):
        e.buffers[i].force_establish()
        e.buffers[i].flush_ready(e.devices[i])
    csn = e.commit.advance_csn()
    assert csn == e.buffers[1].dsn == 0
