"""Serving-tier scheduler semantics, deterministic and randomized (stepped
mode, no wall clocks — except the one threaded test of executor faults,
which exist only in the threaded loop).

Covers: batch-cut triggers (size / latency budget / head-of-line FIFO), the
cut's conflict rule (reads share a cut; a read or write of a key written
earlier in the cut ends it) and its stop counters, a read-only ack held
to the CSN and not behind an older blocked RMW of its worker, the
ack = durable ∧ committable gate under partial flush interleavings (the
Qww/Qwr watermark rule observed end-to-end through the scheduler), the RAW
commit-order invariant under randomized flush schedules asserted against
Qwr footers in the decoded device logs, lossless-or-explicit admission
control (including the retry-capacity exemption), max_unacked backpressure,
the Zipfian generator, retry-with-backoff under hot-key skew, and an
executor fault surfacing in ``stats()``.
"""

import random
from collections import Counter

import pytest

from repro.core import EngineConfig
from repro.core.txn import decode_records
from repro.db.batch import TxnSpec
from repro.db.ycsb import COL_BYTES, RMWSpecFactory, Zipfian, key_of, load
from repro.serve import (
    ABORTED,
    ACKED,
    INFLIGHT,
    REJECTED,
    GroupCommitScheduler,
    ServeConfig,
    ShardedBackend,
    SingleBackend,
    run_stepped_schedule,
)


def _backend(tmp_path, n_workers=2, n_buffers=1, mode="vectorized",
             device_kind="null"):
    # watermark-gating tests pass device_kind="ssd": the null device is fast
    # enough that drain() self-ticks the logger (fast-device assist), which
    # would flush buffers the test deliberately holds back
    cfg = EngineConfig(n_buffers=n_buffers, device_kind=device_kind,
                       device_dir=str(tmp_path))
    return SingleBackend.make(mode, n_workers=n_workers, cfg=cfg)


def _wspec(i, val=b"v"):
    return TxnSpec(writes=[(key_of(1000 + i), val)])


# --- batch cutting ------------------------------------------------------------

def test_cut_on_max_batch(tmp_path):
    """A full queue cuts immediately, without waiting out the budget."""
    sched = GroupCommitScheduler(
        _backend(tmp_path),
        ServeConfig(max_batch=4, latency_budget_steps=10**6),
    )
    tickets = [sched.submit(_wspec(i)) for i in range(10)]
    sched.step()
    assert sched.n_cuts == 1 and sched.n_cut_txns == 4
    assert [t.status for t in tickets[:4]] == [ACKED] * 4
    assert all(t.status != ACKED for t in tickets[4:])
    sched.step()  # 6 queued >= max_batch: cuts again without budget expiry
    assert sched.n_cuts == 2 and sched.n_cut_txns == 8
    # the final 2 are below max_batch and the budget is effectively infinite:
    # they stay queued until the budget is restored
    sched.step()
    assert sched.stats()["queue_depth"] == 2 and sched.n_cuts == 2
    sched.cfg.latency_budget_steps = 1
    sched.run_until_drained()
    assert all(t.status == ACKED for t in tickets)


def test_cut_on_latency_budget(tmp_path):
    """Below max_batch, the head's wait time triggers the cut."""
    sched = GroupCommitScheduler(
        _backend(tmp_path),
        ServeConfig(max_batch=64, latency_budget_steps=3),
    )
    t = sched.submit(_wspec(0))  # t_submit = step 0
    sched.step()                 # now=1: waited 1 < 3
    sched.step()                 # now=2: waited 2 < 3
    assert t.status != ACKED and sched.n_cuts == 0
    sched.step()                 # now=3: waited 3 >= 3 -> cut
    assert sched.n_cuts == 1 and t.status == ACKED
    assert t.latency() == 3.0    # steps, by construction


def test_cut_head_of_line_fifo(tmp_path):
    """Conflicting transactions split cuts but never reorder: commit and
    ack order equal admission order, per key and globally."""
    sched = GroupCommitScheduler(
        _backend(tmp_path), ServeConfig(max_batch=64, latency_budget_steps=1)
    )
    k1, k2 = key_of(1), key_of(2)
    a = sched.submit(TxnSpec(writes=[(k1, b"a")]))
    b = sched.submit(TxnSpec(writes=[(k1, b"b")]))  # conflicts with a
    c = sched.submit(TxnSpec(writes=[(k2, b"c")]))  # behind b: FIFO holds it
    sched.step()
    # first cut is [a] alone — b conflicts, and c must not jump the queue
    assert sched.n_cut_txns == 1
    assert a.status == ACKED and b.status != ACKED and c.status != ACKED
    sched.run_until_drained()
    assert [a.ack_seq, b.ack_seq, c.ack_seq] == [0, 1, 2]
    # k1's final value is the later admission's write
    got = sched.backend.table.get(k1)
    val = got[0] if isinstance(got, tuple) else got.value
    assert val == b"b"


def _loaded(tmp_path, keys, **kw):
    be = _backend(tmp_path, **kw)
    for k in keys:
        be.table.insert(k, b"v0-" + k.encode())
    return be


def test_reads_of_one_key_share_a_cut(tmp_path):
    """Reads conflict with nothing: two read-only tickets of one key run in
    one cut and both ack, at the SSN of the version they read."""
    k = key_of(1)
    sched = GroupCommitScheduler(_loaded(tmp_path, [k]),
                                 ServeConfig(max_batch=64))
    r1 = sched.submit(TxnSpec(reads=[k]))
    r2 = sched.submit(TxnSpec(reads=[k]))
    sched.step()
    assert sched.n_cuts == 1 and sched.n_cut_txns == 2
    assert r1.status == ACKED and r2.status == ACKED
    assert r1.ssn == r2.ssn
    st = sched.stats()
    assert st["acked_read_only"] == 2 and st["cut_stop_conflict"] == 0


def test_rmw_after_a_read_of_its_key_joins_the_cut(tmp_path):
    """A write after a read of its key is no RAW or WAW pair: it joins the
    cut, both win, and the reader keeps the version it read."""
    k = key_of(1)
    sched = GroupCommitScheduler(_loaded(tmp_path, [k]),
                                 ServeConfig(max_batch=64))
    r = sched.submit(TxnSpec(reads=[k]))
    m = sched.submit(TxnSpec(reads=[k], writes=[(k, b"m")]))
    sched.step()
    assert sched.n_cuts == 1 and sched.n_cut_txns == 2
    assert r.status == ACKED and m.status == ACKED
    assert r.ssn < m.ssn
    assert r.ack_seq < m.ack_seq
    assert sched.backend.table.get(k)[0] == b"m"


def test_read_after_a_write_of_its_key_ends_the_cut(tmp_path):
    """A RAW pair never shares a cut: the reader runs in the next one and
    reads the writer's version."""
    k, k2 = key_of(1), key_of(2)
    sched = GroupCommitScheduler(_loaded(tmp_path, [k, k2]),
                                 ServeConfig(max_batch=64))
    w = sched.submit(TxnSpec(writes=[(k, b"w")]))
    r = sched.submit(TxnSpec(reads=[k]))
    behind = sched.submit(TxnSpec(reads=[k2]))   # FIFO: waits behind r
    sched.step()
    assert sched.n_cut_txns == 1
    assert w.status == ACKED and r.status != ACKED and behind.status != ACKED
    sched.run_until_drained()
    assert r.ssn == w.ssn
    assert [w.ack_seq, r.ack_seq, behind.ack_seq] == [0, 1, 2]


def test_stats_count_why_each_cut_ended(tmp_path):
    sched = GroupCommitScheduler(_backend(tmp_path),
                                 ServeConfig(max_batch=3))
    sched.submit(_wspec(0))
    sched.submit(_wspec(0))            # same key: the first cut stops here
    sched.step()                       # [a]: conflict
    sched.step()                       # [b]: queue drained
    for i in range(1, 5):
        sched.submit(_wspec(i))
    sched.step()                       # three: max_batch
    sched.step()                       # the last one: drained
    st = sched.stats()
    assert (st["cut_stop_conflict"], st["cut_stop_full"],
            st["cut_stop_drained"]) == (1, 1, 2)
    assert st["cuts"] == 4 and st["acked"] == 6
    assert st["acked_read_only"] == 0


def test_read_only_ack_waits_for_the_version_it_read(tmp_path):
    """A read-only ticket reads a version whose record sits unflushed on
    the other device: its SSN is that record's, so it is not acked until
    the CSN (least DSN over devices) reaches it, after its writer."""
    k = key_of(1)
    be = _loaded(tmp_path, [k], n_workers=2, n_buffers=2, device_kind="ssd")
    sched = GroupCommitScheduler(be, ServeConfig(max_batch=8))
    w = sched.submit(TxnSpec(writes=[(k, b"w")]))   # worker 0 -> buffer 0
    sched.step(tick_parts=[1])                      # buffer 0 held
    r = sched.submit(TxnSpec(reads=[k]))            # worker 1: w's version
    for _ in range(3):
        sched.step(tick_parts=[1])
    assert w.status == INFLIGHT and r.status == INFLIGHT
    assert r.txn.buffer_id == -1 and r.ssn == w.ssn
    assert min(b.dsn for b in be.engine.buffers) < r.ssn
    sched.step()                                    # buffer 0 flushes
    assert w.status == ACKED and r.status == ACKED
    assert w.ack_seq < r.ack_seq
    assert sched.stats()["acked_read_only"] == 1


def test_read_only_ack_passes_a_blocked_rmw_of_its_worker(tmp_path):
    """An RMW on worker 0 waits for its held buffer; a later read-only
    ticket of a durable key on the same worker acks in the step that ran
    it, not behind the RMW, and is counted as having passed it."""
    k, d = key_of(1), key_of(2)
    be = _loaded(tmp_path, [k, d], n_workers=2, n_buffers=2,
                 device_kind="ssd")
    sched = GroupCommitScheduler(be, ServeConfig(max_batch=8))
    m = sched.submit(TxnSpec(reads=[k], writes=[(k, b"m")]))  # worker 0
    sched.step(tick_parts=[1])                      # buffer 0 held
    assert m.status == INFLIGHT and m.txn.buffer_id == 0
    other = sched.submit(TxnSpec(reads=[d]))        # worker 1
    r = sched.submit(TxnSpec(reads=[d]))            # worker 0, behind m
    sched.step(tick_parts=[1])
    assert r.txn.buffer_id == -1 and r.ssn == 0
    assert r.status == ACKED and other.status == ACKED
    assert m.status == INFLIGHT
    st = sched.stats()
    assert (st["ro_commits"], st["ro_commits_passed"]) == (2, 1)
    sched.step()                                    # buffer 0 flushes
    assert m.status == ACKED and r.ack_seq < m.ack_seq
    assert sched.stats()["ro_commits_passed"] == 1


# --- ack gate: durable AND committable ---------------------------------------

def test_ack_gated_on_watermarks_partial_ticks(tmp_path):
    """With two log buffers and selective flushing, acks wait for the exact
    Qww (own-buffer DSN) / Qwr (CSN = min DSN) watermark conditions."""
    be = _backend(tmp_path, n_workers=2, n_buffers=2, device_kind="ssd")
    sched = GroupCommitScheduler(
        be, ServeConfig(max_batch=8, latency_budget_steps=1)
    )
    k, k2 = key_of(1), key_of(2)
    w = sched.submit(TxnSpec(writes=[(k, b"w")]))          # worker 0 -> buf 0
    r = sched.submit(TxnSpec(reads=[k], writes=[(k2, b"r")]))  # worker 1 -> buf 1
    sched.step(tick_parts=[1])  # cut [w]; only buffer 1 flushes
    sched.step(tick_parts=[1])  # cut [r]; r's record durable in buf 1
    # w's record sits unflushed in buffer 0: w fails Qww (own DSN), and r
    # fails Qwr (CSN = min DSN is pinned by buffer 0) even though its own
    # record is durable
    assert w.status == INFLIGHT and r.status == INFLIGHT
    sched.step()  # full tick: w durable -> acked; CSN still below r's SSN
    assert w.status == ACKED
    assert r.status == INFLIGHT
    sched.step()  # idle buffer 0 heartbeats to the frontier; CSN catches up
    assert r.status == ACKED
    assert w.ack_seq < r.ack_seq


@pytest.mark.parametrize("seed", range(5))
def test_raw_commit_order_randomized(seed, tmp_path):
    """Randomized stepped interleavings: writers write unique keys, readers
    carry RAW dependencies on earlier writers.  Invariants, checked against
    the ack sequence AND the decoded device logs:

    * every admitted transaction acks (liveness under partial flushing);
    * a RAW-dependent reader acks strictly after each of its predecessor
      writers, and its SSN exceeds theirs;
    * its log record carries the Qwr footer (has_reads) — the recovery-time
      witness of the commit-order constraint — and writers carry none.
    """
    rng = random.Random(seed)
    be = _backend(tmp_path, n_workers=2, n_buffers=2, device_kind="ssd")
    sched = GroupCommitScheduler(
        be,
        ServeConfig(max_batch=rng.choice([2, 4, 8]), latency_budget_steps=1,
                    queue_capacity=10**6),
    )
    n = rng.randrange(8, 30)
    schedule, preds, written = [], [], []
    at = 0
    for i in range(n):
        at += rng.randrange(0, 2)
        if written and rng.random() < 0.5:
            picks = rng.sample(written, min(len(written), rng.randrange(1, 3)))
            reads = [k for k, _ in picks]
            preds.append([j for _, j in picks])
        else:
            reads = []
            preds.append([])
        wkey = key_of(1000 + i)
        schedule.append((at, TxnSpec(reads=reads,
                                     writes=[(wkey, b"v%d" % i)])))
        written.append((wkey, i))

    trng = random.Random(seed + 777)
    tickets = run_stepped_schedule(
        sched, schedule,
        tick_parts_fn=lambda step: trng.choice([None, None, [0], [1], []]),
    )
    assert all(t.status == ACKED for t in tickets)

    by_tid = {}
    for dev in be.engine.devices:
        for rec in decode_records(dev.read_all()):
            if rec.tid:  # tid 0 = heartbeat records
                by_tid[rec.tid] = rec
    for i, t in enumerate(tickets):
        rec = by_tid[t.txn.tid]
        assert rec.ssn == t.ssn
        assert rec.has_reads == bool(schedule[i][1].reads)  # Qwr footer
        for p in preds[i]:
            assert tickets[p].ack_seq < t.ack_seq, (i, p)
            assert tickets[p].ssn < t.ssn, (i, p)


# --- admission control: lossless or explicit ---------------------------------

def test_admission_overflow_explicit_reject(tmp_path):
    """Deterministic queue overflow: beyond capacity, submissions are
    refused explicitly at submit time; every *admitted* transaction still
    terminates ACKED.  Statuses exactly partition the submissions — nothing
    is silently dropped."""
    sched = GroupCommitScheduler(
        _backend(tmp_path),
        ServeConfig(max_batch=2, latency_budget_steps=1, queue_capacity=4),
    )
    tickets = [sched.submit(_wspec(i)) for i in range(12)]
    assert [t.status for t in tickets[4:]] == [REJECTED] * 8
    assert sched.n_admitted == 4 and sched.n_rejected == 8
    sched.run_until_drained()
    counts = Counter(t.status for t in tickets)
    assert counts == {ACKED: 4, REJECTED: 8}
    assert sched.n_admitted + sched.n_rejected == sched.n_submitted
    # capacity freed: new submissions are admitted again and complete
    t = sched.submit(_wspec(99))
    assert t.status != REJECTED
    sched.run_until_drained()
    assert t.status == ACKED


def test_retry_is_capacity_exempt(tmp_path):
    """A validation loser must re-enter the queue even when new arrivals
    have filled it to capacity: retries are already-admitted work, so the
    admission bound does not apply to them (re-admitting them through the
    bounded queue would silently drop them exactly under overload).  The
    loser re-enters at the *front* and completes."""
    be = _backend(tmp_path)
    load(be.table, 4, seed=7)
    sched = GroupCommitScheduler(
        be,
        ServeConfig(max_batch=8, latency_budget_steps=1, queue_capacity=2,
                    backoff_steps=1, max_retries=3),
    )
    k = key_of(0)

    def rmw():
        got = be.table.get_or_insert(k)
        val, ssn = got if isinstance(got, tuple) else (got.value, got.ssn)
        return TxnSpec(reads=[k], writes=[(k, val[:8] + b"!")], observed=[ssn])

    t1 = sched.submit(make_spec=rmw)
    t2 = sched.submit(make_spec=rmw)  # same key: observed SSN goes stale
    assert t1.status != REJECTED and t2.status != REJECTED
    sched.step()   # cut [t1] (head-of-line), ack t1
    sched.step()   # cut [t2]: t2's observed SSN is stale -> retry backoff
    assert t1.status == ACKED and sched.n_retries == 1 and t2.attempts == 2
    # flood the queue to capacity while t2 is in backoff
    f1, f2 = sched.submit(_wspec(1)), sched.submit(_wspec(2))
    f3 = sched.submit(_wspec(3))
    assert f1.status != REJECTED and f2.status != REJECTED
    assert f3.status == REJECTED  # capacity enforced for *new* admissions
    sched.run_until_drained()
    # ...but the retry re-entered (front of queue) and acked before the flood
    assert t2.status == ACKED and t2.attempts == 2
    assert t2.ack_seq < f1.ack_seq < f2.ack_seq
    got = be.table.get_or_insert(k)
    val = got[0] if isinstance(got, tuple) else got.value
    assert val[:9].endswith(b"!")


def test_backpressure_max_unacked(tmp_path):
    """Durability-lag backpressure: with flushing stalled, at most
    max_unacked transactions are executed-but-unacked; cutting resumes as
    acks release."""
    sched = GroupCommitScheduler(
        _backend(tmp_path, device_kind="ssd"),
        ServeConfig(max_batch=2, latency_budget_steps=1, max_unacked=2),
    )
    tickets = [sched.submit(_wspec(i)) for i in range(6)]
    for _ in range(5):
        sched.step(tick_parts=[])  # execute but never flush
    st = sched.stats()
    assert st["max_unacked"] == 2        # cutter stalled at the cap
    assert st["queue_depth"] == 4        # the rest stayed queued
    assert all(t.status != ACKED for t in tickets)
    sched.run_until_drained()            # full ticks: drains in waves of <= 2
    assert all(t.status == ACKED for t in tickets)
    assert sched.stats()["max_unacked"] == 2


# --- zipfian ------------------------------------------------------------------

def test_zipfian_distribution():
    z = Zipfian(1000, theta=0.99, seed=3)
    s = z.sample(50_000)
    assert s.min() >= 0 and s.max() < 1000
    freq = Counter(s.tolist())
    # rank 0 is the hottest, by a wide margin over the tail
    assert freq[0] > freq.most_common(20)[-1][1]
    assert freq[0] / len(s) > 0.05                    # heavy head
    assert freq[0] >= freq[1] >= freq[5] > freq[500]  # monotone-ish decay
    # deterministic under the seed
    assert Zipfian(1000, 0.99, seed=3).sample(100).tolist() == \
        Zipfian(1000, 0.99, seed=3).sample(100).tolist()
    # theta=0 degenerates to (near-)uniform
    u = Zipfian(1000, theta=0.0, seed=3).sample(50_000)
    assert Counter(u.tolist()).most_common(1)[0][1] / len(u) < 0.01


def test_retry_with_backoff_under_skew(tmp_path):
    """Zipf-hot read-modify-write clients: losers retry with regenerated
    specs and eventually win; exhausted tickets abort explicitly after
    exactly 1 + max_retries attempts; the final table state equals the net
    effect of exactly the acked transactions (each RMW flips the first
    column's bits, so per-key XOR parity is the oracle)."""
    be = _backend(tmp_path, n_workers=2)
    n_keys = 8
    load(be.table, n_keys, seed=7)
    before = {key_of(i): be.table.get(key_of(i))[0] for i in range(n_keys)}
    fac = RMWSpecFactory(be.table, n_keys, seed=11, theta=0.9)
    sched = GroupCommitScheduler(
        be,
        ServeConfig(max_batch=8, latency_budget_steps=1, max_retries=4,
                    backoff_steps=1, queue_capacity=10**6),
    )
    tickets = [sched.submit(make_spec=fac.spec_fn(), client_id=i)
               for i in range(40)]
    sched.run_until_drained(max_steps=5000)
    assert all(t.status in (ACKED, ABORTED) for t in tickets)
    assert sched.n_retries > 0  # skew actually produced conflicts
    for t in tickets:
        if t.status == ABORTED:
            assert t.attempts == 1 + sched.cfg.max_retries
    acked_per_key = Counter(t.spec.writes[0][0] for t in tickets
                            if t.status == ACKED)
    for i in range(n_keys):
        k = key_of(i)
        head = before[k][:COL_BYTES]
        if acked_per_key[k] % 2:
            head = bytes(b ^ 0xFF for b in head)
        assert be.table.get(k)[0][:COL_BYTES] == head, k


# --- sharded serving ----------------------------------------------------------

def test_sharded_serving_with_cross_shard(tmp_path):
    """The scheduler over a ShardedBackend: single-shard and cross-shard
    transactions interleave; cross-shard acks release only after the
    coordinator's durable-on-all sweep marks them committed."""
    be = ShardedBackend.make(n_shards=2, n_buffers=1, n_workers=2,
                             device_kind="null", device_dir=str(tmp_path))
    sched = GroupCommitScheduler(
        be, ServeConfig(max_batch=8, latency_budget_steps=1)
    )
    shard0 = [k for k in (key_of(i) for i in range(40))
              if be.eng.shard_of(k) == 0]
    shard1 = [k for k in (key_of(i) for i in range(40))
              if be.eng.shard_of(k) == 1]
    singles = [sched.submit(TxnSpec(writes=[(k, b"s-" + k.encode())]))
               for k in (shard0[:3] + shard1[:3])]
    cross = sched.submit(TxnSpec(writes=[(shard0[5], b"x0"),
                                         (shard1[5], b"x1")]))
    sched.run_until_drained()
    assert all(t.status == ACKED for t in singles + [cross])
    assert cross.txn.committed and len(cross.txn.parts) == 2
    data = be.eng.to_dict()
    for k in shard0[:3] + shard1[:3]:
        assert data[k.encode()][0] == b"s-" + k.encode()
    assert data[shard0[5].encode()][0] == b"x0"
    assert data[shard1[5].encode()][0] == b"x1"


def test_sharded_cross_shard_reader_ends_the_cut_for_a_writer(tmp_path):
    """A cross-shard ticket's prepare runs after the cut's single-shard
    sub-batches, so a later single-shard writer of a key it reads must wait
    for the next cut; a single-shard writer after a single-shard reader
    joins."""
    be = ShardedBackend.make(n_shards=2, n_buffers=1, n_workers=2,
                             device_kind="null", device_dir=str(tmp_path))
    keys = [key_of(i) for i in range(40)]
    a0, b0 = [k for k in keys if be.eng.shard_of(k) == 0][:2]
    a1 = [k for k in keys if be.eng.shard_of(k) == 1][0]
    for k in (a0, b0, a1):
        be.eng.insert(k, b"v0")
    sched = GroupCommitScheduler(be, ServeConfig(max_batch=8))
    x = sched.submit(TxnSpec(reads=[a0, a1], writes=[(b0, b"x")]))
    w = sched.submit(TxnSpec(writes=[(a0, b"w")]))
    sched.step()
    assert sched.n_cut_txns == 1 and sched.stats()["cut_stop_conflict"] == 1
    sched.run_until_drained()
    assert x.status == ACKED and w.status == ACKED
    assert x.ssn < w.ssn and x.ack_seq < w.ack_seq
    r = sched.submit(TxnSpec(reads=[a0]))
    m = sched.submit(TxnSpec(reads=[a0], writes=[(a0, b"m")]))
    sched.step()
    assert sched.n_cuts == 3 and sched.n_cut_txns == 4
    assert r.status == ACKED and m.status == ACKED


# --- executor faults (threaded loop) -----------------------------------------

def test_executor_fault_kept_in_stats(tmp_path):
    """An executor exception aborts its cut explicitly, is counted and kept
    (traceback) in ``stats()``, and the loop keeps serving afterwards."""
    be = _backend(tmp_path)
    real_execute = be.execute
    calls = []

    def faulty_execute(specs, worker_ids=None, max_rounds=1):
        calls.append(len(specs))
        if len(calls) == 1:
            raise RuntimeError("injected executor fault")
        return real_execute(specs, worker_ids=worker_ids,
                            max_rounds=max_rounds)

    be.execute = faulty_execute
    sched = GroupCommitScheduler(be, ServeConfig(latency_budget_s=1e-4))
    assert sched.stats()["last_exec_error"] is None
    sched.start()
    try:
        first = sched.submit(_wspec(0))
        assert first.wait(timeout=10) == ABORTED
        second = sched.submit(_wspec(1))
        assert second.wait(timeout=10) == ACKED
    finally:
        sched.stop(quiesce=True)
    st = sched.stats()
    assert st["exec_errors"] == 1 and st["acked"] == 1
    assert "RuntimeError: injected executor fault" in st["last_exec_error"]
