"""Recovery equivalence: vectorized ≡ pallas ≡ scalar ≡ threaded replay on
randomized multi-device logs with torn tails and RSNe-skipped records.

Each trial drives a real Poplar engine (stepped mode, file-backed devices)
through a random mix of write-only / RAW-carrying transactions with random
per-buffer flush interleavings, "crashes" with some records never flushed,
optionally tears the tail of one device file, and then recovers through every
replay mode — the full :class:`RecoveredState` (data incl. SSNs, rsns/rsne
watermarks, replayed/skipped counts) must match byte for byte.
"""

import os
import random

import numpy as np
import pytest

from repro.core import (
    EngineConfig,
    PoplarEngine,
    Txn,
    Worker,
    decode_columnar,
    decode_records,
    recover,
    replay_columnar,
)
from repro.core.fastdecode import decode_fast_tile
from repro.core.recovery import RecoveredState, _replay_scalar, compute_rsne
from repro.core.txn import decode_columnar_stream
from repro.db import ArrayTable, BatchOCC, TxnSpec, tpcc, ycsb

KEYS = [f"k{i}" for i in range(8)] + ["k\x00nul", ""]


class _Cell:
    __slots__ = ("ssn",)

    def __init__(self):
        self.ssn = 0


def _states_equal(a: RecoveredState, b: RecoveredState) -> bool:
    return (
        a.data == b.data
        and a.rsns == b.rsns
        and a.rsne == b.rsne
        and a.n_replayed == b.n_replayed
        and a.n_skipped_uncommitted == b.n_skipped_uncommitted
    )


def _run_trial(seed: int, tmp_path) -> None:
    rng = random.Random(seed)
    n_buffers = rng.choice([1, 2, 3, 4])
    tmp = tmp_path / f"trial{seed}"
    tmp.mkdir()
    engine = PoplarEngine(
        EngineConfig(n_buffers=n_buffers, device_kind="null", device_dir=str(tmp))
    )
    workers = [Worker(engine, i) for i in range(n_buffers * 2)]
    cells = {k: _Cell() for k in KEYS}

    n_txns = rng.randrange(10, 60)
    crash_at = rng.randrange(1, n_txns + 1)
    for i in range(n_txns):
        reads = rng.sample(KEYS, rng.randrange(0, 3))
        writes = rng.sample(KEYS, rng.randrange(0, 3))
        t = Txn(
            tid=1000 + i,
            read_set=[(k, cells[k].ssn) for k in reads],
            write_set=[(k, f"{seed}/{i}/{k!r}".encode()) for k in writes],
        )
        workers[rng.randrange(len(workers))].run(
            t, [cells[k] for k in reads], [cells[k] for k in writes]
        )
        if i < crash_at:
            # random flush interleaving; beyond crash_at nothing is flushed
            for b in range(n_buffers):
                if rng.random() < 0.5:
                    engine.logger_tick(b, force=True)
            engine.commit.advance_csn()

    for d in engine.devices:
        d.close()

    # torn tail: chop a few bytes off one device's log
    if rng.random() < 0.5:
        victim = engine.devices[rng.randrange(n_buffers)]
        size = os.path.getsize(victim.path)
        if size > 4:
            with open(victim.path, "r+b") as f:
                f.seek(-rng.randrange(1, 4), os.SEEK_END)
                f.truncate()

    st_scalar = recover(engine.devices, parallel=False, mode="scalar")
    st_threaded = recover(engine.devices, parallel=True, mode="scalar")
    st_vec = recover(engine.devices, parallel=False, mode="vectorized")
    st_vec_par = recover(engine.devices, parallel=True, mode="vectorized")

    assert _states_equal(st_scalar, st_vec), seed
    assert _states_equal(st_scalar, st_vec_par), seed
    assert st_scalar.data == st_threaded.data, seed

    # pallas scatter-max apply (interpret mode) on the same logs
    st_pallas = recover(engine.devices, parallel=False, mode="pallas")
    assert _states_equal(st_scalar, st_pallas), seed


@pytest.mark.parametrize("seed", range(8))
def test_vectorized_replay_equivalence(seed, tmp_path):
    _run_trial(seed, tmp_path)


def test_ssn_tie_and_nul_key_semantics():
    """Direct-log corner cases: duplicate keys inside one record (equal SSNs
    — first write wins under the strict > guard), keys that differ only by
    trailing NULs, and a checkpoint image that wins its SSN ties."""
    def rec(ssn, writes, has_reads=False):
        t = Txn(tid=ssn, write_set=writes,
                read_set=[("r", 0)] if has_reads else [])
        t.ssn = ssn
        return t.encode()

    log0 = rec(1, [(b"a", b"first"), (b"a", b"second"), (b"a\x00", b"nul")])
    log1 = rec(2, [(b"b", b"x")], has_reads=True) + rec(3, [(b"a", b"new")])
    base = {b"a": (b"ckpt", 3), b"c": (b"keep", 1)}

    recs = [decode_records(log0), decode_records(log1)]
    cols = [decode_columnar(log0), decode_columnar(log1)]
    rsne = compute_rsne(recs)

    st = RecoveredState()
    st.data.update(base)
    _replay_scalar(st, recs, rsne, parallel=False)

    for use_kernel in (False, True):
        data, n_rep, n_skip = replay_columnar(
            cols, rsne, base=dict(base), use_kernel=use_kernel
        )
        assert data == st.data
        assert (n_rep, n_skip) == (st.n_replayed, st.n_skipped_uncommitted)

    # the checkpoint's ssn=3 ties record ssn=3: checkpoint wins (strict >)
    assert st.data[b"a"] == (b"ckpt", 3)
    # intra-record duplicate: first write of the record wins the SSN tie
    assert b"a\x00" in st.data and st.data[b"a\x00"] == (b"nul", 1)


def test_heartbeat_records_end_to_end(tmp_path):
    """`_emit_heartbeat` zero-write records: they must unpin CSN at runtime
    and RSNe at recovery, while both the columnar decode and the scalar
    replay apply no writes for them (regression for the idle-buffer liveness
    path)."""
    engine = PoplarEngine(
        EngineConfig(n_buffers=2, device_kind="null", device_dir=str(tmp_path))
    )
    w = Worker(engine, 0)  # worker 0 -> buffer 0; buffer 1 stays idle
    cells = {"a": _Cell(), "b": _Cell()}

    t1 = Txn(tid=1, write_set=[("a", b"v1")])
    w.run(t1, [], [cells["a"]])
    t2 = Txn(
        tid=2, read_set=[("a", cells["a"].ssn)], write_set=[("b", b"v2")]
    )
    w.run(t2, [cells["a"]], [cells["b"]])

    engine.logger_tick(0, force=True)  # flush buffer 0 only
    w.drain()
    assert t1.committed  # write-only: commits on its own buffer's DSN
    assert not t2.committed  # RAW-carrying: CSN pinned at 0 by idle buffer 1

    engine.logger_tick(1, force=True)  # idle buffer 1 heartbeats to frontier
    assert engine.commit.csn >= t2.ssn  # CSN unpinned
    w.drain()
    assert t2.committed

    for d in engine.devices:
        d.close()

    # the heartbeat is a zero-write tid-0 record in buffer 1's log
    cols = decode_columnar(engine.devices[1].read_all())
    assert cols.n_records >= 1
    assert (cols.n_writes == 0).all() and (cols.tid == 0).all()
    assert len(cols.wr_rec) == 0  # columnar decode carries no writes for it
    assert cols.last_ssn == t2.ssn

    expected = {b"a": (b"v1", t1.ssn), b"b": (b"v2", t2.ssn)}
    for mode in ("scalar", "vectorized", "pallas"):
        st = recover(engine.devices, parallel=False, mode=mode)
        assert st.rsne == t2.ssn, mode  # heartbeat unpins RSNe (else 0)
        assert st.data == expected, mode  # zero-write records add no keys


def test_recover_rejects_unknown_mode(tmp_path):
    engine = PoplarEngine(EngineConfig(n_buffers=1, device_kind="null"))
    with pytest.raises(ValueError):
        recover(engine.devices, mode="bogus")


def test_columnar_roundtrip_matches_rows():
    """decode_columnar(to_records) carries exactly the rows decode_records
    sees, including torn-frame truncation."""
    body = b""
    for i in range(5):
        t = Txn(tid=i, write_set=[(f"k{i}", b"v" * i)],
                read_set=[("x", 0)] if i % 2 else [])
        t.ssn = i + 1
        body += t.encode()
    torn = body[:-3]
    rows = decode_records(torn)
    cols = decode_columnar(torn)
    got = cols.to_records()
    assert [(r.ssn, r.tid, r.has_reads, r.writes) for r in rows] == [
        (r.ssn, r.tid, r.has_reads, r.writes) for r in got
    ]
    assert cols.last_ssn == rows[-1].ssn
    assert np.array_equal(cols.wr_klen, [len(k) for k, _ in sum((r.writes for r in rows), [])])


# --- the four decoders agree ---------------------------------------------------

def _served_log(traffic: str) -> bytes:
    """One device's log bytes after the batched executor ran a traffic."""
    table = ArrayTable()
    engine = PoplarEngine(EngineConfig(n_buffers=1, device_kind="null"))
    occ = BatchOCC(table, engine, n_workers=2)
    rng = random.Random(7)
    if traffic == "ycsb_wo":
        batches = [ycsb.YCSBWriteOnly(64, seed=b, theta=0.99).next_batch(16)
                   for b in range(3)]
    elif traffic == "ycsbf":
        batches = []
        for _ in range(3):
            keys = [ycsb.key_of(rng.randrange(16)) for _ in range(16)]
            batches.append([
                TxnSpec(reads=[k]) if rng.random() < 0.5 else
                TxnSpec(reads=[k], writes=[(k, rng.randbytes(100))])
                for k in keys])
    else:
        tpcc.load(table, warehouses=2)
        gen = tpcc.TPCC(None, warehouses=2, seed=3)
        batches = [gen.next_batch(8, lookup=table.get_or_insert)
                   for _ in range(3)]
    for specs in batches:
        occ.execute_batch(specs, max_rounds=2)
        engine.logger_tick(0, force=True)
    engine.devices[0].close()
    return engine.devices[0].read_all()


def _rows(records):
    return [(r.ssn, r.tid, r.has_reads, r.writes, r.xdep, r.cmd_op, r.cmd_deps)
            for r in records]


@pytest.mark.parametrize("tail", ["clean", "torn", "corrupt"])
@pytest.mark.parametrize("traffic", ["ycsb_wo", "ycsbf", "tpcc"])
def test_decoders_agree(traffic, tail):
    """``decode_records``, ``decode_columnar``, ``decode_columnar_stream``
    and ``decode_fast_tile`` yield the same records from a served log, and
    truncate a torn or corrupt tail at the same frame; the fast tile may
    decline (``None``) instead."""
    buf = _served_log(traffic)
    n_all = len(decode_records(buf))
    assert n_all > 4
    if tail == "torn":
        buf = buf[:-5]
    elif tail == "corrupt":
        mid = len(buf) // 2                 # one payload byte: crc mismatch
        buf = buf[:mid] + bytes([buf[mid] ^ 0xFF]) + buf[mid + 1:]

    rows = _rows(decode_records(buf))
    assert (len(rows) == n_all) == (tail == "clean")
    assert _rows(decode_columnar(buf).to_records()) == rows
    col, consumed = decode_columnar_stream(buf)
    assert _rows(col.to_records()) == rows
    assert (consumed == len(buf)) == (tail == "clean")

    tile = decode_fast_tile(buf)
    if tile is None:
        return
    assert tile.consumed == consumed
    np.testing.assert_array_equal(tile.ssn, [r[0] for r in rows])
    np.testing.assert_array_equal(tile.has_reads, [r[2] for r in rows])
    np.testing.assert_array_equal(tile.wr_rec, col.wr_rec)
    assert tile.keys_fixed.tolist() == col.keys_fixed.tolist()
    assert tile.values_for(np.arange(len(tile.wr_rec))) == col.values
