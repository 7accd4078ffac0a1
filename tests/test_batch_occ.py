"""Batched forward-path equivalence (paper §4.2/§4.4, batched).

Property: on randomized YCSB-style batches — conflict-free and
conflict-heavy, with and without driver-observed SSNs — the batched
array-native pipeline (`BatchOCC`: vectorized OCC + batched Algorithm-1
allocation via ``reserve_batch`` + ``encode_batch``/``publish_batch``)
produces *exactly* what the scalar per-transaction machinery
(`ScalarBatchOCC`: dict Table cells, per-txn ``engine.allocate`` +
``Txn.encode`` + ``engine.publish``) produces under the same batch
semantics:

* the same winners/losers per round, the same tids, the same per-txn SSNs
  and read/write sets;
* the same final per-tuple (value, SSN) state;
* byte-identical device logs — so records written via ``publish_batch``
  recover byte-identically through the existing vectorized ``recover()``.
"""

import random

import numpy as np
import pytest

from repro.core import EngineConfig, PoplarEngine, Txn, encode_batch, recover
from repro.db import ArrayTable, BatchOCC, ScalarBatchOCC, Table, TxnSpec
from repro.db import ycsb
from repro.db.batch import _concat_ranges


def _mk_engine(tmp_path, tag: str, n_buffers: int) -> PoplarEngine:
    d = tmp_path / tag
    d.mkdir()
    # flush_interval is explicit (conftest leaves it alone) and effectively
    # infinite: heartbeats are wall-clock-gated, and the scalar oracle's
    # slower per-txn drains would otherwise cross the interval on a slow
    # machine and heartbeat-bump its buffer SSN chains to the frontier while
    # the faster batched engine's drains don't — breaking SSN equivalence
    # nondeterministically.  quiesce() force-ticks at the end, so both
    # engines still heartbeat/flush identically from identical states.
    return PoplarEngine(
        EngineConfig(n_buffers=n_buffers, device_kind="null",
                     device_dir=str(d), flush_interval=60.0)
    )


def _gen_batch(rng, keys, batch_size, scalar_table, with_observed):
    specs = []
    for i in range(batch_size):
        reads = rng.sample(keys, rng.randrange(0, 3))
        writes = [
            (k, rng.randbytes(rng.randrange(0, 40)))
            for k in rng.sample(keys, rng.randrange(0, 3))
        ]
        if not reads and not writes:
            writes = [(keys[0], b"fallback")]
        observed = None
        if with_observed and reads and rng.random() < 0.4:
            observed = [scalar_table.get_or_insert(k).ssn for k in reads]
            if rng.random() < 0.3:
                # deliberately stale: exercises the vectorized observed-SSN
                # abort path
                observed[rng.randrange(len(observed))] += 1
        specs.append(TxnSpec(reads=reads, writes=writes, observed=observed))
    return specs


def _run_trial(seed: int, tmp_path, mode: str) -> None:
    rng = random.Random(seed)
    n_buffers = rng.choice([1, 2, 3])
    n_workers = n_buffers * 2
    # small keyspace => conflict-heavy batches; large => mostly conflict-free
    n_keys = rng.choice([6, 60])
    keys = [ycsb.key_of(i) for i in range(n_keys)]

    tab_s = Table()
    tab_v = ArrayTable()
    for k in keys[: n_keys // 2]:  # half preloaded, half created by specs
        v = rng.randbytes(8)
        tab_s.insert(k, v)
        tab_v.insert(k, v)
    eng_s = _mk_engine(tmp_path, "scalar", n_buffers)
    eng_v = _mk_engine(tmp_path, "vec", n_buffers)
    oracle = ScalarBatchOCC(tab_s, eng_s, n_workers=n_workers)
    batched = BatchOCC(tab_v, eng_v, n_workers=n_workers, mode=mode)

    for _ in range(rng.randrange(2, 5)):
        specs = _gen_batch(rng, keys, rng.randrange(1, 24), tab_s,
                           with_observed=True)
        max_rounds = rng.choice([1, 2, 3])
        rs = oracle.execute_batch(specs, max_rounds=max_rounds)
        rv = batched.execute_batch(specs, max_rounds=max_rounds)

        assert rs.committed_idx == rv.committed_idx, seed
        assert rs.aborted == rv.aborted, seed
        assert rs.rounds == rv.rounds, seed
        for ts, tv in zip(rs.committed, rv.committed):
            assert (ts.tid, ts.ssn, ts.worker_id) == (tv.tid, tv.ssn, tv.worker_id), seed
            assert ts.read_set == tv.read_set and ts.write_set == tv.write_set, seed
        oracle.drain()
        batched.drain()

    # identical per-tuple (value, ssn) state
    state_s = {
        k: (tab_s.get(k).value, tab_s.get(k).ssn) for k in keys if tab_s.get(k)
    }
    state_v = {k: tab_v.get(k) for k in keys if tab_v.get(k) is not None}
    assert state_s == state_v, seed

    eng_s.quiesce(range(n_workers))
    eng_v.quiesce(range(n_workers))
    for d in eng_s.devices + eng_v.devices:
        d.close()

    # byte-identical logs, and batch-published records recover byte-identically
    assert [d.read_all() for d in eng_s.devices] == [
        d.read_all() for d in eng_v.devices
    ], seed
    st_s = recover(eng_s.devices, mode="vectorized", parallel=False)
    st_v = recover(eng_v.devices, mode="vectorized", parallel=False)
    assert st_s.data == st_v.data and st_s.rsne == st_v.rsne, seed
    # the recovered image agrees with the live columnar table wherever the
    # log wrote (uncontacted preloaded keys aren't in the log)
    live = tab_v.to_dict()
    for kb, (val, ssn) in st_v.data.items():
        assert live[kb] == (val, ssn), seed


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("mode", ["vectorized", "pallas"])
def test_batched_equals_scalar_oracle(mode, seed, tmp_path):
    _run_trial(seed, tmp_path, mode=mode)


def test_ycsb_write_only_batch(tmp_path):
    """The fig5 configuration in miniature: write-only YCSB batches through
    the batched pipeline, recovered through vectorized recover()."""
    n_records = 200
    tab = ArrayTable()
    ycsb.load(tab, n_records)
    eng = _mk_engine(tmp_path, "ycsb", 2)
    occ = BatchOCC(tab, eng, n_workers=4)
    wl = ycsb.YCSBWriteOnly(n_records, seed=5)
    total = 0
    for _ in range(4):
        specs = wl.next_batch(64)
        res = occ.execute_batch(specs, max_rounds=2)
        total += len(res.committed)
        assert len(res.committed) + len(res.aborted) == len(specs)
        occ.drain()
    assert total > 0
    eng.quiesce(range(4))
    for d in eng.devices:
        d.close()
    st = recover(eng.devices, mode="vectorized")
    live = tab.to_dict()
    assert st.data  # something durable
    for kb, pair in st.data.items():
        assert live[kb] == pair


def test_tpcc_batch_driver(tmp_path):
    """TPC-C batch generation against the columnar store: read-modify-write
    specs carry observed SSNs and commit through the batched pipeline."""
    from repro.db import tpcc

    tab = ArrayTable()
    tpcc.load(tab, warehouses=2)
    eng = _mk_engine(tmp_path, "tpcc", 2)
    occ = BatchOCC(tab, eng, n_workers=2)
    wl = tpcc.TPCC(Table(), warehouses=2, seed=3)  # dict table unused w/ lookup
    specs = wl.next_batch(16, lookup=tab.get_or_insert)
    res = occ.execute_batch(specs, max_rounds=1)
    assert len(res.committed) >= 1
    # all committed specs validated their observed SSNs against live state
    occ.drain()
    eng.quiesce(range(2))


def test_indexed_equals_spec_path(tmp_path):
    """`execute_indexed` (read/write-index arrays, columnar framing from the
    table's key columns) ≡ `execute_batch` (string-keyed specs) on the same
    batches: same winners, tids, SSNs, final state, byte-identical logs."""
    n_records = 100
    tab_a, tab_b = ArrayTable(), ArrayTable()
    ycsb.load(tab_a, n_records)
    ycsb.load(tab_b, n_records)
    eng_a = _mk_engine(tmp_path, "spec", 2)
    eng_b = _mk_engine(tmp_path, "idx", 2)
    occ_a = BatchOCC(tab_a, eng_a, n_workers=4)
    occ_b = BatchOCC(tab_b, eng_b, n_workers=4)
    rng = random.Random(7)
    for it in range(3):
        bsz = 40
        kidx = [rng.randrange(n_records) for _ in range(bsz)]
        vals = [rng.randbytes(rng.randrange(0, 30)) for _ in range(bsz)]
        # every third txn also reads a random row (Qwr routing + flag)
        rd = [[rng.randrange(n_records)] if i % 3 == 0 else [] for i in range(bsz)]
        specs = [
            TxnSpec(reads=[ycsb.key_of(r) for r in rd[i]],
                    writes=[(ycsb.key_of(kidx[i]), vals[i])])
            for i in range(bsz)
        ]
        r_a = occ_a.execute_batch(specs, max_rounds=2)

        rd_row = np.asarray([r for rs in rd for r in rs], dtype=np.int64)
        rd_start = np.zeros(bsz + 1, dtype=np.int64)
        np.cumsum([len(rs) for rs in rd], out=rd_start[1:])
        r_b = occ_b.execute_indexed(
            rd_row, rd_start,
            np.asarray(kidx, dtype=np.int64),
            np.arange(bsz + 1, dtype=np.int64),
            vals, max_rounds=2,
        )
        assert r_a.committed_idx == r_b.committed_idx, it
        assert r_a.aborted == r_b.aborted, it
        for ta, tb in zip(r_a.committed, r_b.committed):
            assert (ta.tid, ta.ssn, ta.worker_id) == (tb.tid, tb.ssn, tb.worker_id)
            assert ta.write_only == tb.write_only
        occ_a.drain()
        occ_b.drain()

    assert tab_a.to_dict() == tab_b.to_dict()
    eng_a.quiesce(range(4))
    eng_b.quiesce(range(4))
    for d in eng_a.devices + eng_b.devices:
        d.close()
    assert [d.read_all() for d in eng_a.devices] == [
        d.read_all() for d in eng_b.devices
    ]


def test_encode_batch_matches_scalar_encode():
    """encode_batch is byte-identical to per-record Txn.encode."""
    rng = random.Random(9)
    txns = []
    for i in range(20):
        t = Txn(
            tid=i + 1,
            read_set=[("r", 0)] if i % 3 == 0 else [],
            write_set=[
                (f"key{j}", rng.randbytes(rng.randrange(0, 30)))
                for j in range(rng.randrange(0, 4))
            ],
        )
        t.ssn = 100 + i
        txns.append(t)
    blob, lengths = encode_batch(txns)
    scalar = [t.encode() for t in txns]
    assert blob == b"".join(scalar)
    assert lengths.tolist() == [len(r) for r in scalar]


def test_concat_ranges():
    starts = np.array([0, 5, 9], dtype=np.int64)
    lens = np.array([2, 0, 3], dtype=np.int64)
    assert _concat_ranges(starts, lens).tolist() == [0, 1, 9, 10, 11]
    assert _concat_ranges(starts[:0], lens[:0]).tolist() == []


def test_reserve_batch_matches_serial_reserve():
    """One reserve_batch == N serial reserves: same SSN chain, same offsets,
    same final buffer state (Algorithm 1 equivalence at the buffer level)."""
    from repro.core.log_buffer import LogBuffer

    rng = random.Random(11)
    a = LogBuffer(0, capacity=1 << 20)
    b = LogBuffer(0, capacity=1 << 20)
    a.ssn = b.ssn = 7
    bases = np.array([rng.randrange(0, 30) for _ in range(50)], dtype=np.int64)
    lengths = np.array([rng.randrange(29, 200) for _ in range(50)], dtype=np.int64)
    ssns, offsets, _ = a.reserve_batch(bases, lengths)
    serial = [b.reserve(int(bs), int(ln))[:2] for bs, ln in zip(bases, lengths)]
    assert ssns.tolist() == [s for s, _ in serial]
    assert offsets.tolist() == [o for _, o in serial]
    assert (a.ssn, a.offset) == (b.ssn, b.offset)
