"""Crash injection under open-loop serving load.

The serving tier's ack contract is: an acknowledged transaction is durable
and committable, so it must survive any crash *after* the ack — including a
mid-flush kill that leaves a torn frame at the device tail (the same
physical injection as test_crash_injection.py).  The acked set at kill time
is therefore an exact recovery oracle:

* every ACKED ticket's write is present in the recovered state;
* no torn-tail or never-flushed ("lost tail") value is ever recovered;
* un-acked work may or may not have reached the device — no constraint,
  which is precisely why the ack gate, not execution, is the contract.

One threaded run (real clocks, Poisson open-loop arrivals, kill mid-stream)
and one deterministic stepped sharded run (partial per-shard flushing, cut
off mid-drain) — single-shard and cross-shard transactions both covered.

The kill-point sweep then crashes the compiled (``mode="pallas"``) served
path the benchmark cells run, single-engine and 2-shard, under Zipfian
blind writes, Zipfian reads and read-modify-writes, and TPC-C
New-Order/Payment, at five stepped kill points each.  Besides the acked
prefix it checks that ``recover(mode="pallas")`` equals the scalar oracle
and that an acked read-only transaction's reads survive: the recovered SSN
of every key it read is at least the version it read.
"""

import os
import random
import time

import pytest

from repro.core import EngineConfig, Txn, recover
from repro.db import tpcc
from repro.db.batch import TxnSpec
from repro.db.ycsb import YCSBWriteOnly, Zipfian, key_of
from repro.serve import (
    ACKED,
    INFLIGHT,
    GroupCommitScheduler,
    ServeConfig,
    ShardedBackend,
    SingleBackend,
)
from repro.shard import recover_sharded


def _torn_record(key: str, cut: int = 7) -> bytes:
    t = Txn(tid=777777, write_set=[(key, b"TORN-VALUE-NEVER-COMMITTED")])
    t.ssn = 1 << 40  # would win every last-writer-wins race if replayed
    rec = t.encode()
    assert cut < len(rec)
    return rec[:-cut]


def test_open_loop_kill_torn_tail(tmp_path):
    cfg = EngineConfig(n_buffers=2, device_kind="ssd",
                       device_dir=str(tmp_path), device_clock="real",
                       flush_interval=1e-3, logger_poll=1e-4)
    be = SingleBackend.make("vectorized", n_workers=2, cfg=cfg)
    sched = GroupCommitScheduler(
        be, ServeConfig(latency_budget_s=5e-4, queue_capacity=10**6)
    )
    sched.start()
    tickets = []
    try:
        # open-loop: submit at a steady offered rate, never awaiting acks,
        # then kill mid-stream — later submissions are still in flight
        for i in range(120):
            tickets.append(sched.submit(
                TxnSpec(writes=[(key_of(2000 + i), b"val-%d" % i)]),
                client_id=i,
            ))
            time.sleep(2e-4)
    finally:
        sched.stop(quiesce=False)   # kill: no final flush, no final drain

    acked = [t for t in tickets if t.status == ACKED]
    unacked = [t for t in tickets if t.status != ACKED]
    assert acked, "no transaction acked before the kill"

    # writes buffered after the kill are never flushed (the crash tail)
    be.occ.execute_batch(
        [TxnSpec(writes=[(key_of(9000 + i), b"lost-%d" % i)]) for i in range(4)]
    )
    for d in be.engine.devices:
        d.close()

    # mid-flush kill: a partial frame lands at the end of device 0
    with open(os.path.join(str(tmp_path), "log_0.bin"), "ab") as f:
        f.write(_torn_record(key_of(2000)))
        f.flush()
        os.fsync(f.fileno())

    state = recover(be.engine.devices, parallel=False)
    for v, _ in state.data.values():
        assert v != b"TORN-VALUE-NEVER-COMMITTED"
        assert not v.startswith(b"lost-")
    # acked-prefix oracle: every acked write survives, exactly (keys are
    # written once, so value and SSN must match the ticket)
    for t in acked:
        k, v = t.spec.writes[0]
        assert state.data[k.encode()] == (v, t.ssn), k
    # recovered un-acked writes are uncorrupted (prefix property: whatever
    # of the tail did reach the device is the real record)
    for t in unacked:
        k, v = t.spec.writes[0]
        got = state.data.get(k.encode())
        assert got is None or got[0] == v


def test_stepped_sharded_kill_torn_tail(tmp_path):
    be = ShardedBackend.make(n_shards=2, n_buffers=1, n_workers=2,
                             device_kind="ssd", device_dir=str(tmp_path))
    sched = GroupCommitScheduler(
        be, ServeConfig(max_batch=4, latency_budget_steps=1)
    )
    keys = [key_of(3000 + i) for i in range(30)]
    by_shard = [[k for k in keys if be.eng.shard_of(k) == s] for s in (0, 1)]
    tickets = [sched.submit(TxnSpec(writes=[(k, b"s-" + k.encode())]))
               for k in keys]
    # cross-shard transaction on fresh keys (one per shard, written nowhere
    # else, so the acked/un-acked oracle stays exact per key)
    xk = [next(k for k in (key_of(4000 + i) for i in range(40))
               if be.eng.shard_of(k) == s) for s in (0, 1)]
    cross = sched.submit(TxnSpec(writes=[(xk[0], b"x0"), (xk[1], b"x1")]))
    tickets.append(cross)
    # a few full steps, then steps that flush only shard 0 — shard 1's tail
    # stays volatile — then stop mid-drain (no quiesce: this is the crash)
    for _ in range(4):
        sched.step()
    for _ in range(3):
        sched.step(tick_parts=[0])
    acked = [t for t in tickets if t.status == ACKED]
    unacked = [t for t in tickets if t.status != ACKED]
    assert acked and unacked, "want a genuine mid-drain kill"

    for devs in be.eng.devices:
        for d in devs:
            d.close()
    with open(os.path.join(str(tmp_path), "shard1", "log_0.bin"), "ab") as f:
        f.write(_torn_record(by_shard[1][0]))
        f.flush()
        os.fsync(f.fileno())

    st = recover_sharded(be.eng.devices, parallel=False)
    for v, _ in st.data.values():
        assert v != b"TORN-VALUE-NEVER-COMMITTED"
    for t in acked:
        for k, v in t.spec.writes:
            assert st.data[k.encode()][0] == v, k
    for t in unacked:
        for k, v in t.spec.writes:
            got = st.data.get(k.encode())
            assert got is None or got[0] == v


# --- kill-point sweep on the compiled served path -----------------------------

N_KEYS = 64          # Zipf 0.99 key space of the YCSB traffics
PER_STEP = 4         # open-loop arrivals submitted before each step
N_TICKETS = 17 * PER_STEP   # enough for the longest kill point
TORN = b"TORN-VALUE-NEVER-COMMITTED"


def _sweep_backend(kind, tmp_path):
    if kind == "single":
        cfg = EngineConfig(n_buffers=2, device_kind="ssd",
                           device_dir=str(tmp_path))
        return SingleBackend.make("pallas", n_workers=2, cfg=cfg)
    return ShardedBackend.make(n_shards=2, mode="pallas", n_workers=2,
                               device_kind="ssd", device_dir=str(tmp_path),
                               device_clock="virtual")


def _sweep_traffic(kind, be, seed):
    """Preload the keys the traffic reads; return one ``spec`` or
    ``make_spec`` keyword for ``GroupCommitScheduler.submit`` per ticket."""
    if kind == "tpcc":
        tpcc.load(be.table, warehouses=2)
        gen = tpcc.TPCC(None, warehouses=2, seed=seed)
        # New-Order/Payment read-modify-writes re-read on every attempt
        return [dict(make_spec=lambda: gen.next_spec(be.table.get))
                for _ in range(N_TICKETS)]
    for i in range(N_KEYS):
        be.table.insert(key_of(i), b"seed%d" % i)
    if kind == "ycsb_wo":
        specs = YCSBWriteOnly(N_KEYS, seed=seed, theta=0.99).next_specs(N_TICKETS)
        return [dict(spec=s) for s in specs]
    # YCSB-F shape: half reads, half read-modify-writes of Zipf-hot keys
    rng = random.Random(seed)
    keys = Zipfian(N_KEYS, theta=0.99, seed=seed).sample(N_TICKETS).tolist()
    submits = []
    for i, r in enumerate(keys):
        k = key_of(r)
        if rng.random() < 0.5:
            submits.append(dict(spec=TxnSpec(reads=[k])))
        else:
            submits.append(dict(spec=TxnSpec(
                reads=[k], writes=[(k, b"rmw-%d-" % i + rng.randbytes(16))])))
    return submits


def _write_ssn(be, t, key):
    """The SSN ``t`` wrote ``key`` at: its own, or on a sharded backend the
    SSN of its record on the key's shard."""
    if isinstance(t.txn, Txn):
        return t.txn.ssn
    shard = be.eng.shard_of(key)
    return next(p.ssn for p in t.txn.parts if p.shard == shard)


@pytest.mark.parametrize("kill", range(5))
@pytest.mark.parametrize("traffic", ["ycsb_wo", "ycsbf", "tpcc"])
@pytest.mark.parametrize("backend", ["single", "sharded"])
def test_kill_point_sweep(backend, traffic, kill, tmp_path):
    be = _sweep_backend(backend, tmp_path)
    submits = _sweep_traffic(traffic, be, seed=kill)
    sched = GroupCommitScheduler(be, ServeConfig(max_batch=8,
                                                 latency_budget_steps=1))
    # full steps, then steps that flush only part 0 (buffer 0, or shard 0)
    # until part 1 holds executed but unacknowledged work, and one more (its
    # reads may see that work); then the crash, with no quiesce, catching
    # the next arrivals still queued
    n_full = 1 + 2 * kill
    tickets = []
    arrivals = iter(submits)

    def arrive():
        for sub in [next(arrivals) for _ in range(PER_STEP)]:
            tickets.append(sched.submit(client_id=len(tickets), **sub))

    for _ in range(n_full):
        arrive()
        sched.step()
    for _ in range(6):
        arrive()
        sched.step(tick_parts=[0])
        if any(t.status == INFLIGHT for t in tickets):
            break
    arrive()
    sched.step(tick_parts=[0])
    arrive()
    acked = [t for t in tickets if t.status == ACKED]
    assert acked, "no transaction acked before the kill"
    assert any(t.status == INFLIGHT for t in tickets), "no in-flight work"

    # writes executed after the kill are never flushed (the lost tail)
    hot = [k for t in tickets for k, _ in t.spec.writes][:4]
    lost = [TxnSpec(writes=[(k, b"LOST-%d" % j)])
            for j, k in enumerate(dict.fromkeys(hot))]
    if backend == "single":
        be.occ.execute_batch(lost)
        devices = [be.engine.devices]
    else:
        be.eng.execute_batch(lost)
        devices = be.eng.devices
    for devs in devices:
        for d in devs:
            d.close()
    # mid-flush kill: a partial frame lands at the end of the held-back part
    tail = ("log_1.bin" if backend == "single"
            else os.path.join("shard1", "log_0.bin"))
    with open(os.path.join(str(tmp_path), tail), "ab") as f:
        f.write(_torn_record(hot[0]))

    if backend == "single":
        st = recover(be.engine.devices, mode="pallas", parallel=False)
        oracle = recover(be.engine.devices, mode="scalar", parallel=False)
        rsne, rsne_oracle = st.rsne, oracle.rsne
    else:
        st = recover_sharded(be.eng.devices, mode="pallas", parallel=False)
        oracle = recover_sharded(be.eng.devices, mode="scalar", parallel=False)
        rsne = [s.rsne for s in st.shards]
        rsne_oracle = [s.rsne for s in oracle.shards]
    data = st.data
    assert data == oracle.data and rsne == rsne_oracle

    # every recovered value is an executed attempt's write (the preload is
    # in no log)
    written = {}
    for t in tickets:
        if t.txn is not None:
            for k, v in t.spec.writes:
                written.setdefault(k, set()).add((v, _write_ssn(be, t, k)))
    for kb, (v, ssn) in data.items():
        k = kb.decode()
        assert v != TORN and not v.startswith(b"LOST-"), k
        assert (v, ssn) in written.get(k, ()), k
    # acked prefix: each key's newest acked write survives, or a newer one
    newest = {}
    for t in acked:
        for k, v in t.spec.writes:
            ssn = _write_ssn(be, t, k)
            if ssn > newest.get(k, (b"", -1))[1]:
                newest[k] = (v, ssn)
    for k, (v, ssn) in newest.items():
        got = data.get(k.encode())
        assert got is not None and got[1] >= ssn, k
        if got[1] == ssn:
            assert got[0] == v, k
    # an acked read-only ticket read only versions that are durable
    for t in acked:
        if not t.spec.writes:
            for k, seen in t.txn.read_set:
                assert data.get(k.encode(), (b"", 0))[1] >= seen, k
