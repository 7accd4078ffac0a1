"""Tracer semantics: ring buffer behavior, the disabled-tracer zero-cost
contract, and structural determinism of DAG dumps.

The two load-bearing guarantees pinned here:

* disabled tracing allocates nothing and touches nothing beyond one bool
  load per hook (tracemalloc filtered to ``trace/span.py`` over a tight
  ``execute_batch`` loop);
* the *structural* trace of a deterministic stepped serve run is
  byte-identical across two fresh runs — timestamps differ, the DAG does
  not — which is what makes ``BENCH_trace_dump.json`` diffable and the
  critical-path attribution reproducible.
"""

import gc
import glob
import json
import os
import tracemalloc

import numpy as np
import pytest

from repro.core import EngineConfig, recover
from repro.db.batch import TxnSpec
from repro.db.ycsb import key_of
from repro.serve import (
    GroupCommitScheduler,
    ServeConfig,
    ShardedBackend,
    SingleBackend,
)
from repro.trace import (
    ST_ACK,
    ST_CUT,
    ST_DRIVER,
    ST_ENCODE,
    ST_FLUSH,
    ST_GC,
    ST_PUBLISH,
    ST_SEQUENCE,
    ST_VALIDATE,
    EVENT_NAMES,
    STAGE_NAMES,
    TICKET_COLUMNS,
    TICKET_DTYPE,
    TRACER,
    TraceDump,
    Tracer,
    build_dag,
    critical_path,
    disable,
    enable,
)


@pytest.fixture(autouse=True)
def _disarm():
    """Every test leaves the process tracer disarmed and empty."""
    yield
    disable()
    TRACER.reset()


# --- ring buffer unit tests ---------------------------------------------------

def test_record_and_dump_roundtrip():
    tr = Tracer(capacity=8)
    tr.record(ST_VALIDATE, shard=1, device=2, batch=3, txn_lo=10, txn_hi=20,
              t0=1.0, t1=2.5, nbytes=100, n_txn=7, aux=9)
    d = tr.dump()
    assert d.n == 1 and d.dropped == 0
    assert d.stage[0] == ST_VALIDATE and d.shard[0] == 1
    assert d.device[0] == 2 and d.batch[0] == 3
    assert (d.txn_lo[0], d.txn_hi[0]) == (10, 20)
    assert d.nbytes[0] == 100 and d.n_txn[0] == 7 and d.aux[0] == 9
    assert d.duration()[0] == pytest.approx(1.5)
    assert d.makespan() == pytest.approx(1.5)


def test_ring_wraparound_keeps_newest_and_counts_dropped():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.record(ST_DRIVER, batch=i)
    d = tr.dump()
    assert d.n == 4
    assert d.dropped == 6
    assert d.batch.tolist() == [6, 7, 8, 9]  # oldest-first, newest kept


def test_reset_clears_rows_and_batch_sequence():
    tr = Tracer(capacity=4)
    tr.record(ST_DRIVER)
    assert tr.next_batch_id() == 1
    tr.reset()
    assert tr.dump().n == 0
    assert tr.next_batch_id() == 1  # sequences restart: reruns align
    tr.reset(capacity=16)
    assert tr.capacity == 16


def test_dump_save_load_roundtrip(tmp_path):
    tr = Tracer(capacity=8)
    tr.record(ST_PUBLISH, shard=0, device=1, batch=2, txn_lo=5, txn_hi=9,
              t0=0.5, t1=0.7, nbytes=64, n_txn=5)
    p = str(tmp_path / "dump.json")
    d = tr.dump()
    d.save(p)
    d2 = TraceDump.load(p)
    assert d2.structural_dict() == d.structural_dict()
    assert np.allclose(d2.t0, d.t0) and np.allclose(d2.t1, d.t1)


def test_enable_disable_round():
    enable(capacity=32)
    assert TRACER.enabled and TRACER.capacity == 32
    TRACER.record(ST_DRIVER)
    d = disable()
    assert not TRACER.enabled and d.n == 1


def test_stage_names_cover_taxonomy():
    assert len(STAGE_NAMES) == 15
    assert STAGE_NAMES[ST_VALIDATE] == "validate"
    assert STAGE_NAMES[ST_FLUSH] == "flush"
    assert STAGE_NAMES[ST_DRIVER] == "driver"
    assert STAGE_NAMES[ST_GC] == "gc"


# --- disabled-tracer cost contract -------------------------------------------

def _stepped_sched(tmp_path, sub="a"):
    cfg = EngineConfig(n_buffers=2, device_kind="null",
                       device_dir=str(tmp_path / sub))
    backend = SingleBackend.make("vectorized", n_workers=2, cfg=cfg)
    return GroupCommitScheduler(
        backend, ServeConfig(max_batch=16, latency_budget_steps=1)
    )


def test_disabled_tracer_allocates_nothing(tmp_path):
    """tracemalloc filtered to span.py: a tight execute_batch loop with the
    tracer disabled must not allocate a single block in the tracer module
    (the hooks reduce to one attribute load + a false branch) — nor do the
    scheduler's ticket-table hooks, a full collection (no ``gc`` hook is
    installed) or the recovery hooks of every mode."""
    sched = _stepped_sched(tmp_path)
    for i in range(32):
        sched.submit(TxnSpec(writes=[(key_of(i), b"w")]))
    sched.step()  # warm up every code path before measuring
    devices = sched.backend.engine.devices
    for mode in ("scalar", "vectorized", "pallas"):
        recover(devices, mode=mode)

    assert not TRACER.enabled
    assert TRACER._on_gc not in gc.callbacks
    flt = tracemalloc.Filter(True, "*trace/span.py")
    tracemalloc.start()
    try:
        for i in range(32, 160):
            sched.submit(TxnSpec(reads=[key_of(i - 1)],
                                 writes=[(key_of(i), b"w")]))
            sched.step()
        sched.run_until_drained()
        gc.collect()
        for mode in ("scalar", "vectorized", "pallas"):
            recover(devices, mode=mode)
        snap = tracemalloc.take_snapshot().filter_traces([flt])
    finally:
        tracemalloc.stop()
    assert sum(s.size for s in snap.statistics("filename")) == 0
    assert TRACER.dump().n == 0 and TRACER.n_tickets == 0


def test_disabled_tracer_records_nothing(tmp_path):
    sched = _stepped_sched(tmp_path)
    for i in range(8):
        sched.submit(TxnSpec(writes=[(key_of(i), b"w")]))
    sched.run_until_drained()
    assert TRACER.dump().n == 0


# --- structural determinism ---------------------------------------------------

def _traced_serve_run(tmp_path, sub):
    """One deterministic stepped serve run, traced end to end."""
    enable()
    try:
        sched = _stepped_sched(tmp_path, sub)
        for i in range(64):
            sched.submit(TxnSpec(writes=[(key_of(i % 40), bytes([i % 251]))]))
            if i % 4 == 3:
                sched.step()
        sched.run_until_drained()
    finally:
        dump = disable()
    return dump


def test_two_identical_runs_dump_identical_dags(tmp_path):
    d1 = _traced_serve_run(tmp_path, "r1")
    d2 = _traced_serve_run(tmp_path, "r2")
    assert d1.n > 0
    # raw wall-clock columns differ between runs ...
    # ... but the structural dump (and hence the DAG) is byte-identical
    s1 = json.dumps(d1.structural_dict(), sort_keys=True).encode()
    s2 = json.dumps(d2.structural_dict(), sort_keys=True).encode()
    assert s1 == s2
    g1, g2 = build_dag(d1), build_dag(d2)
    assert g1.canonical_bytes() == g2.canonical_bytes()
    assert g1.fingerprint() == g2.fingerprint()


def test_serve_trace_covers_expected_stages(tmp_path):
    d = _traced_serve_run(tmp_path, "r3")
    stages = set(d.stage.tolist())
    for st in (ST_VALIDATE, ST_SEQUENCE, ST_ENCODE, ST_PUBLISH, ST_FLUSH,
               ST_CUT, ST_ACK):
        assert st in stages, f"missing stage {STAGE_NAMES[st]}"


def test_critical_path_attribution_partitions_makespan(tmp_path):
    d = _traced_serve_run(tmp_path, "r4")
    dag = build_dag(d)
    _, attr = critical_path(dag)
    total = sum(attr.values())
    # the walk partitions [start of earliest span, end of last] exactly:
    # stage segments + explicit wait, nothing double counted
    assert total == pytest.approx(d.makespan(), rel=1e-9)
    assert all(v >= 0 for v in attr.values())


# --- ticket table -------------------------------------------------------------

def _ticket_rows(ssns, shard=0, device=1, stamps=(1.0, 2.0, 3.0, 4.0, 5.0)):
    rows = np.zeros(len(ssns), TICKET_DTYPE)
    rows["ssn"], rows["shard"], rows["device"] = ssns, shard, device
    for c, v in zip(TICKET_COLUMNS[3:], stamps):
        rows[c] = v
    return rows


def test_ticket_table_wraparound_keeps_newest_and_counts_dropped():
    tr = Tracer(capacity=4)
    tr.record_many(_ticket_rows(range(3)))
    tr.record_many(_ticket_rows(range(3, 10)))
    tk = tr.dump().tickets
    assert tk.n == 4 and tk.dropped == 6
    assert tk.ssn.tolist() == [6, 7, 8, 9]
    assert tk.t_ack.tolist() == [5.0] * 4


def test_dump_save_load_keeps_ticket_table(tmp_path):
    tr = Tracer(capacity=8)
    tr.record_many(_ticket_rows([7], shard=2))
    p = str(tmp_path / "dump.json")
    tr.dump().save(p)
    tk = TraceDump.load(p).tickets
    assert {c: getattr(tk, c).tolist() for c in TICKET_COLUMNS} == {
        "ssn": [7], "shard": [2], "device": [1], "t_submit": [1.0],
        "t_cut": [2.0], "t_precommit": [3.0], "t_commit": [4.0],
        "t_ack": [5.0]}


def test_durable_at_matches_hand_built_flush_rows():
    tr = Tracer(capacity=16)
    # device 0 flushes DSN 0→5 ending at 1.0, then →9 at 2.0; device 1 →4;
    # shard 1's device 0 (an equal buffer id) reaches 20 first, at 0.5
    tr.record(ST_FLUSH, shard=1, device=0, txn_lo=0, txn_hi=20,
              t0=0.4, t1=0.5)
    tr.record(ST_FLUSH, device=0, txn_lo=0, txn_hi=5, t0=0.9, t1=1.0)
    tr.record(ST_PUBLISH, device=0, txn_lo=6, txn_hi=9, t0=1.1, t1=1.2)
    tr.record(ST_FLUSH, device=1, txn_lo=0, txn_hi=4, t0=1.4, t1=1.5)
    tr.record(ST_FLUSH, device=0, txn_lo=5, txn_hi=9, t0=1.9, t1=2.0)
    d = tr.dump()
    got = d.durable_at([0, 0, 0, 0, 1, 1, -1], [1, 5, 6, 9, 4, 5, 1])
    np.testing.assert_array_equal(
        got, [1.0, 1.0, 2.0, 2.0, 1.5, np.nan, np.nan])
    assert d.durable_at(0, 3) == 1.0
    np.testing.assert_array_equal(
        d.durable_at([0, 0, 1, 0], [9, 21, 1, 1], shard=[1, 1, 1, -1]),
        [0.5, np.nan, np.nan, np.nan])


def _threaded_run(tmp_path, n=120):
    """A threaded scheduler serving ``n`` tickets (a third with a read, a
    tenth read-only), traced; returns the dump and the number of acks."""
    cfg = EngineConfig(n_buffers=2, device_kind="null",
                       device_dir=str(tmp_path / "thr"), flush_interval=2e-3)
    be = SingleBackend.make("vectorized", n_workers=2, cfg=cfg)
    sched = GroupCommitScheduler(be, ServeConfig(max_batch=16,
                                                 latency_budget_s=5e-4))
    enable()
    sched.start()
    try:
        tickets = []
        for i in range(n):
            reads = [key_of(i - 1)] if i % 3 == 0 and i else []
            writes = [] if i % 10 == 5 else [(key_of(i), bytes([i % 251]))]
            if not writes:
                reads = [key_of(i - 2)]
            tickets.append(sched.submit(TxnSpec(reads=reads, writes=writes)))
        for t in tickets:
            assert t.wait(timeout=30) == "acked"
    finally:
        sched.stop(quiesce=True)
        dump = disable()
    return dump, sched.stats()["acked"]


def test_threaded_scheduler_writes_one_ticket_row_per_ack(tmp_path):
    dump, acked = _threaded_run(tmp_path)
    tk = dump.tickets
    assert tk.n == acked == 120 and tk.dropped == 0
    assert (tk.ssn > 0).all()
    read_only = tk.device == -1
    assert read_only.sum() == 12 and (tk.shard == 0).all()
    assert set(tk.device[~read_only].tolist()) == {0, 1}


def test_ticket_stamps_are_ordered_and_stages_partition_latency(tmp_path):
    dump, _ = _threaded_run(tmp_path)
    tk = dump.tickets
    durable = np.where(tk.device < 0, tk.t_precommit,
                       np.minimum(dump.durable_at(tk.device, tk.ssn),
                                  tk.t_commit))
    assert np.isfinite(durable).all()
    assert (tk.t_submit > 0).all()
    assert (tk.t_submit <= tk.t_cut).all()
    assert (tk.t_cut <= tk.t_precommit).all()
    assert (tk.t_precommit <= durable).all()
    assert (durable <= tk.t_commit).all() and (tk.t_commit <= tk.t_ack).all()
    st = dump.ticket_stages()
    total = st["queue"] + st["exec"] + st["flush"] + st["commit"]
    np.testing.assert_allclose(total, tk.t_ack - tk.t_submit, rtol=0,
                               atol=1e-12)
    for v in st.values():
        assert (v >= 0).all()


def test_sharded_tickets_match_flush_rows_of_their_own_shard(tmp_path):
    """Two shards with one buffer each (equal buffer ids): a ticket's
    record is durable at its own shard's flush, and a cross-shard ticket's
    flush and commit stages are NaN, not a guess."""
    be = ShardedBackend.make(n_shards=2, n_buffers=1, n_workers=2,
                             device_kind="null", device_dir=str(tmp_path))
    sched = GroupCommitScheduler(be, ServeConfig(max_batch=16,
                                                 latency_budget_steps=1))
    keys = [key_of(i) for i in range(80)]
    on = [[k for k in keys if be.eng.shard_of(k) == p] for p in (0, 1)]
    enable()
    # shard 0's DSN runs far ahead before shard 1 logs its first record
    for k in on[0][:12]:
        sched.submit(TxnSpec(writes=[(k, b"a")]))
    sched.run_until_drained()
    for k in on[1][:3]:
        sched.submit(TxnSpec(writes=[(k, b"b")]))
    sched.submit(TxnSpec(writes=[(on[0][20], b"x"), (on[1][20], b"y")]))
    sched.run_until_drained()
    dump = disable()
    tk = dump.tickets
    assert tk.n == 16
    assert tk.shard.tolist().count(0) == 12
    assert tk.shard.tolist().count(1) == 3 and (tk.device == 0).sum() == 15
    st = dump.ticket_stages()
    single = tk.shard >= 0
    for name, v in st.items():
        assert np.isfinite(v[single]).all() and (v[single] >= 0).all()
        if name in ("flush", "commit"):
            assert np.isnan(v[~single]).all()
        else:
            assert (v[~single] >= 0).all()
    one = tk.shard == 1
    own = dump.durable_at(tk.device[one], tk.ssn[one], 1)
    other = dump.durable_at(tk.device[one], tk.ssn[one], 0)
    assert (own >= tk.t_precommit[one]).all()
    assert (other < tk.t_precommit[one]).all()


# --- collector pauses -----------------------------------------------------------

def test_gc_collect_gives_one_gc_row_while_enabled():
    enable()
    assert TRACER._on_gc in gc.callbacks
    gc.collect()
    d = disable()
    full = (d.stage == ST_GC) & (d.aux == 2)
    assert full.sum() == 1
    i = int(np.flatnonzero(full)[0])
    assert d.t1[i] >= d.t0[i] > 0 and d.n_txn[i] >= 0
    assert TRACER._on_gc not in gc.callbacks
    gc.collect()
    assert (TRACER.dump().stage == ST_GC).sum() == (d.stage == ST_GC).sum()


def test_gc_rows_stay_out_of_the_structural_dump():
    tr = Tracer(capacity=8)
    tr.record(ST_DRIVER, t0=1.0, t1=2.0)
    tr.record(ST_GC, t0=5.0, t1=9.0, aux=2)
    d = tr.dump()
    assert d.n == 2 and d.structural_dict()["n"] == 1
    assert d.makespan() == pytest.approx(1.0)


# --- spans on the profiler's clock ----------------------------------------------

def test_every_span_has_its_profiler_event_on_one_clock(tmp_path):
    """A CPU JAX profile around a traced stepped run: every ring row has a
    ``repro.<stage>`` host event, and each event starts a constant offset
    from its row's ``t0`` (the profile counts from its own start)."""
    from jax import profiler

    out = str(tmp_path / "prof")
    opts = profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    profiler.start_trace(out, profiler_options=opts)
    try:
        enable()
        sched = _stepped_sched(tmp_path, "prof_run")
        for i in range(48):
            sched.submit(TxnSpec(writes=[(key_of(i % 40), b"v")]))
            if i % 4 == 3:
                sched.step()
        sched.run_until_drained()
        gc.collect()
        d = disable()
    finally:
        profiler.stop_trace()
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    starts = {}
    for plane in profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    starts.setdefault(ev.name, []).append(ev.start_ns)
    assert d.n > 0 and (d.stage == ST_GC).any()
    by_stage = {}
    for st in np.unique(d.stage).tolist():
        t0 = np.sort(d.t0[d.stage == st])
        ev = np.sort(np.asarray(starts.get(EVENT_NAMES[st], []), np.float64))
        assert len(ev) == len(t0), STAGE_NAMES[st]
        by_stage[STAGE_NAMES[st]] = ev - 1e9 * t0
    offsets = np.concatenate(list(by_stage.values()))
    mid = np.median(offsets)
    assert offsets.max() - offsets.min() <= 50e3, {
        k: ((v - mid) / 1e3).round(1).tolist() for k, v in by_stage.items()}
