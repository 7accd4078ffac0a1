"""Group-commit transparency properties for the serving tier.

The scheduler's batch cutter is strict-FIFO and conflict-free (a cut is the
longest queue prefix in which nothing touches a key written earlier in the
prefix), which makes group commit *transparent* at the log-byte level.  Two
properties pin that down:

P1 (equivalence vs direct batch): for a conflict-free arrival schedule, the
   serve path — arrivals trickling in over steps, arbitrary cut sizes and
   latency budgets — produces **byte-identical device logs** and identical
   final table state to a *single* direct ``execute_batch`` of the same
   transactions on a fresh identical stack.  Checked for the vectorized,
   pallas and scalar executors, and for the sharded engine.

P2 (cut invariance): for an *arbitrary* schedule (duplicate/hot keys — the
   cutter splits at conflicts; Zipf-skewed reads and read-modify-writes,
   where reads share cuts), any two scheduler configurations (different
   cut sizes, latency budgets, arrival timings) produce byte-identical logs,
   identical final state, and the same per-transaction SSNs and ack order.

Both run as seeded-random trials (always, tier-1) and as hypothesis
properties when hypothesis is installed.

Preconditions the trials honour (and document): one log buffer per engine
(idle-buffer heartbeats are timing-dependent bytes), and worker ids assigned
in admission order (the scheduler's round-robin matches the executor's
default striping).
"""

import random

import pytest

from repro.core import EngineConfig, PoplarEngine
from repro.db.batch import BatchOCC, ScalarBatchOCC, TxnSpec
from repro.db.ycsb import Zipfian, key_of
from repro.serve import (
    ACKED,
    GroupCommitScheduler,
    ServeConfig,
    SingleBackend,
    run_stepped_schedule,
)
from repro.shard import ShardedConfig, ShardedEngine
from repro.serve.backend import ShardedBackend

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # tier-1 containers: seeded trials below still run
    HAVE_HYPOTHESIS = False

READ_POOL = 20  # keys 0..19: preloaded; only the read/RMW schedules write them


def _mk_backend(mode, base_dir, tag, n_workers):
    d = base_dir / tag
    d.mkdir()
    cfg = EngineConfig(n_buffers=1, device_kind="null", device_dir=str(d))
    be = SingleBackend.make(mode, n_workers=n_workers, cfg=cfg)
    for i in range(READ_POOL):
        be.table.insert(key_of(i), f"seed{i}".encode())
    return be


def _state(table, keys):
    out = {}
    for k in keys:
        got = table.get(k)
        if got is None:
            continue
        out[k] = got if isinstance(got, tuple) else (got.value, got.ssn)
    return out


def _run_serve(be, specs, gaps, max_batch, budget_steps):
    """Drive the serve path over a stepped schedule; return tickets."""
    sched = GroupCommitScheduler(
        be,
        ServeConfig(
            max_batch=max_batch,
            latency_budget_steps=budget_steps,
            queue_capacity=10**6,
        ),
    )
    at, schedule = 0, []
    for spec, gap in zip(specs, gaps):
        at += gap
        schedule.append((at, spec))
    return run_stepped_schedule(sched, schedule)


def _settle_direct(be, res, max_steps=200):
    """Flush + drain a direct execute_batch result until fully committed."""
    for _ in range(max_steps):
        be.tick()
        be.drain()
        if all(t.committed for t in res.committed):
            return
    raise TimeoutError("direct batch did not settle")


def _check_equivalence(mode, base_dir, specs, gaps, max_batch, budget_steps,
                       n_workers):
    """P1: serve path vs one direct execute_batch — bytes, state, SSNs."""
    keys = sorted({k for s in specs for k in list(s.reads) + [w for w, _ in s.writes]}
                  | {key_of(i) for i in range(READ_POOL)})
    be_s = _mk_backend(mode, base_dir, "serve", n_workers)
    be_d = _mk_backend(mode, base_dir, "direct", n_workers)

    tickets = _run_serve(be_s, specs, gaps, max_batch, budget_steps)
    assert all(t.status == ACKED for t in tickets)
    # conflict-free => commit order is admission order, globally
    acks = [t.ack_seq for t in tickets]
    assert acks == sorted(acks)

    res = be_d.occ.execute_batch(specs, max_rounds=1)
    assert not res.aborted and list(res.committed_idx) == list(range(len(specs)))
    _settle_direct(be_d, res)

    # identical per-transaction SSNs (same Algorithm-1 chain)...
    assert [t.ssn for t in tickets] == [t.ssn for t in res.committed]
    # ...identical final table state...
    assert _state(be_s.table, keys) == _state(be_d.table, keys)
    # ...and byte-identical device logs
    for d in be_s.engine.devices + be_d.engine.devices:
        d.close()
    assert [d.read_all() for d in be_s.engine.devices] == [
        d.read_all() for d in be_d.engine.devices
    ]


def _conflict_free_trial(seed, base_dir, mode):
    rng = random.Random(seed)
    n = rng.randrange(1, 22)
    specs = []
    for i in range(n):
        reads = [key_of(j) for j in rng.sample(range(READ_POOL),
                                               rng.randrange(0, 3))]
        # write keys unique per txn and disjoint from the read pool
        specs.append(TxnSpec(
            reads=reads,
            writes=[(key_of(1000 + i), rng.randbytes(rng.randrange(1, 40)))],
        ))
    gaps = [rng.randrange(0, 3) for _ in range(n)]
    _check_equivalence(mode, base_dir, specs, gaps,
                       max_batch=rng.choice([1, 2, 3, 8, 64]),
                       budget_steps=rng.choice([1, 2]),
                       n_workers=rng.choice([1, 2, 3]))


MODES = ("vectorized", "scalar", "pallas")


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("mode", MODES)
def test_equivalence(mode, seed, tmp_path):
    _conflict_free_trial(seed, tmp_path, mode)


# --- P1, sharded --------------------------------------------------------------

def _mk_sharded(base_dir, tag):
    d = base_dir / tag
    d.mkdir()
    eng = ShardedEngine(ShardedConfig(
        n_shards=2, n_buffers=1, n_workers=1,
        device_kind="null", device_dir=str(d),
    ))
    for i in range(READ_POOL):
        eng.insert(key_of(i), f"seed{i}".encode())
    return eng


@pytest.mark.parametrize("seed", range(3))
def test_equivalence_sharded(seed, tmp_path):
    """Serve path over a ShardedBackend vs one direct sharded execute_batch:
    byte-identical per-shard logs (single-shard, write-only, conflict-free
    specs — cross-shard coordination bytes are covered by state-level tests
    in test_serve_scheduler.py)."""
    rng = random.Random(seed)
    n = rng.randrange(1, 20)
    specs = [
        TxnSpec(writes=[(key_of(1000 + i), rng.randbytes(rng.randrange(1, 32)))])
        for i in range(n)
    ]
    gaps = [rng.randrange(0, 3) for _ in range(n)]

    eng_s = _mk_sharded(tmp_path, "serve")
    eng_d = _mk_sharded(tmp_path, "direct")

    tickets = _run_serve(ShardedBackend(eng_s), specs, gaps,
                         max_batch=rng.choice([1, 2, 4, 16]), budget_steps=1)
    assert all(t.status == ACKED for t in tickets)

    res = eng_d.execute_batch(specs)
    assert not res.aborted and not res.cross
    for _ in range(200):
        eng_d.tick(force=True)
        eng_d.drain()
        if all(t.committed for t in res.committed):
            break
    else:
        raise TimeoutError("direct sharded batch did not settle")

    flat_s = [d for devs in eng_s.devices for d in devs]
    flat_d = [d for devs in eng_d.devices for d in devs]
    for d in flat_s + flat_d:
        d.close()
    assert [d.read_all() for d in flat_s] == [d.read_all() for d in flat_d]
    assert eng_s.to_dict() == eng_d.to_dict()


# --- P2: cut invariance on arbitrary (conflicting) schedules ------------------

def _arbitrary_specs(rng, n):
    """Hot-key schedule: writes collide freely (the cutter must split)."""
    specs = []
    for _ in range(n):
        wkeys = rng.sample(range(READ_POOL, READ_POOL + 6),
                           rng.randrange(1, 3))
        specs.append(TxnSpec(
            reads=[key_of(j) for j in rng.sample(range(READ_POOL),
                                                 rng.randrange(0, 2))],
            writes=[(key_of(k), rng.randbytes(rng.randrange(1, 24)))
                    for k in wkeys],
        ))
    return specs


def _zipf_read_rmw_specs(rng, n):
    """YCSB-F-like schedule: half read-only, half read-modify-write, keys
    Zipf-skewed over a few preloaded keys.  Reads of one key share a cut,
    an RMW joins after reads of its key, a read after a write ends it."""
    zipf = Zipfian(6, theta=0.99, seed=rng.randrange(1 << 30))
    specs = []
    for r in zipf.sample(n).tolist():
        k = key_of(r)
        if rng.random() < 0.5:
            specs.append(TxnSpec(reads=[k]))
        else:
            specs.append(TxnSpec(reads=[k], writes=[(k, rng.randbytes(
                rng.randrange(1, 24)))]))
    return specs


def _check_cut_invariance(mode, base_dir, specs, cfg_a, cfg_b, n_workers):
    keys = sorted({k for s in specs
                   for k in list(s.reads) + [w for w, _ in s.writes]})
    results = []
    for tag, (gaps, max_batch, budget) in (("a", cfg_a), ("b", cfg_b)):
        be = _mk_backend(mode, base_dir, tag, n_workers)
        tickets = _run_serve(be, specs, gaps, max_batch, budget)
        assert all(t.status == ACKED for t in tickets)
        for d in be.engine.devices:
            d.close()
        results.append((
            [d.read_all() for d in be.engine.devices],
            _state(be.table, keys),
            [t.ssn for t in tickets],
            [t.ack_seq for t in tickets],
        ))
    assert results[0] == results[1]


@pytest.mark.parametrize("seed,make_specs", [
    pytest.param(s, _arbitrary_specs, id=str(s)) for s in range(6)
] + [
    pytest.param(s, _zipf_read_rmw_specs, id=f"zipf_read_rmw-{s}")
    for s in range(6)
])
def test_cut_invariance(seed, make_specs, tmp_path):
    rng = random.Random(100 + seed)
    n = rng.randrange(2, 24)
    specs = make_specs(rng, n)
    cfg_a = ([rng.randrange(0, 3) for _ in range(n)],
             rng.choice([1, 2, 4, 64]), rng.choice([1, 2]))
    cfg_b = ([rng.randrange(0, 3) for _ in range(n)],
             rng.choice([1, 3, 8, 64]), rng.choice([1, 3]))
    mode = MODES[seed % len(MODES)]
    _check_cut_invariance(mode, tmp_path, specs, cfg_a, cfg_b,
                          n_workers=rng.choice([1, 2]))


# --- hypothesis wrappers (skipped when hypothesis is absent) ------------------

if HAVE_HYPOTHESIS:
    import tempfile
    from pathlib import Path

    schedule_st = st.lists(
        st.tuples(
            st.lists(st.integers(0, READ_POOL - 1), max_size=2, unique=True),
            st.integers(0, 2),     # arrival gap (steps)
            st.integers(1, 24),    # value length
        ),
        min_size=1, max_size=20,
    )

    @settings(max_examples=40, deadline=None)
    @given(sched=schedule_st, max_batch=st.sampled_from([1, 2, 4, 8]),
           budget=st.integers(1, 2), mode=st.sampled_from(["vectorized", "scalar"]),
           n_workers=st.integers(1, 3))
    def test_equivalence_hypothesis(sched, max_batch, budget, mode, n_workers):
        specs = [
            TxnSpec(reads=[key_of(j) for j in reads],
                    writes=[(key_of(1000 + i), bytes([i % 251] * vlen))])
            for i, (reads, _, vlen) in enumerate(sched)
        ]
        gaps = [g for _, g, _ in sched]
        with tempfile.TemporaryDirectory() as d:
            base = Path(d)
            (base / "serve").parent.mkdir(exist_ok=True)
            _check_equivalence(mode, base, specs, gaps, max_batch, budget,
                               n_workers)
else:

    @pytest.mark.skip(reason="hypothesis not installed; the seeded trials "
                             "above exercise the same properties")
    def test_equivalence_hypothesis():
        pass
