"""YCSB-F's schema and the two readers it brought: ``loaded()`` regenerates
what ``load()`` inserted, the clients read at each call and change one
field, and ``cut_conflict_pct`` / ``stage_ro_commit_p50_ms`` read the
scheduler's stop counters and the read-only ticket rows (``None`` where
those are absent, as on a program that lacks them)."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import _paths
import _tiny
from harness.spec import Bench
from repro.db.array_table import ArrayTable
from repro.trace.span import ST_FLUSH, TICKET_DTYPE, Tracer

BENCH = Bench(_paths.ROOT)
SEED = 2**31 + 5
CFG = BENCH.config("ycsb_f_10m")


def _schema(rows=3000):
    return BENCH.schema("ycsb_f").Schema(CFG, rows=rows)


def _loaded_table(schema, seed=SEED):
    table = ArrayTable(capacity=schema.capacity)
    schema.load(table, seed)
    return table


def test_config_keeps_workload_f_shapes():
    with open(os.path.join(_paths.ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    (entry,) = [c for c in doc["configs"] if c["name"] == "ycsb_f_10m"]
    assert entry["reduced"] == [] and CFG["reduced"] == {}
    assert (CFG["rows"], CFG["fields"], CFG["field_bytes"]) == (
        10_000_000, 10, 100)
    assert (CFG["read_proportion"], CFG["rmw_proportion"]) == (0.5, 0.5)
    traffic = BENCH.traffic("ycsbf_zipf_open")
    assert traffic["txn"] == {"key_dist": "zipfian", "theta": 0.99}


@pytest.mark.parametrize("block", [512, 1 << 16])
def test_loaded_equals_what_load_inserted(block, monkeypatch):
    mod = BENCH.schema("ycsb_f")
    monkeypatch.setattr(mod, "LOAD_BLOCK", block)
    schema = mod.Schema(CFG, rows=3000)
    table = _loaded_table(schema)
    keys = [f"user{i:010d}" for i in range(schema.rows)]
    got = schema.loaded(SEED, keys)
    assert got == {k: table.get(k)[0] for k in keys}
    assert all(len(v) == 1000 for v in got.values())
    assert len(set(got.values())) == schema.rows
    # a few keys alone draw the same records; another seed draws others
    few = [keys[0], keys[1234], keys[-1]]
    assert schema.loaded(SEED, few) == {k: got[k] for k in few}
    assert schema.loaded(SEED + 1, few)[keys[0]] != got[keys[0]]


def test_clients_read_at_each_call_and_change_one_field():
    schema = _schema(rows=200)
    table = _loaded_table(schema)
    src = schema.clients(BENCH.traffic("ycsbf_zipf_open"), table, SEED)
    fns = src.take(400)
    specs = [f() for f in fns]
    reads = [s for s in specs if not s.writes]
    rmws = [s for s in specs if s.writes]
    assert 150 < len(reads) < 250 and len(reads) + len(rmws) == 400
    for s in specs:
        (k,) = s.reads
        assert s.observed == [table.get(k)[1]]
        want = src.spec(s.tag)
        assert want.values == (table.get(k)[0],)
        assert list(want.writes) == s.writes
    for s in rmws:
        ((k, new),) = s.writes
        old = table.get(k)[0]
        diff = [f for f in range(10)
                if new[100 * f:100 * f + 100] != old[100 * f:100 * f + 100]]
        assert len(diff) == 1 and len(new) == 1000
    # a retry calls the closure again and re-reads the record
    j = next(i for i, s in enumerate(specs) if s.writes)
    k = specs[j].reads[0]
    table.ssn[table.row_of(k)] = 41
    again = fns[j]()
    assert again.observed == [41] and again.tag != specs[j].tag


def test_take_is_fixed_by_the_seed():
    schema = _schema(rows=200)
    table = _loaded_table(schema)
    traffic = BENCH.traffic("ycsbf_zipf_open")

    def shapes(seed):
        src = schema.clients(traffic, table, seed)
        return [(s.reads, s.writes) for s in (f() for f in src.take(50))]

    assert shapes(SEED) == shapes(SEED)
    assert shapes(SEED) != shapes(SEED + 1)


@pytest.mark.parametrize("stats,want", [
    ({"cuts": 8, "cut_stop_conflict": 2}, 25.0),
    ({"cuts": 0, "cut_stop_conflict": 0}, None),
    ({"cuts": 8}, None),                  # a scheduler without the counter
])
def test_cut_conflict_pct_reads_the_stop_counter(stats, want):
    got = BENCH.reader("cut_conflict_pct")(SimpleNamespace(stats=stats))
    assert got == (pytest.approx(want) if want is not None else None)


def _ro_dump():
    tr = Tracer(capacity=16)
    tr.record(ST_FLUSH, device=0, txn_lo=0, txn_hi=9, t0=1.0, t1=1.5)
    # ssn, shard, device, submit, cut, precommit, commit, ack
    tr.record_many(np.array([
        (3, 0, 0, 1.000, 1.001, 1.003, 1.600, 1.700),    # a write
        (3, 0, -1, 2.000, 2.001, 2.002, 2.010, 2.012),   # read-only: 10 ms
        (4, 0, -1, 3.000, 3.001, 3.004, 3.030, 3.034),   # read-only: 30 ms
        (5, 0, -1, 4.000, 4.001, 4.010, 4.030, 4.050),   # read-only: 40 ms
        (6, -1, -1, 5.000, 5.001, 5.002, 5.900, 5.902),  # cross-shard
        (7, 0, -1, 0.0, 0.0, 6.000, 6.500, 6.500),       # before tracing
    ], TICKET_DTYPE))
    return tr.dump()


def test_stage_ro_commit_reads_read_only_rows():
    run = SimpleNamespace(spans=_ro_dump())
    got = BENCH.reader("stage_ro_commit_p50_ms")(run)
    assert got == pytest.approx(30.0)


def test_stage_ro_commit_is_none_without_read_only_rows():
    read = BENCH.reader("stage_ro_commit_p50_ms")
    assert read(SimpleNamespace(spans=None)) is None
    assert read(SimpleNamespace(spans=Tracer(capacity=4).dump())) is None
    tr = Tracer(capacity=4)
    tr.record_many(np.array([(3, 0, 0, 1.0, 1.001, 1.003, 1.6, 1.7)],
                            TICKET_DTYPE))
    assert read(SimpleNamespace(spans=tr.dump())) is None


def test_traced_run_reports_the_new_metrics_and_compiles_nothing():
    said = []
    result, _ = _tiny.runner.run(
        BENCH, "ycsbf_zipf_lat", SEED, 1.5, True, _tiny.time.perf_counter(),
        scale=_tiny.SCALE["ycsbf_zipf_lat"], device=_tiny.CPU,
        say=said.append)
    assert said[0]["compiles"]["window"] == {"traces": 0,
                                             "compiles_or_cache_loads": 0}
    assert result["correct"], result["checks"]
    m = result["metrics"]
    assert 0.0 <= m["cut_conflict_pct"]["value"] <= 100.0
    assert m["stage_ro_commit_p50_ms"]["value"] > 0.0
