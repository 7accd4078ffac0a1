"""The readers of the per-ticket stages, the collector's share of the window
and the fused recovery's split, on a hand-built ``TraceDump`` and
``RecoveryReport``: what they read, and ``None`` where it is absent; and a
traced tiny run of a cell that lists them."""

from types import SimpleNamespace

import numpy as np
import pytest

import _paths
import _tiny
from harness.spec import Bench
from repro.core.recovery import RecoveryReport
from repro.trace.span import (
    ST_FLUSH,
    ST_GC,
    ST_VALIDATE,
    TICKET_DTYPE,
    TraceDump,
    Tracer,
)

BENCH = Bench(_paths.ROOT)
STAGES = ("queue", "exec", "flush", "commit")


def _run(spans=None, recovery=None, t0=10.0, t_end=20.0):
    return SimpleNamespace(spans=spans, recovery=recovery,
                           window=SimpleNamespace(t0=t0, t_end=t_end))


def _tickets(*rows):
    """Ticket rows from tuples in ``TICKET_DTYPE`` order."""
    return np.array(list(rows), TICKET_DTYPE)


def _dump():
    """Three tickets: a write on device 0 made durable by the flush ending
    at 1.5, one on device 1 whose flush span ends after its commit (held to
    the commit), and a read-only one."""
    tr = Tracer(capacity=16)
    tr.record(ST_FLUSH, device=0, txn_lo=0, txn_hi=4, t0=1.3, t1=1.5)
    tr.record(ST_FLUSH, device=1, txn_lo=0, txn_hi=9, t0=2.0, t1=2.9)
    # ssn, shard, device, submit, cut, precommit, commit, ack
    tr.record_many(_tickets(
        (3, 0, 0, 1.000, 1.001, 1.003, 1.600, 1.700),
        (5, 0, 1, 2.000, 2.004, 2.010, 2.800, 2.812),
        (5, 0, -1, 3.000, 3.002, 3.003, 3.050, 3.060),
    ))
    return tr.dump()


@pytest.mark.parametrize("stage,want_ms", [
    ("queue", [1.0, 4.0, 2.0]),
    ("exec", [2.0, 6.0, 1.0]),
    ("flush", [497.0, 790.0, 0.0]),
    ("commit", [200.0, 12.0, 57.0]),
])
def test_stage_reader_is_the_median_of_its_stage(stage, want_ms):
    st = _dump().ticket_stages()
    np.testing.assert_allclose(st[stage] * 1e3, want_ms, atol=1e-9)
    got = BENCH.reader(f"stage_{stage}_p50_ms")(_run(_dump()))
    assert got == pytest.approx(float(np.median(want_ms)))


@pytest.mark.parametrize("stage", STAGES)
def test_stage_reader_is_none_without_ticket_rows(stage):
    read = BENCH.reader(f"stage_{stage}_p50_ms")
    assert read(_run(None)) is None
    empty = Tracer(capacity=4).dump()
    assert read(_run(empty)) is None
    no_table = TraceDump(**{k: v for k, v in vars(empty).items()
                            if k != "tickets"})
    assert read(_run(no_table)) is None


def test_stage_reader_skips_tickets_admitted_before_tracing():
    tr = Tracer(capacity=16)
    tr.record_many(_tickets((1, 0, -1, 0.0, 0.0, 1.0, 1.1, 1.2),
                            (2, 0, -1, 1.0, 1.5, 2.0, 2.1, 2.2)))
    read = BENCH.reader("stage_queue_p50_ms")
    assert read(_run(tr.dump())) == pytest.approx(500.0)


def test_gc_pause_pct_clips_collections_to_the_window():
    tr = Tracer(capacity=16)
    tr.record(ST_VALIDATE, t0=11.0, t1=19.0)
    tr.record(ST_GC, t0=9.5, t1=10.5, aux=2)    # 0.5 s inside
    tr.record(ST_GC, t0=12.0, t1=13.0, aux=1)   # 1.0 s
    tr.record(ST_GC, t0=19.8, t1=21.0, aux=2)   # 0.2 s
    tr.record(ST_GC, t0=25.0, t1=26.0, aux=2)   # outside
    got = BENCH.reader("gc_pause_pct")(_run(tr.dump()))
    assert got == pytest.approx(100.0 * 1.7 / 10.0)


def test_gc_pause_pct_is_none_without_gc_rows():
    read = BENCH.reader("gc_pause_pct")
    assert read(_run(None)) is None
    tr = Tracer(capacity=4)
    tr.record(ST_VALIDATE, t0=11.0, t1=12.0)
    assert read(_run(tr.dump())) is None


@pytest.mark.parametrize("part", ["wait", "scan", "apply"])
def test_recover_reader_reads_the_fused_split(part):
    read = BENCH.reader(f"recover_{part}_s")
    rep = RecoveryReport(mode="pallas", fused=True, replay_s=1.0,
                         fused_wait_s=0.25, fused_scan_s=0.125,
                         fused_apply_s=0.5)
    assert read(_run(recovery=rep)) == getattr(rep, f"fused_{part}_s")
    assert read(_run(recovery=RecoveryReport(mode="pallas"))) is None
    assert read(_run(recovery=None)) is None
    older = SimpleNamespace(fused=True, replay_s=1.0)   # a report without it
    assert read(_run(recovery=older)) is None


def test_traced_lat_run_reports_stages_and_recovery_split(monkeypatch):
    import repro.core

    reports = []
    real_recover = repro.core.recover

    def recover(*a, **kw):
        state = real_recover(*a, **kw)
        reports.append(state.report)
        return state

    monkeypatch.setattr(repro.core, "recover", recover)
    result, _ = _tiny.run("tpcc_lat", trace=True)
    assert result["correct"]
    m = result["metrics"]
    for stage in STAGES:
        assert f"stage_{stage}_p50_ms" in m
    (rep,) = reports
    split = ("recover_wait_s", "recover_scan_s", "recover_apply_s")
    if rep.fused:
        assert all(name in m for name in split)
        assert sum(m[n]["value"] for n in split) <= rep.replay_s
    else:
        assert not any(name in m for name in split)
