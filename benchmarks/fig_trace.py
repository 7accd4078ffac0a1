"""Trace-driven cost model vs reality — predicted vs measured throughput.

One traced calibration run fits per-stage cost models
(`repro.trace.sim.CostModel`); the replay simulator then *predicts* txn/s
and commit latency for every other cell of a (batch size × devices ×
shards × cross-ratio) grid spanning the fig5 (batch), fig9 (devices) and
shard-scalability axes — each prediction is checked against a real
measured run of the same cell.  Also reported:

* ``fidelity`` — discrete-event replay of the calibration DAG itself vs
  its measured makespan (the simulator's floor: same config, recorded
  durations, re-derived schedule);
* ``critical_path`` — per-stage attribution of a noisy cross-shard cell's
  critical path (what the raw BENCH_shard swings never showed);
* ``overhead`` — traced vs untraced throughput, interleaved windows on
  one live engine (must stay < 3%: a few ring writes per *batch*);
* ``autotune`` — the simulator-chosen (batch, devices) vs the
  measured-best cell.

The calibration trace dump is persisted to ``BENCH_trace_dump.json`` next
to ``BENCH_trace.json``.  With ``REPRO_TRACE_GATE=1`` (CI bench smoke)
the script exits non-zero when the calibration cell's predicted-vs-
measured drift exceeds 25% — the regression gate ROADMAP item 4 asks for.
"""

import json
import os
import time
from typing import Dict, List

import numpy as np

from _util import FAST, bench_runtime_setup, emit, robust_stats, run_metadata

from repro.core.engine import EngineConfig
from repro.db import TxnSpec
from repro.db.ycsb import key_of
from repro.shard import ShardedConfig, ShardedEngine
from repro.trace import (
    ST_DRIVER,
    ST_XPREPARE,
    TRACER,
    CostModel,
    SimConfig,
    WorkloadProfile,
    autotune,
    build_dag,
    critical_path,
    disable,
    enable,
    simulate,
    simulate_dag,
)

N_TXN = 8192 if FAST else 24576
N_RECORDS = 8192 if FAST else 40_000
VALUE_BYTES = 600
MAX_DRIFT = 0.25
IO_UNIT = EngineConfig().io_unit

# the grid: batch axis (fig5-style), device axis (fig9-style), shard axis
CAL = (512, 2)                                   # calibration cell
SINGLE = [(b, d) for b in (512, 2048) for d in (1, 2, 4)]
SHARD_CELLS = [(2, 0.0), (2, 0.5)] if FAST else [(2, 0.0), (2, 0.5), (4, 0.5)]
NOISY_CELL = (2, 0.5)                            # traced for the breakdown
OVERHEAD_REPS = 8 if FAST else 10  # max-of-windows only needs one clean
#                                    window per side; 5 was too few to dodge
#                                    a burst of host steal-time
CELL_REPS = 3        # measured cells keep the best of 3 (steal-time noise
#                      on this container only ever deflates a window)


class _Workload:
    """Write-only workload with a controlled cross-shard ratio (the
    fig_shard construction: one full write, or two half writes on two
    distinct shards — same payload either way)."""

    def __init__(self, buckets: List[List[str]], ratio: float, seed: int = 7):
        self.buckets = buckets
        self.ratio = ratio if len(buckets) > 1 else 0.0
        self.rng = np.random.default_rng(seed)

    def next_batch(self, n: int) -> List[TxnSpec]:
        rng = self.rng
        nb = len(self.buckets)
        blob = rng.bytes(n * VALUE_BYTES)
        half = VALUE_BYTES // 2
        cross = rng.random(n) < self.ratio
        s1 = rng.integers(0, nb, n)
        s2 = (s1 + rng.integers(1, max(nb, 2), n)) % nb
        sizes = np.asarray([len(b) for b in self.buckets])
        k1 = rng.integers(0, sizes[s1])
        k2 = rng.integers(0, sizes[s2])
        specs: List[TxnSpec] = []
        for i in range(n):
            off = i * VALUE_BYTES
            a = self.buckets[s1[i]][k1[i]]
            if cross[i]:
                b = self.buckets[s2[i]][k2[i]]
                specs.append(TxnSpec(writes=[
                    (a, blob[off:off + half]),
                    (b, blob[off + half:off + VALUE_BYTES]),
                ]))
            else:
                specs.append(
                    TxnSpec(writes=[(a, blob[off:off + VALUE_BYTES])])
                )
        return specs


def _run_cell(shards: int, devices: int, batch: int,
              ratio: float = 0.0) -> Dict:
    """Measure one cell: fixed N_TXN work through the threaded sharded
    engine (logger threads flush concurrently — the regime the simulator's
    cpu/device resource split models).  When the tracer is armed, the
    driver halves of the loop (workload gen; drain + ack sweep) are traced
    too, so the calibration trace covers the whole wall window."""
    eng = ShardedEngine(ShardedConfig(
        n_shards=shards, n_buffers=devices, n_workers=devices,
        device_kind="ssd", device_clock="real",
        table_capacity=N_RECORDS // shards + 1,
        engine=EngineConfig(n_buffers=devices, device_kind="ssd",
                            logger_poll=1e-3),
    ))
    buckets: List[List[str]] = [[] for _ in range(shards)]
    for i in range(N_RECORDS):
        k = key_of(i)
        buckets[eng.shard_of(k)].append(k)
        eng.insert(k, b"\x00")
    wl = _Workload(buckets, ratio)
    eng.start()

    n_committed = 0
    lat: List[float] = []
    pending: List = []

    def sweep() -> None:
        nonlocal n_committed
        keep = []
        for t in pending:
            if t.committed:
                n_committed += 1
                tc = getattr(t, "t_commit", 0.0)
                tp = getattr(t, "t_precommit", 0.0)
                if tc and tp:
                    lat.append(tc - tp)
            else:
                keep.append(t)
        pending[:] = keep

    eng.execute_batch(wl.next_batch(min(batch, 256)))  # warm-up
    eng.drain()
    _trace = TRACER.enabled
    t0 = time.perf_counter()
    done = 0
    while done < N_TXN:
        if _trace:
            _td0 = TRACER.begin(ST_DRIVER)
        specs = wl.next_batch(batch)
        if _trace:
            TRACER.record(ST_DRIVER, t0=_td0, t1=time.perf_counter(),
                          n_txn=batch)
        res = eng.execute_batch(specs, max_rounds=2)
        done += batch
        if _trace:
            _td0 = TRACER.begin(ST_DRIVER)
        pending.extend(res.committed)
        pending.extend(res.cross)
        eng.drain()
        sweep()
        if _trace:
            TRACER.record(ST_DRIVER, t0=_td0, t1=time.perf_counter())
    try:
        eng.quiesce(timeout=30)
    except TimeoutError:
        pass
    elapsed = time.perf_counter() - t0
    eng.stop()
    sweep()
    out = {
        "txn_s": n_committed / elapsed,
        "elapsed_s": elapsed,
        "committed": n_committed,
    }
    if lat:
        out["p50_ms"] = float(np.percentile(lat, 50)) * 1e3
        out["p99_ms"] = float(np.percentile(lat, 99)) * 1e3
    return out


def _overhead_windows(reps: int):
    """Traced vs untraced throughput on ONE live engine: alternate
    measurement windows of fixed work with the tracer off/on, engine and
    page cache shared, so the comparison isn't swamped by per-run setup
    variance (table build, thread starts) the way separate runs are."""
    eng = ShardedEngine(ShardedConfig(
        n_shards=1, n_buffers=CAL[1], n_workers=CAL[1],
        device_kind="ssd", device_clock="real",
        table_capacity=N_RECORDS + 1,
        engine=EngineConfig(n_buffers=CAL[1], device_kind="ssd",
                            logger_poll=1e-3),
    ))
    keys = []
    for i in range(N_RECORDS):
        k = key_of(i)
        keys.append(k)
        eng.insert(k, b"\x00")
    wl = _Workload([keys], 0.0)
    eng.start()
    pending: List = []

    def window() -> float:
        done = 0
        n_committed = 0
        _trace = TRACER.enabled
        t0 = time.perf_counter()
        while done < N_TXN:
            if _trace:
                _td0 = TRACER.begin(ST_DRIVER)
            specs = wl.next_batch(CAL[0])
            if _trace:
                TRACER.record(ST_DRIVER, t0=_td0, t1=time.perf_counter(),
                              n_txn=CAL[0])
            res = eng.execute_batch(specs, max_rounds=2)
            done += CAL[0]
            if _trace:
                _td0 = TRACER.begin(ST_DRIVER)
            pending.extend(res.committed)
            eng.drain()
            keep = []
            for t in pending:
                if t.committed:
                    n_committed += 1
                else:
                    keep.append(t)
            pending[:] = keep
            if _trace:
                TRACER.record(ST_DRIVER, t0=_td0, t1=time.perf_counter())
        return done / (time.perf_counter() - t0)

    window()                                   # warm-up, discarded
    off_runs, on_runs = [], []
    for _ in range(reps):
        off_runs.append(window())
        enable()
        on_runs.append(window())
        disable()
    eng.stop()
    # this container's steal-time spikes inflate single windows by up to
    # 2x; the MIN over alternating windows is the classic robust estimator
    # for added-cost noise (a spike only ever slows a window down), so the
    # overhead ratio compares the cleanest traced vs untraced windows
    return off_runs, on_runs, 1.0 - max(on_runs) / max(off_runs)


def _measure_cell(shards: int, devices: int, batch: int,
                  ratio: float = 0.0) -> Dict:
    """Best of CELL_REPS runs — host noise only deflates a window."""
    runs = [_run_cell(shards, devices, batch, ratio)
            for _ in range(CELL_REPS)]
    return max(runs, key=lambda r: r["txn_s"])


def _predict(model: CostModel, profile: WorkloadProfile, shards: int,
             devices: int, batch: int, ratio: float = 0.0):
    return simulate(model, SimConfig(
        shards=shards, devices=devices, batch_size=batch, n_txn=N_TXN,
        cross_ratio=ratio, io_unit=IO_UNIT,
    ), profile)


def _drift(pred: float, meas: float) -> float:
    return abs(pred - meas) / meas if meas else float("inf")


def run():
    repo_root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    rows: List[Dict] = []

    # --- calibration: one traced run fits the cost model -----------------
    # (best of CELL_REPS: a steal-time spike inside the calibration run
    # would bias every coefficient, not just one cell)
    cal = dump = None
    for _ in range(CELL_REPS):
        enable()
        c = _run_cell(1, CAL[1], CAL[0])
        d = disable()
        if cal is None or c["txn_s"] > cal["txn_s"]:
            cal, dump = c, d
    if dump.dropped:
        raise SystemExit(
            f"fig_trace: calibration trace dropped {dump.dropped} spans — "
            f"a wrapped ring under-samples early stages and would skew "
            f"every CostModel coefficient; raise enable(capacity=...)"
        )
    dump.save(
        os.path.join(repo_root, "BENCH_trace_dump.json"),
        extra={"bench": "trace_dump", "fast": FAST, "meta": run_metadata()},
    )
    model = CostModel.fit(dump)
    profile = WorkloadProfile.from_dump(dump)
    dag = build_dag(dump)
    _, cal_attr = critical_path(dag)

    # --- second traced run: the noisy cross-shard cell -------------------
    # serves double duty: (a) only a sharded trace observes the per-txn
    # coordinator prepare cost, grafted onto the calibration fit; (b) its
    # critical path is the breakdown BENCH_shard's raw swings never showed
    enable()
    _run_cell(NOISY_CELL[0], 1, CAL[0], NOISY_CELL[1])
    xdump = disable()
    if xdump.dropped:
        raise SystemExit(
            f"fig_trace: cross-shard trace dropped {xdump.dropped} spans; "
            f"refusing to graft a biased ST_XPREPARE fit"
        )
    model.merge_stage(CostModel.fit(xdump), ST_XPREPARE)
    # fold the untraced per-txn residual (routing, GIL churn) into the
    # driver lane so predictions extrapolate from an unbiased baseline
    model.calibrate_pad(cal["txn_s"], SimConfig(
        shards=1, devices=CAL[1], batch_size=CAL[0], n_txn=N_TXN,
        io_unit=IO_UNIT,
    ), profile)

    # simulator floor: replay the recorded DAG vs its measured makespan
    replay = simulate_dag(dag)
    rows.append({
        "bench": "trace", "kind": "fidelity",
        "batch": CAL[0], "devices": CAL[1], "shards": 1, "cross_ratio": 0.0,
        "measured_txn_s": round(cal["txn_s"], 1),
        "predicted_txn_s": round(replay.txn_s, 1),
        "drift_pct": round(100 * _drift(replay.makespan, dump.makespan()), 1),
        "detail": json.dumps({
            "replay_makespan_s": round(replay.makespan, 4),
            "measured_makespan_s": round(dump.makespan(), 4),
        }),
    })

    # --- predicted vs measured over the grid -----------------------------
    measured_single: Dict = {}
    cal_drift = None
    for batch, devices in SINGLE:
        meas = _measure_cell(1, devices, batch)
        measured_single[(batch, devices)] = meas
        pred = _predict(model, profile, 1, devices, batch)
        drift = _drift(pred.txn_s, meas["txn_s"])
        if (batch, devices) == CAL:
            cal_drift = drift
        rows.append({
            "bench": "trace", "kind": "config",
            "batch": batch, "devices": devices, "shards": 1,
            "cross_ratio": 0.0,
            "measured_txn_s": round(meas["txn_s"], 1),
            "predicted_txn_s": round(pred.txn_s, 1),
            "drift_pct": round(100 * drift, 1),
            "measured_p50_ms": round(meas.get("p50_ms", float("nan")), 2),
            "predicted_p50_ms": round(pred.p50_commit * 1e3, 2),
            "predicted_p99_ms": round(pred.p99_commit * 1e3, 2),
        })
    for shards, ratio in SHARD_CELLS:
        meas = _measure_cell(shards, 1, CAL[0], ratio)
        pred = _predict(model, profile, shards, 1, CAL[0], ratio)
        rows.append({
            "bench": "trace", "kind": "config",
            "batch": CAL[0], "devices": 1, "shards": shards,
            "cross_ratio": ratio,
            "measured_txn_s": round(meas["txn_s"], 1),
            "predicted_txn_s": round(pred.txn_s, 1),
            "drift_pct": round(100 * _drift(pred.txn_s, meas["txn_s"]), 1),
            "predicted_p50_ms": round(pred.p50_commit * 1e3, 2),
            "predicted_p99_ms": round(pred.p99_commit * 1e3, 2),
        })

    # --- critical path of the noisy cross-shard cell ---------------------
    xdag = build_dag(xdump)
    _, xattr = critical_path(xdag)
    total = sum(xattr.values()) or 1.0
    rows.append({
        "bench": "trace", "kind": "critical_path",
        "batch": CAL[0], "devices": 1, "shards": NOISY_CELL[0],
        "cross_ratio": NOISY_CELL[1],
        "detail": json.dumps({
            k: round(v / total, 3)
            for k, v in sorted(xattr.items(), key=lambda kv: -kv[1])
        }),
    })
    rows.append({
        "bench": "trace", "kind": "critical_path",
        "batch": CAL[0], "devices": CAL[1], "shards": 1, "cross_ratio": 0.0,
        "detail": json.dumps({
            k: round(v / (sum(cal_attr.values()) or 1.0), 3)
            for k, v in sorted(cal_attr.items(), key=lambda kv: -kv[1])
        }),
    })

    # --- tracer overhead: interleaved traced/untraced windows ------------
    off_runs, on_runs, overhead = _overhead_windows(OVERHEAD_REPS)
    rows.append({
        "bench": "trace", "kind": "overhead",
        "batch": CAL[0], "devices": CAL[1], "shards": 1, "cross_ratio": 0.0,
        "measured_txn_s": round(max(off_runs), 1),
        "predicted_txn_s": round(max(on_runs), 1),  # traced throughput
        "drift_pct": round(100 * overhead, 2),
        "detail": json.dumps({
            "untraced": robust_stats(off_runs),
            "traced": robust_stats(on_runs),
            "untraced_runs": [round(x, 1) for x in off_runs],
            "traced_runs": [round(x, 1) for x in on_runs],
        }),
    })

    # --- autotune vs the measured-best single-shard cell -----------------
    tn = autotune(model, profile, n_txn=N_TXN, batch_grid=(512, 2048),
                  device_grid=(1, 2, 4), io_unit=IO_UNIT)
    best_cell = max(measured_single, key=lambda c: measured_single[c]["txn_s"])
    best_meas = measured_single[best_cell]["txn_s"]
    chosen = measured_single.get((tn.batch_size, tn.devices))
    chosen_meas = chosen["txn_s"] if chosen else float("nan")
    rows.append({
        "bench": "trace", "kind": "autotune",
        "batch": tn.batch_size, "devices": tn.devices, "shards": 1,
        "cross_ratio": 0.0,
        "measured_txn_s": round(chosen_meas, 1),
        "predicted_txn_s": round(tn.predicted.txn_s, 1),
        "drift_pct": round(
            100 * _drift(chosen_meas, best_meas), 1
        ),  # vs measured-best
        "detail": json.dumps({
            "measured_best_cell": list(best_cell),
            "measured_best_txn_s": round(best_meas, 1),
        }),
    })

    emit(rows, ["bench", "kind", "batch", "devices", "shards", "cross_ratio",
                "measured_txn_s", "predicted_txn_s", "drift_pct"],
         name="trace")

    assert cal_drift is not None
    print(f"# calibration drift: {100 * cal_drift:.1f}% "
          f"(gate {100 * MAX_DRIFT:.0f}%), tracer overhead: "
          f"{100 * overhead:.2f}%")
    if os.environ.get("REPRO_TRACE_GATE") == "1" and cal_drift > MAX_DRIFT:
        raise SystemExit(
            f"trace drift gate: |predicted-measured| = {100 * cal_drift:.1f}%"
            f" > {100 * MAX_DRIFT:.0f}% on the calibration config"
        )
    return rows


if __name__ == "__main__":
    bench_runtime_setup()
    run()
