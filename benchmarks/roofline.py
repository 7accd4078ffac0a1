"""OLTP log-pipeline roofline (BENCH_roofline_oltp.json).

Each stage of the logging/recovery pipeline is a byte stream (log bytes
in, table state out), so its roof is the machine's *measured* stream-copy
memory bandwidth (probed at startup — the shared container's attainable
rate, not a spec sheet), with the emulated SSD read bandwidth
(``REPRO_SSD_BW`` x device parallelism) shown alongside as the IO roof the
paper's recovery model divides by.  Per (stage, mode) row: achieved
bytes/s over the stage's wall time vs those roofs, for

* ``replay`` — end-to-end ``recover()`` on segmented devices;
* ``replica_apply`` — ship + continuous apply into a live ``ArrayTable``;
* ``batch_occ`` — the batched forward path (validate→sequence→encode→
  publish) on null devices, bytes = log bytes produced;

in all three equivalence modes (scalar oracle / vectorized numpy /
compiled ``pallas``).  The fraction column is achieved/mem-roof: how much
of the machine's copy bandwidth the mode sustains.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

OLTP_MODES = ("scalar", "vectorized", "pallas")


def _mem_bw_probe(nbytes: int = 32 << 20, reps: int = 5) -> float:
    """Measured stream-copy bandwidth (read + write streams counted), the
    attainable roof for the byte-stream OLTP stages on this machine."""
    src = np.ones(nbytes, np.uint8)
    dst = np.empty_like(src)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        np.copyto(dst, src)
        best = min(best, time.perf_counter() - t0)
    return 2 * nbytes / best


def _oltp_row(section, mode, nbytes, wall_s, mem_bw, ssd_bw, extra=None):
    r = {
        "bench": "roofline_oltp", "section": section, "mode": mode,
        "MB": round(nbytes / 1e6, 2), "wall_s": round(wall_s, 4),
        "achieved_MBps": round(nbytes / wall_s / 1e6, 2),
        "mem_roof_MBps": round(mem_bw / 1e6, 1),
        "ssd_roof_MBps": round(ssd_bw / 1e6, 1),
        "frac_of_mem_roof": round(nbytes / wall_s / mem_bw, 4),
    }
    if extra:
        r.update(extra)
    return r


def _oltp_replay(t23, mem_bw, ssd_bw_dev, n_devices=2):
    from repro.core import recover

    logs = t23._synth_logs(n_devices, t23.REPLAY_RECORDS, t23.REPLAY_KEYS)
    nbytes = sum(len(b) for b in logs)
    devs = t23._seg_devices(logs)
    rows = []
    ref = None
    for mode in OLTP_MODES:
        recover(devs, mode=mode)  # warm (jit compiles / allocator first-touch)
        t0 = time.perf_counter()
        st = recover(devs, mode=mode)
        wall = time.perf_counter() - t0
        if ref is None:
            ref = st.data
        else:
            assert st.data == ref, f"replay mode {mode} diverged"
        rows.append(_oltp_row("replay", mode, nbytes, wall, mem_bw,
                              ssd_bw_dev * n_devices,
                              {"records": t23.REPLAY_RECORDS}))
    return rows


def _oltp_replica_apply(t23, mem_bw, ssd_bw_dev, n_devices=2):
    from repro.replica import Replica

    logs = t23._synth_logs(n_devices, t23.REPLAY_RECORDS, t23.REPLAY_KEYS)
    nbytes = sum(len(b) for b in logs)
    rows = []
    applied = {}
    for mode in OLTP_MODES:
        devs = t23._seg_devices(logs)
        # warm pass on its own replica (jit compiles for the pallas mode,
        # allocator first-touch for the others), then the timed catch-up
        warm = Replica(t23._seg_devices(logs), mode=mode, parallel=False)
        while warm.poll(parallel=False):
            pass
        rep = Replica(devs, mode=mode, parallel=False)
        t0 = time.perf_counter()
        while rep.poll(parallel=False):
            pass
        wall = time.perf_counter() - t0
        applied[mode] = rep.applier.n_applied
        rows.append(_oltp_row("replica_apply", mode, nbytes, wall, mem_bw,
                              ssd_bw_dev * n_devices,
                              {"records": rep.applier.n_applied}))
    assert len(set(applied.values())) == 1, f"apply counts diverged: {applied}"
    return rows


def _oltp_batch_occ(mem_bw, ssd_bw_dev, n_devices=2, batch_size=2048):
    from _util import FAST, make_engine

    from repro.db import ArrayTable, BatchOCC, ScalarBatchOCC, Table
    from repro.db import ycsb

    n_records = 20_000
    n_batches = 2 if FAST else 8
    scalar_batches = 1 if FAST else 2  # per-txn python loop; keep it bounded
    rows = []
    for mode in OLTP_MODES:
        engine = make_engine("poplar", n_devices, "null", 4)
        engine.start()
        if mode == "scalar":
            table = Table()
            ycsb.load(table, n_records)
            occ = ScalarBatchOCC(table, engine, n_workers=4)
            n_b = scalar_batches
        else:
            table = ArrayTable(capacity=n_records)
            ycsb.load(table, n_records)
            occ = BatchOCC(table, engine, n_workers=4, mode=mode)
            n_b = n_batches
        wl = ycsb.YCSBWriteOnly(n_records, seed=1)
        # full-size warm-up batch: above the fused engagement threshold, so
        # the pallas mode's jit compiles land outside the timed window
        occ.execute_batch(wl.next_batch(batch_size), max_rounds=2)
        base_bytes = sum(d.bytes_written for d in engine.devices)
        t0 = time.perf_counter()
        for _ in range(n_b):
            occ.execute_batch(wl.next_batch(batch_size), max_rounds=2)
        wall = time.perf_counter() - t0
        nbytes = sum(d.bytes_written for d in engine.devices) - base_bytes
        engine.stop()
        rows.append(_oltp_row("batch_occ", mode, nbytes, wall, mem_bw,
                              ssd_bw_dev * n_devices,
                              {"records": n_b * batch_size}))
    return rows


def run_oltp():
    import table23_recovery as t23

    mem_bw = _mem_bw_probe()
    ssd_bw_dev = float(os.environ.get("REPRO_SSD_BW", 1.2e9))
    rows = (_oltp_replay(t23, mem_bw, ssd_bw_dev)
            + _oltp_replica_apply(t23, mem_bw, ssd_bw_dev)
            + _oltp_batch_occ(mem_bw, ssd_bw_dev))
    from _util import emit

    emit(rows, ["bench", "section", "mode", "MB", "records", "wall_s",
                "achieved_MBps", "mem_roof_MBps", "ssd_roof_MBps",
                "frac_of_mem_roof"], name="roofline_oltp")
    return rows


def run(duration=None):
    return run_oltp()


if __name__ == "__main__":
    from _util import bench_runtime_setup

    bench_runtime_setup()
    run()
