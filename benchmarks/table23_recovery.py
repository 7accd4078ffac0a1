"""Tables 2–3 — recovery performance (checkpoint + log recovery time), plus
the replay-throughput comparison for the vectorized recovery engine.

Part 1 (paper tables): a scaled workload journals through each variant onto
n emulated SSDs with a mid-run fuzzy checkpoint; we then crash and recover,
reporting

  * checkpoint recovery time = max over devices of (ckpt bytes / read bw)
    + parallel in-memory replay (CENTR: single device serializes reads);
  * log recovery time analogously over log bytes;
  * measured wall replay time (CPU component).

Per the paper, recovery time is proportional to bytes-read / device
parallelism: POPLAR/SILO with n devices ≈ CENTR / n.

Part 2 (``bench=replay``): synthesized multi-device logs (write-only and
RAW-carrying records, one device's flush frontier lagging so RSNe actually
skips durable-but-uncommitted records) replayed through the scalar oracle
and the batched vectorized engine across 1–8 devices, reporting the replay
stage's wall time and records/s for each — the vectorized path must come out
>= 5x at 100k+ records.  A ``bench=replay_kernel`` row exercises the
compiled bucket-padded scatter-max apply (``replay_columnar`` with
``use_kernel=True``; an XLA scatter program on every backend).

Part 3 (``bench=recover_fused``): end-to-end segmented recovery — the same
synthesized logs written and sealed onto segment-chained devices, recovered
via ``recover(mode="pallas")`` (crc-trusted fast tile decode + compiled
hash-slot replay) vs ``recover(mode="vectorized")``, asserted state-equal.
The compiled path must beat the vectorized one end-to-end.
"""

from __future__ import annotations

import os
import random
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from _util import (FAST, bench_runtime_setup, emit, run_bench,  # noqa: E402
                   ycsb_write_factory)

from repro.core import CheckpointDaemon, EngineConfig, PoplarEngine, Txn, recover  # noqa: E402
from repro.core.recovery import (  # noqa: E402
    RecoveredState,
    _replay_scalar,
    compute_rsne,
    replay_columnar,
)
from repro.core.txn import decode_columnar, decode_records  # noqa: E402
from repro.core.variants import CentrEngine, SiloEngine  # noqa: E402
from repro.db import OCCWorker, Table  # noqa: E402
from repro.db import ycsb  # noqa: E402

SSD_READ_BW = 1.2e9  # symmetric with write (§6.1)

REPLAY_RECORDS = 20_000 if FAST else 200_000
REPLAY_KEYS = REPLAY_RECORDS // 10


def _run_one(engine_name: str, n_devices: int, tmp: str, n_txns: int = 4000):
    table = Table()
    ycsb.load(table, 10_000)
    cfg = EngineConfig(n_buffers=n_devices, device_kind="null", device_dir=tmp)
    if engine_name == "centr":
        eng = CentrEngine(cfg)
        n_devices = 1
    elif engine_name == "silo":
        eng = SiloEngine(cfg, epoch_interval=10e-3)
    else:
        eng = PoplarEngine(cfg)
    eng.start()
    workers = [OCCWorker(table, eng, i) for i in range(4)]
    wl = [ycsb.YCSBWriteOnly(10_000, seed=i) for i in range(4)]

    # first half of the workload
    for i in range(n_txns // 2):
        w = workers[i % 4]
        wl[i % 4].next_txn(w)
        w.drain()

    # fuzzy checkpoint (Poplar engines expose a CSN; others use buffer DSN)
    csn_fn = (lambda: eng.commit.csn) if hasattr(eng, "commit") else (lambda: 10**12)
    ck = CheckpointDaemon(os.path.join(tmp, "ckpt"), n_threads=2, m_files=2, csn_fn=csn_fn)
    parts = table.partitions(2)
    try:
        ck.run_once([table.snapshot_partition(p) for p in parts], validate_timeout=5.0)
        ckpt_dir = os.path.join(tmp, "ckpt")
    except TimeoutError:
        ckpt_dir = None

    # second half
    for i in range(n_txns // 2):
        w = workers[i % 4]
        wl[i % 4].next_txn(w)
        w.drain()
    eng.quiesce(range(4), timeout=30)
    eng.stop()

    # crash + recover
    t0 = time.perf_counter()
    state = recover(eng.devices, checkpoint_dir=ckpt_dir, parallel=True)
    wall_replay_s = time.perf_counter() - t0

    log_bytes = [d.bytes_written for d in eng.devices]
    ckpt_bytes = 0
    if ckpt_dir:
        ckpt_bytes = sum(
            os.path.getsize(os.path.join(ckpt_dir, f))
            for f in os.listdir(ckpt_dir) if f.endswith(".bin")
        )
    # emulated IO makespans (devices read in parallel)
    log_io_s = max(b / SSD_READ_BW for b in log_bytes) if log_bytes else 0.0
    ckpt_io_s = (ckpt_bytes / n_devices) / SSD_READ_BW
    rep = state.report
    return {
        "engine": engine_name,
        "devices": n_devices,
        "log_MB": round(sum(log_bytes) / 1e6, 2),
        "ckpt_MB": round(ckpt_bytes / 1e6, 2),
        "ckpt_recovery_s": round(ckpt_io_s, 6),
        "log_recovery_s": round(log_io_s, 6),
        "wall_replay_s": round(wall_replay_s, 4),
        "recovered_keys": len(state.data),
        "rsne": state.rsne,
        # structured RecoveryReport breakdown (what replayed, what each §5
        # rule dropped, decode vs replay wall split)
        "n_decoded": rep.n_decoded,
        "n_replayed": rep.n_replayed,
        "n_dropped_above_rsne": rep.n_dropped_above_rsne,
        "ckpt_keys": rep.checkpoint_keys,
        "decode_s": round(rep.decode_s, 4),
        "replay_s": round(rep.replay_s, 4),
        "n_segments": len(rep.segments),
    }


def _synth_logs(n_devices: int, n_records: int, n_keys: int,
                val_bytes: int = 64, wr_frac: float = 0.2, seed: int = 1234):
    """Synthesize per-device framed logs: globally increasing SSNs dealt
    round-robin (per-device monotone, like flush order), a mix of write-only
    and RAW-carrying records, and device 0's frontier stopped at ~90% so
    RSNe genuinely skips tail Qwr records on the other devices."""
    rng = random.Random(seed)
    bufs = [bytearray() for _ in range(n_devices)]
    stall_at = int(n_records * 0.9)
    ssn = 0
    for i in range(n_records):
        ssn += 1
        d = i % n_devices
        if n_devices > 1 and d == 0 and i >= stall_at:
            continue  # device 0 "crashed" with this record still in memory
        key = f"k{rng.randrange(n_keys):010d}"
        t = Txn(
            tid=i,
            write_set=[(key, ssn.to_bytes(8, "little") * (val_bytes // 8))],
            read_set=[("dep", 0)] if rng.random() < wr_frac else [],
        )
        t.ssn = ssn
        bufs[d].extend(t.encode())
    return [bytes(b) for b in bufs]


def _best_of(f, reps: int = 3) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        f()
        best = min(best, time.perf_counter() - t0)
    return best


def _bench_replay(n_devices: int, n_records: int):
    logs = _synth_logs(n_devices, n_records, REPLAY_KEYS)

    t0 = time.perf_counter()
    device_records = [decode_records(b) for b in logs]
    t_dec_scalar = time.perf_counter() - t0
    rsne = compute_rsne(device_records)

    # scalar oracle, lock-free sequential loop (best case for scalar)
    t_scalar = _best_of(
        lambda: _replay_scalar(RecoveredState(), device_records, rsne, parallel=False)
    )
    st = RecoveredState()
    st.rsne = rsne
    _replay_scalar(st, device_records, rsne, parallel=False)

    # the seed's deployed replay path: one thread per device, per-write lock
    t_scalar_thr = _best_of(
        lambda: _replay_scalar(RecoveredState(), device_records, rsne, parallel=True),
        reps=1,
    )

    t0 = time.perf_counter()
    cols = [decode_columnar(b) for b in logs]
    t_dec_vec = time.perf_counter() - t0
    assert compute_rsne(cols) == rsne

    t_vec = _best_of(lambda: replay_columnar(cols, rsne))
    data, n_replayed, n_skipped = replay_columnar(cols, rsne)

    assert data == st.data, "vectorized replay diverged from the scalar oracle"
    assert (n_replayed, n_skipped) == (st.n_replayed, st.n_skipped_uncommitted)
    return {
        "bench": "replay",
        "devices": n_devices,
        "n_records": n_records,
        "n_skipped": n_skipped,
        "scalar_decode_s": round(t_dec_scalar, 4),
        "vec_decode_s": round(t_dec_vec, 4),
        "scalar_replay_s": round(t_scalar, 4),
        "scalar_threaded_s": round(t_scalar_thr, 4),
        "vec_replay_s": round(t_vec, 4),
        "scalar_rec_per_s": int(n_records / t_scalar),
        "vec_rec_per_s": int(n_records / t_vec),
        "speedup": round(t_scalar / t_vec, 2),
        "speedup_vs_threaded": round(t_scalar_thr / t_vec, 2),
    }


def _seg_devices(logs, n_segments: int = 4):
    """Write each synthesized blob onto a segment-chained in-memory device:
    ``n_segments - 1`` sealed segments (sealed at record boundaries with the
    correct last-SSN stamp, so seal-time crcs and RSNe floors are exactly
    what the engine's flush path would have produced) plus a live tail."""
    from repro.core.storage import DeviceSpec, StorageDevice
    from repro.core.txn import _HDR, frame_scan, gather_u64
    import numpy as np

    devs = []
    for blob in logs:
        rec_off, _, _ = frame_scan(blob)
        ssn = gather_u64(np.frombuffer(blob, np.uint8), rec_off + _HDR.size)
        d = StorageDevice(DeviceSpec.null(), clock="virtual")
        n = len(rec_off)
        cuts = [max(1, n * i // n_segments) for i in range(1, n_segments)] + [n]
        lo = 0
        for ci, c in enumerate(cuts):
            hi = int(rec_off[c]) if c < n else len(blob)
            if hi > lo:
                d.write(blob[lo:hi])
                if ci < len(cuts) - 1:
                    d.seal(int(ssn[c - 1]))
            lo = hi
        devs.append(d)
    return devs


def _bench_recover_fused(n_devices: int, n_records: int):
    """End-to-end ``recover()`` on segmented devices: compiled fused path
    (mode="pallas") vs the vectorized numpy engine, state-equality asserted."""
    logs = _synth_logs(n_devices, n_records, REPLAY_KEYS)
    devs = _seg_devices(logs)

    # warm the jit cache outside the timed region (one-time process cost;
    # bucket padding keeps it warm for every later shape)
    recover(devs, mode="pallas")

    t_vec = _best_of(lambda: recover(devs, mode="vectorized"))
    t_fused = _best_of(lambda: recover(devs, mode="pallas"))
    a = recover(devs, mode="vectorized")
    b = recover(devs, mode="pallas")
    assert a.data == b.data and a.rsne == b.rsne, "fused recovery diverged"
    assert (a.n_replayed, a.n_skipped_uncommitted) == (
        b.n_replayed, b.n_skipped_uncommitted)
    return {
        "bench": "recover_fused",
        "devices": n_devices,
        "n_records": n_records,
        "segments_per_device": 4,
        "vec_recover_s": round(t_vec, 4),
        "fused_recover_s": round(t_fused, 4),
        "vec_rec_per_s": int(n_records / t_vec),
        "fused_rec_per_s": int(n_records / t_fused),
        "speedup": round(t_vec / t_fused, 2),
        "recovered_keys": len(b.data),
        "agrees": True,
    }


def _bench_replay_kernel(n_devices: int = 2, n_records: int = 4096):
    """Compiled bucket-padded scatter-max apply through ``replay_columnar``
    (an XLA scatter program on every backend — kernels/ops.fused_replay_apply)."""
    logs = _synth_logs(n_devices, n_records, n_keys=512)
    cols = [decode_columnar(b) for b in logs]
    rsne = compute_rsne(cols)
    data_np, _, _ = replay_columnar(cols, rsne)
    t0 = time.perf_counter()
    data_k, _, _ = replay_columnar(cols, rsne, use_kernel=True)
    t_kernel = time.perf_counter() - t0
    assert data_k == data_np, "pallas replay diverged from the numpy engine"
    return {
        "bench": "replay_kernel",
        "devices": n_devices,
        "n_records": n_records,
        "kernel_replay_s": round(t_kernel, 4),
        "agrees": True,
    }


def run(duration=None):
    rows = []
    for engine_name, nd in (("centr", 1), ("silo", 2), ("poplar", 2), ("poplar", 4)):
        tmp = tempfile.mkdtemp(prefix=f"rec_{engine_name}_{nd}_")
        try:
            r = _run_one(engine_name, nd, tmp)
            r["bench"] = "table23"
            rows.append(r)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    emit(rows, ["bench", "engine", "devices", "log_MB", "ckpt_MB",
                "ckpt_recovery_s", "log_recovery_s", "wall_replay_s",
                "recovered_keys", "rsne", "n_decoded", "n_replayed",
                "n_dropped_above_rsne", "ckpt_keys", "decode_s", "replay_s",
                "n_segments"], name="table23")

    replay_rows = [_bench_replay(nd, REPLAY_RECORDS) for nd in (1, 2, 4, 8)]
    emit(replay_rows, ["bench", "devices", "n_records", "n_skipped",
                       "scalar_decode_s", "vec_decode_s", "scalar_replay_s",
                       "scalar_threaded_s", "vec_replay_s", "scalar_rec_per_s",
                       "vec_rec_per_s", "speedup", "speedup_vs_threaded"],
         name="table23", append=True)
    kernel_row = _bench_replay_kernel()
    emit([kernel_row], ["bench", "devices", "n_records", "kernel_replay_s", "agrees"], name="table23", append=True)
    fused_rows = [_bench_recover_fused(nd, REPLAY_RECORDS) for nd in (2, 4)]
    emit(fused_rows, ["bench", "devices", "n_records", "segments_per_device",
                      "vec_recover_s", "fused_recover_s", "vec_rec_per_s",
                      "fused_rec_per_s", "speedup", "recovered_keys", "agrees"],
         name="table23", append=True)
    return rows + replay_rows + [kernel_row] + fused_rows


if __name__ == "__main__":
    bench_runtime_setup()
    run()
