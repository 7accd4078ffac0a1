"""Chip smoke run: the served OLTP path, a crash and its recovery on one TPU.

One process, no subprocesses; every phase runs in the process that holds the
chip:

1. **gate** — JAX must report a TPU and the Pallas kernels must run compiled
   (``repro.kernels.ops._default_interpret()`` false, ``REPRO_FORCE_INTERPRET``
   unset).  There is no CPU fallback.  The persistent compile cache is set up
   here (``enable_compile_cache``), before the first compile.
2. **load** — ``SingleBackend.make("pallas")`` over two emulated SSD log
   devices with real device clocks, loaded by ``ycsb.load`` with YCSB's table:
   10M rows of 10 fields x 100 B (paper §6.2).
3. **serve** — open-loop Poisson clients submit Zipfian(0.99) write-only YCSB
   transactions through ``GroupCommitScheduler`` at a fixed offered rate.
   Every ticket must be acked (durable and committable), with no executor
   error and no reject.  These cuts run the Pallas ``occ_seg_reduce`` kernel;
   its shapes are compiled first, through the same entry point.
4. **bulk** — ``execute_batch`` rounds of 8,192 transactions, above the fused
   threshold, so the fused validate->sequence program runs too.
5. **crash** — a last batch is in flight when the engine stops without
   quiescing; a torn frame lands on a device tail.  ``recover(mode="pallas")``
   must take the fused path, equal the scalar and vectorized recoveries
   exactly, and hold every acknowledged write.

Each phase prints one JSON line.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
any failed check exits non-zero without it.

Run from the repository root: ``python chip_smoke.py``.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time
from typing import Dict, List, Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro import obs  # noqa: E402
from repro.core import EngineConfig, Txn, recover  # noqa: E402
from repro.db import ycsb  # noqa: E402
from repro.db.batch import TxnSpec  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.serve import (  # noqa: E402
    ACKED,
    GroupCommitScheduler,
    OpenLoopDriver,
    ServeConfig,
    SingleBackend,
)

N_ROWS = 10_000_000          # YCSB / paper §6.2 table size
SERVE_TXNS = 4_000
SERVE_RATE = 2_000.0         # offered txn/s, inside fig_serve's sweep
MAX_BATCH = 256              # fig_serve's cut bound
BULK_BATCHES = 3
BULK_TXNS = 8_192            # 8,192 lanes per round: above fused_min_lanes
# a sealed segment holds >= 1,024 records of ~1 KB, so every sealed tile
# takes the fused device scan, and each device seals several
SEGMENT_BYTES = 1_310_720
THETA = 0.99                 # YCSB's default Zipfian constant
SEED = 0

# one acknowledged write: (key, value, ssn)
Acked = Tuple[str, bytes, int]


class SmokeError(RuntimeError):
    """A phase's check failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def device_gate() -> Dict:
    """The device JAX reports, or :class:`SmokeError` unless it is a TPU on
    which the Pallas kernels run compiled."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    check(platform == "tpu", f"JAX finds no TPU (platform {platform!r})")
    check("REPRO_FORCE_INTERPRET" not in os.environ,
          "REPRO_FORCE_INTERPRET is set: the kernels would run in interpret mode")
    check(not ops._default_interpret(),
          "the Pallas kernels would run in interpret mode")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def load_phase(
    rows: int, device_dir: str, segment_bytes: int = SEGMENT_BYTES,
    seed: int = SEED,
) -> Tuple[SingleBackend, Dict]:
    cfg = EngineConfig(n_buffers=2, device_kind="ssd", device_dir=device_dir,
                       device_clock="real", segment_bytes=segment_bytes)
    be = SingleBackend.make("pallas", n_workers=2, cfg=cfg,
                            table_capacity=rows)
    t0 = time.perf_counter()
    ycsb.load(be.table, rows, seed=seed)
    dt = time.perf_counter() - t0
    check(len(be.table) == rows, f"loaded {len(be.table)} rows, want {rows}")
    return be, {"phase": "load", "rows": rows,
                "row_bytes": ycsb.N_COLS * ycsb.COL_BYTES,
                "load_s": dt}


def _writes_of(txns) -> List[Acked]:
    return [(k, v, t.ssn) for t in txns for k, v in t.write_set]


def _warm_cut_shapes(be: SingleBackend, max_batch: int) -> List[Acked]:
    """Compile every cut shape before the clock starts: one conflict-free
    batch per power-of-two size up to ``max_batch``, through the backend's
    own entry point.  A served cut is conflict-free, so its kernel shapes are
    these.  Returns the committed writes."""
    committed = []
    value = bytes(ycsb.N_COLS * ycsb.COL_BYTES)
    n, base = 1, len(be.table) - 1
    while n <= max_batch:
        specs = [TxnSpec(writes=[(ycsb.key_of(base - i), value)])
                 for i in range(n)]
        committed += [t for _, t in be.execute(specs).committed]
        n *= 2
    be.tick()
    be.drain()
    check(all(t.committed for t in committed), "warm-up batch did not commit")
    return _writes_of(committed)


def serve_phase(
    be: SingleBackend, n_txn: int = SERVE_TXNS, rate: float = SERVE_RATE,
    max_batch: int = MAX_BATCH, seed: int = SEED,
) -> Tuple[List[Acked], Dict]:
    t0 = time.perf_counter()
    acked = _warm_cut_shapes(be, max_batch)
    warm_s = time.perf_counter() - t0
    compiled_warm = ops.fused_cache_sizes()["occ_seg_reduce"]

    specs = ycsb.YCSBWriteOnly(len(be.table), seed=seed + 1,
                               theta=THETA).next_specs(n_txn)
    sched = GroupCommitScheduler(be, ServeConfig(
        latency_budget_s=1e-3, max_batch=max_batch, queue_capacity=4096))
    sched.start()
    try:
        rep = OpenLoopDriver(sched, specs, rate_per_s=rate,
                             seed=seed + 2).run(settle_timeout_s=60.0)
    finally:
        sched.stop(quiesce=True)
    st = sched.stats()
    check(st["exec_errors"] == 0,
          f"{st['exec_errors']} executor errors; last:\n{st['last_exec_error']}")
    check(rep.submitted == n_txn and rep.rejected == 0,
          f"submitted {rep.submitted} of {n_txn}, rejected {rep.rejected}")
    check(rep.acked == n_txn, f"acked {rep.acked} of {n_txn} "
          f"(aborted {rep.aborted})")
    compiled = ops.fused_cache_sizes()["occ_seg_reduce"]
    check(compiled > 0, "the served cuts never ran occ_seg_reduce")
    for t in rep.tickets:
        if t.status == ACKED:
            acked += [(k, v, t.ssn) for k, v in t.spec.writes]
    return acked, {
        "phase": "serve", "warmup_s": warm_s, "submitted": rep.submitted,
        "acked": rep.acked, "rejected": rep.rejected,
        "exec_errors": st["exec_errors"], "offered_per_s": rate,
        "goodput_per_s": rep.goodput_per_s, "p50_ms": rep.pct_ms(50),
        "p99_ms": rep.pct_ms(99), "cuts": st["cuts"],
        "mean_cut": st["mean_cut"],
        "occ_seg_reduce_compiles": compiled,
        "occ_seg_reduce_compiles_in_window": compiled - compiled_warm,
    }


def bulk_phase(
    be: SingleBackend, n_batches: int = BULK_BATCHES,
    batch_txns: int = BULK_TXNS, seed: int = SEED,
) -> Tuple[List[Acked], Dict]:
    wl = ycsb.YCSBWriteOnly(len(be.table), seed=seed + 3, theta=THETA)
    obs.enable()
    committed: List[Txn] = []
    be.start()
    try:
        t0 = time.perf_counter()
        for _ in range(n_batches):
            out = be.execute(wl.next_batch(batch_txns))
            committed += [t for _, t in out.committed]
        be.quiesce(timeout=60.0)
        be.drain()
        dt = time.perf_counter() - t0
    finally:
        be.stop()
        snap = obs.disable()
    check(all(t.committed for t in committed), "bulk commits not durable")
    sizes = ops.fused_cache_sizes()
    check(sizes["fused_validate_sequence"] > 0,
          "no round ran fused_validate_sequence")
    check(sizes["occ_seg_reduce"] > 0, "occ_seg_reduce never compiled")
    counters = snap["counters"]
    declines = {r: counters.get(f"occ.fused.decline.{r}", 0)
                for r in ("small_batch", "dense_padding", "i32_range")}
    return _writes_of(committed), {
        "phase": "bulk", "batches": n_batches, "batch_txns": batch_txns,
        "committed": len(committed), "seconds": dt,
        "fused_rounds": counters.get("occ.fused.rounds", 0),
        "fused_declines": declines, "compiles": sizes,
    }


def _torn_frame(key: str, cut: int = 7) -> bytes:
    t = Txn(tid=777777, write_set=[(key, b"TORN-VALUE-NEVER-COMMITTED")])
    t.ssn = 1 << 40     # would win every last-writer-wins race if replayed
    return t.encode()[:-cut]


def crash_phase(
    be: SingleBackend, device_dir: str, acked: List[Acked],
    batch_txns: int = BULK_TXNS, seed: int = SEED,
) -> Dict:
    # a batch is in flight when the engine stops: no quiesce, no final
    # drain; whatever of it reached the devices is un-acked tail
    wl = ycsb.YCSBWriteOnly(len(be.table), seed=seed + 4, theta=THETA)
    be.start()
    be.execute(wl.next_batch(batch_txns))
    be.stop()
    for d in be.engine.devices:
        d.close()
    sealed = [d.stats()["n_sealed_segments"] for d in be.engine.devices]
    check(min(sealed) >= 2, f"sealed segments per device {sealed}, want >= 2")
    with open(os.path.join(device_dir, "log_0.bin"), "ab") as f:
        f.write(_torn_frame(acked[0][0]))
        f.flush()
        os.fsync(f.fileno())

    times = {}
    states = {}
    for mode in ("pallas", "vectorized", "scalar"):
        t0 = time.perf_counter()
        states[mode] = recover(be.engine.devices, mode=mode)
        times[mode] = time.perf_counter() - t0
    rep = states["pallas"].report
    check(rep.fused, "recover(mode='pallas') did not take the fused path")
    check(ops.fused_cache_sizes()["fused_replay_scan"] > 0,
          "no recovery tile ran fused_replay_scan")
    ref = states["scalar"]
    for mode in ("pallas", "vectorized"):
        st = states[mode]
        check(st.data == ref.data, f"{mode} image differs from scalar")
        check((st.rsne, st.n_replayed) == (ref.rsne, ref.n_replayed),
              f"{mode} watermarks differ from scalar")
    data = ref.data
    check(all(v != b"TORN-VALUE-NEVER-COMMITTED" for v, _ in data.values()),
          "a torn frame was replayed")
    missing = 0
    for k, v, s in acked:
        got = data.get(k.encode())
        if got is None or not (got[1] > s or got == (v, s)):
            missing += 1
    check(missing == 0, f"{missing} of {len(acked)} acked writes not recovered")
    return {
        "phase": "crash", "sealed_segments": sealed, "fused": rep.fused,
        "images_equal": True, "recovered_keys": len(data),
        "acked_writes": len(acked), "acked_missing": missing,
        "rsne": ref.rsne, "n_replayed": ref.n_replayed,
        "recover_s": times,
        "fused_replay_scan_compiles":
            ops.fused_cache_sizes()["fused_replay_scan"],
    }


def _line(obj: Dict) -> None:
    print(json.dumps(obj), flush=True)


def main() -> int:
    try:
        device = device_gate()
        cache = ops.enable_compile_cache()    # before the first compile
        _line({"phase": "gate", "device": device, "compile_cache": cache})
        with tempfile.TemporaryDirectory(prefix="chip_smoke_") as d:
            be, r = load_phase(N_ROWS, d)
            _line(r)
            acked, r = serve_phase(be)
            _line(r)
            more, r = bulk_phase(be)
            _line(r)
            _line(crash_phase(be, d, acked + more))
    except SmokeError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    _line({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
