"""One run of one cell: set-up, the measured window, crash, recovery, check.

The system under test is the served path of ``repro``:
``GroupCommitScheduler.submit`` over ``SingleBackend.make("pallas")``
(``BatchOCC`` -> ``PoplarEngine`` -> emulated SSD log devices with real
clocks).  Everything else here is the yardstick: the clients, the clock, the
kernel warm-up, the crash, and the plain reference in :mod:`reference`.

Phases, in one process:

1. **set-up** — build the backend, load the table from the seed through the
   public ``insert``, compile every kernel shape the window and the recovery
   can use, and draw the window's transactions and arrival times;
2. **window** — offer the traffic for ``seconds``: open-loop Poisson
   arrivals at the mix's fixed rate (latency is charged from the scheduled
   arrival to the moment the client is answered), or a closed loop of
   ``clients`` with no think time; each ticket's answer is kept as plain
   numbers as soon as it comes, and the ticket dropped;
3. **settle** — every ticket of the window reaches a terminal state;
4. **crash** — a last burst of ``max_batch`` tickets is submitted and the
   server stops without quiescing as soon as the first of them is
   acknowledged, then a torn frame is appended to device 0, and everything
   of the program is dropped;
5. **recover** — ``recover(mode="pallas")`` on devices reopened from disk;
6. **check** — the plain reference replays the recorded cuts against the
   device bytes and the recovered image (see :mod:`reference`).
"""

from __future__ import annotations

import contextlib
import gc
import glob
import math
import os
import shutil
import sys
import tempfile
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import probes, reference
from .spec import Bench

SETTLE_S = 60.0          # the longest a window's ticket may take to settle
CRASH_ACK_WAIT_S = 10.0  # the longest the crash waits for a first tail ack
SCAN_ALL_MAX = 1024      # look past an unanswered oldest ticket when at
#                          most this many are open (cheap to scan)
OPEN_SCAN_EVERY = 256    # open loop: look past it once in this many arrivals


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _bucket(n: int, min_size: int = 8) -> int:
    return 1 << (max(int(n), min_size, 1) - 1).bit_length()


# --- warm-up: every kernel shape the traffic can reach ---------------------

def _levels(lo: int, hi: int) -> List[int]:
    """``_pow2(a)`` for every ``a`` in ``[lo, hi]``."""
    out: List[int] = []
    while lo <= hi:
        out.append(_pow2(lo))
        lo = out[-1] + 1
    return out


def warm_shapes(accesses: Tuple[int, int], writes: Tuple[int, int],
                max_cut: int, fused_min_lanes: int
                ) -> Dict[str, List[Tuple]]:
    """The kernel calls a cut of ``1..max_cut`` transactions, each touching
    ``accesses`` keys of which ``writes`` are written, can make in
    ``BatchOCC``: ``occ_seg_reduce`` ``(items, slots, op)`` below
    ``fused_min_lanes`` access lanes (base SSNs: ``max`` over all lanes,
    keyed by transaction; first writers: ``min`` over write lanes, keyed by
    row), ``fused_validate_sequence`` ``(n_txn, k)`` at or above it.  Served
    cuts are conflict-free, so the written rows of a cut are distinct."""
    seg, fused = set(), set()
    top = fused_min_lanes - 1
    for n in range(1, max_cut + 1):
        a_lo, a_hi = n * accesses[0], n * accesses[1]
        for p in _levels(a_lo, min(a_hi, top)):
            seg.add((p, _pow2(n), "max"))
        if a_lo <= top:
            for p in _levels(n * writes[0], min(n * writes[1], top)):
                seg.add((p, p, "min"))
        for k in range(accesses[0], accesses[1] + 1):
            if n * k >= fused_min_lanes:      # the busiest txn has k lanes
                fused.add((_bucket(n), _bucket(k, 1)))
    return {"seg": sorted(seg), "fused": sorted(fused)}


def warm_kernels(ops, shapes: Dict[str, List[Tuple]], cap: int,
                 scan_lanes: List[int]) -> int:
    """Compile (or load from the persistent cache) each shape by calling the
    program's own jitted entry points with arguments of the types its
    callers pass (numpy int32); returns the number of calls."""
    import jax

    n = 0
    for items, slots, op in shapes["seg"]:
        key = np.full(items, -1, np.int32)
        key[:slots] = np.arange(slots, dtype=np.int32)
        jax.block_until_ready(ops.occ_seg_reduce(
            key, np.zeros(items, np.int32), n_slots=slots, op=op))
        n += 1
    for n_txn, k in shapes["fused"]:
        acc = np.zeros((6, n_txn * k), np.int32)
        acc[3] = -1
        jax.block_until_ready(ops.fused_validate_sequence(
            acc, np.ones(n_txn, np.int32), n_txn=n_txn, k=k, cap=cap))
        n += 1
    for lanes in scan_lanes:
        scan = np.zeros((3, lanes), np.int32)
        scan[0] = 2 * lanes
        jax.block_until_ready(ops.fused_replay_scan(scan, n_slots=2 * lanes))
        n += 1
    return n


def scan_ladder(segment_bytes: int, ring_bytes: int, lane_bytes_min: int,
                min_lanes: int = 1024) -> List[int]:
    """Lane buckets a recovery tile can fill: from the fused scan's floor
    to a sealed segment.  A device seals once its tail passes
    ``segment_bytes``, after a flush of at most the log buffer's ring, so a
    segment holds less than their sum."""
    hi = _bucket((segment_bytes + ring_bytes) // lane_bytes_min)
    out, b = [], _bucket(min_lanes)
    while b <= hi:
        out.append(b)
        b *= 2
    return out


# --- the run -----------------------------------------------------------------

@dataclass
class Window:
    """What one measured window produced: per ticket, in submission order,
    its scheduled arrival and actual submission (perf_counter seconds) and
    what its client was told (:class:`probes.Answers`)."""

    seconds: float
    scheduled: np.ndarray
    submitted: np.ndarray
    answers: Dict[str, np.ndarray]
    t0: float = 0.0
    t_end: float = 0.0
    open_loop: bool = True
    queue_samples_end: int = 0       # scheduler queue samples at the close
    compiles: Dict[str, int] = field(default_factory=dict)

    @property
    def n(self) -> int:
        return len(self.scheduled)

    @property
    def acked(self) -> np.ndarray:
        return self.answers["code"] == probes.ACKED

    def latencies_ms(self) -> np.ndarray:
        a = self.acked
        return (self.answers["t_ack"][a] - self.scheduled[a]) * 1e3

    def goodput(self) -> float:
        t = self.answers["t_ack"][self.acked]
        n = int(((t >= self.t0) & (t <= self.t_end)).sum())
        return n / (self.t_end - self.t0)

    def failed(self) -> int:
        c = self.answers["code"]
        return int(((c == probes.REJECTED) | (c == probes.ABORTED)).sum())


class _Pending:
    """The window's tickets not yet answered, oldest first; each answered
    one is handed to :class:`probes.Answers` and dropped."""

    def __init__(self, answers: probes.Answers):
        self.answers = answers
        self.open = deque()

    def add(self, i: int, t) -> None:
        self.open.append((i, t))

    def harvest(self, scan: bool) -> List[int]:
        """Take the answered tickets at the head, and with ``scan`` every
        answered one (where at most ``SCAN_ALL_MAX`` are open); returns
        their indices."""
        done = []
        op = self.open
        while op and probes.answered(op[0][1]):
            i, t = op.popleft()
            self.answers.take(i, t)
            done.append(i)
        if scan and op and len(op) <= SCAN_ALL_MAX:
            keep = deque()
            for i, t in op:
                if probes.answered(t):
                    self.answers.take(i, t)
                    done.append(i)
                else:
                    keep.append((i, t))
            self.open = keep
        return done

    def settle(self, timeout: float) -> None:
        """Wait for every open ticket (at most ``timeout`` in all), then
        take each, answered or not."""
        deadline = time.perf_counter() + timeout
        for i, t in self.open:
            # the event, not the status: the status turns terminal a moment
            # before the client is answered
            t._event.wait(timeout=max(0.0, deadline - time.perf_counter()))
            self.answers.take(i, t)
        self.open = deque()


class CellRun:
    """One cell's system under test and its yardstick, set up from a seed.

    ``scale`` overrides sizes, for tests at a size a CPU holds:
    ``{"schema": {...}, "traffic": {...}, "config": {...}}`` (e.g. ``rows``,
    ``rate_per_s``);
    ``wrap``
    puts a layer between the scheduler and the backend (the control and the
    fault tests break the served path there); ``record=False`` keeps no
    cuts and no attempts, so the reference cannot run (for measuring what
    the record costs)."""

    def __init__(self, bench: Bench, cell: str, seed: int, seconds: float,
                 scale: Optional[Dict] = None, trace: bool = False,
                 wrap=None, record: bool = True):
        from repro.core import EngineConfig
        from repro.kernels import ops
        from repro.serve import GroupCommitScheduler, SingleBackend

        self.bench, self.seed, self.trace = bench, seed, trace
        self.cell = bench.cell(cell)
        scale = scale or {}
        self.cfg = {**bench.config(self.cell["config"]),
                    **scale.get("config", {})}
        self.traffic = {**bench.traffic(self.cell["traffic"]),
                        **scale.get("traffic", {})}
        self.schema = bench.schema(self.cfg["schema"]).Schema(
            self.cfg, **scale.get("schema", {}))
        self.compile_counter = probes.CompileCounter()
        self.compile_counter.install()
        self.gc_pauses = probes.GcPauses()
        self.ack_stamps = probes.AckStamps(GroupCommitScheduler)
        self.kernel_calls = None
        if trace:
            self.kernel_calls = probes.KernelCalls()
            self.kernel_calls.install(ops)
        self._tmp = tempfile.TemporaryDirectory(prefix="bench_logs_")
        self.dev_dir = self._tmp.name
        cfg = self.cfg
        ecfg = EngineConfig(
            n_buffers=int(cfg["log_devices"]), device_kind="ssd",
            device_dir=self.dev_dir, device_clock="real",
            segment_bytes=int(cfg["segment_bytes"]),
            flush_interval=float(cfg["flush_interval_s"]))
        self.be = SingleBackend.make("pallas", n_workers=int(cfg["workers"]),
                                     cfg=ecfg,
                                     table_capacity=self.schema.capacity)
        self.device_writes = probes.DeviceWrites(self.be.engine.devices)
        dev = self.be.engine.devices[0].spec
        if (dev.bandwidth_bytes_per_s, dev.latency_s) != (
                float(cfg["device_bytes_per_s"]), float(cfg["device_latency_s"])):
            raise ValueError(f"log device {dev} is not the configured one")
        t = time.perf_counter()
        self.schema.load(self.be.table, seed)
        self.load_s = time.perf_counter() - t

        tr = self.traffic
        snap = self.compile_counter.snapshot()
        t = time.perf_counter()
        shapes = warm_shapes(self.schema.accesses, self.schema.writes,
                             int(tr["warm_max_cut"]),
                             int(self.be.occ.fused_min_lanes))
        self.warm_calls = warm_kernels(
            ops, shapes, _bucket(len(self.be.table.ssn)),
            scan_ladder(int(cfg["segment_bytes"]),
                        self.be.engine.cfg.buffer_capacity,
                        self.schema.lane_bytes_min))
        self.warm_s = time.perf_counter() - t
        self.setup_compiles = probes.since(self.compile_counter, snap)

        self.source = self.schema.clients(tr, self.be.table, seed)
        self.source.keep = record
        self.record = record
        self.fault = wrap(self.be) if wrap else None
        self.rec = probes.RecordingBackend(self.fault or self.be, on=record)
        self.sched = self.new_scheduler()
        self.exec_errors = 0
        self.arrivals = None
        if tr["arrival"] == "open":
            self.arrivals = self.open_arrivals(float(tr["rate_per_s"]),
                                               seconds)
        self.window: Optional[Window] = None
        self.tail: Dict[str, np.ndarray] = {}
        self.spans = None
        self.device_trace = None

    def new_scheduler(self):
        from repro.serve import GroupCommitScheduler, ServeConfig

        tr = self.traffic
        return GroupCommitScheduler(self.rec, ServeConfig(
            max_batch=int(tr["max_batch"]),
            latency_budget_s=float(tr["latency_budget_s"]),
            queue_capacity=int(tr["queue_capacity"])))

    # --- traffic ---------------------------------------------------------------
    def open_arrivals(self, rate: float, seconds: float
                      ) -> Tuple[np.ndarray, list]:
        """Poisson arrival offsets over ``seconds`` and their transactions,
        drawn from the seed before the window opens."""
        rng = np.random.default_rng([self.seed, 3])
        n = int(rate * seconds * 1.2 + 10 * math.sqrt(rate * seconds) + 10)
        off = np.cumsum(rng.exponential(1.0 / rate, n))
        off = off[off < seconds]
        return off, self.source.take(len(off))

    def _submit(self, x, client: int):
        if callable(x):
            return self.sched.submit(make_spec=x, client_id=client)
        return self.sched.submit(spec=x, client_id=client)

    # --- window ----------------------------------------------------------------
    def serve(self, seconds: float, t_start: Optional[float] = None,
              arrivals=None) -> Window:
        """Start the server and offer the traffic for ``seconds``; returns
        once every ticket of the window is terminal.  ``t_start`` is the
        process start, for ``setup_s``."""
        self.sched.start()
        trace_dir = None
        try:
            if self.trace:
                from repro.trace import span
                from jax import profiler

                trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
                span.enable(capacity=1 << 21)
                opts = profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                profiler.start_trace(trace_dir, profiler_options=opts)
                self.kernel_calls.on = True
            snap = self.compile_counter.snapshot()
            self._answers = probes.Answers()
            self._pending = _Pending(self._answers)
            self.gc_pauses.on = True
            if self.traffic["arrival"] == "open":
                w = self._open_loop(seconds, arrivals or self.arrivals,
                                    t_start)
            else:
                w = self._closed_loop(seconds, int(self.traffic["clients"]),
                                      t_start)
            self.gc_pauses.on = False
            w.compiles = probes.since(self.compile_counter, snap)
            w.queue_samples_end = len(self.sched.queue_samples)
            if self.trace:
                from repro.trace import span
                from jax import profiler

                profiler.stop_trace()
                self.kernel_calls.on = False
                self.spans = span.disable()
            self._pending.settle(SETTLE_S)
            w.answers = self._answers.arrays(w.n)
        except BaseException:
            self.sched.stop(quiesce=False)
            raise
        if trace_dir is not None:
            from . import trace_reduce

            path = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb"))[0]
            self.device_trace = trace_reduce.load(path)
            shutil.rmtree(trace_dir, ignore_errors=True)
        self.window = w
        self.stats = self.sched.stats()
        return w

    def _window_span(self):
        if self.trace:
            from jax import profiler

            return profiler.TraceAnnotation("bench_window")
        return contextlib.nullcontext()

    def _open_loop(self, seconds: float, arrivals, t_start) -> Window:
        off, txns = arrivals
        n = int(np.searchsorted(off, seconds))
        sub = np.empty(n)
        pending = self._pending
        with self._window_span():
            t0 = time.perf_counter()
            if t_start is not None:
                self.setup_s = t0 - t_start
            due = t0 + off[:n]
            for i in range(n):
                pending.harvest(scan=i % OPEN_SCAN_EVERY == 0)
                now = time.perf_counter()
                if now < due[i]:
                    time.sleep(due[i] - now)
                    now = time.perf_counter()
                sub[i] = now
                pending.add(i, self._submit(txns[i], i))
            t_end = max(t0 + seconds, time.perf_counter())
            time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
        return Window(seconds, due, sub, {}, t0, t_end)

    def _closed_loop(self, seconds: float, clients: int, t_start) -> Window:
        sched, sub, home = [], [], []
        pending = self._pending
        with self._window_span():
            t0 = time.perf_counter()
            if t_start is not None:
                self.setup_s = t0 - t_start
            end = t0 + seconds
            first = self.source.take(clients, homes=range(clients))
            for c, x in enumerate(first):
                pending.add(c, self._submit(x, c))
                home.append(c)
                sched.append(t0)
                sub.append(t0)
            while True:
                now = time.perf_counter()
                if now >= end:
                    break
                done = pending.harvest(scan=False)
                if not done and pending.open:
                    pending.open[0][1].wait(timeout=min(1e-3, end - now))
                    done = pending.harvest(scan=True)
                if not done:
                    continue
                homes = [home[i] for i in done]
                nxt = self.source.take(len(done), homes=homes)
                now = time.perf_counter()
                for c, x in zip(homes, nxt):
                    i = len(home)
                    pending.add(i, self._submit(x, c))
                    home.append(c)
                    sched.append(now)
                    sub.append(now)
            t_end = end
        return Window(seconds, np.asarray(sched), np.asarray(sub), {},
                      t0, t_end, open_loop=False)

    # --- crash and recovery ------------------------------------------------------
    def crash(self) -> bytes:
        """Submit a burst of ``max_batch`` tickets and cut the power the
        moment the first of them is acknowledged: from then on the log
        devices keep nothing (writes and seals are dropped), and only the
        tickets acknowledged by then count as acknowledged.  Then stop the
        server without quiescing and append a torn frame to device 0;
        returns the torn bytes."""
        burst = int(self.traffic["max_batch"])
        txns = self.source.take(burst, homes=range(burst))
        switch = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)      # wake at the ack, not 5 ms later
        try:
            tail = [self._submit(x, i) for i, x in enumerate(txns)]
            tail[0].wait(timeout=CRASH_ACK_WAIT_S)
            answers = probes.Answers()
            for i, t in enumerate(tail):
                if probes.answered(t) and t.status == "acked":
                    answers.take(i, t)
            self.device_writes.cut_power()
        finally:
            sys.setswitchinterval(switch)
        # the acknowledged ones, numbered afresh
        answers.rows = [(j,) + r[1:] for j, r in enumerate(answers.rows)]
        self.tail = answers.arrays(len(answers.rows))
        del tail
        self.sched.stop(quiesce=False)
        for d in self.be.engine.devices:
            d.close()
        key = b"torn-frame-never-committed"
        torn = reference.encode(1 << 40, 777_777, 0, [(key, key)])[:-7]
        with open(os.path.join(self.dev_dir, "log_0.bin"), "ab") as f:
            f.write(torn)
            f.flush()
            os.fsync(f.fileno())
        return torn

    def recover(self):
        """``recover(mode="pallas")`` over devices reopened from disk, as a
        fresh process would; returns ``(state, seconds, compiles)``."""
        from repro.core import recover
        from repro.core.storage import make_devices

        devs = make_devices(int(self.cfg["log_devices"]), "ssd", self.dev_dir,
                            clock="real")
        snap = self.compile_counter.snapshot()
        self.gc_pauses.reset()
        self.gc_pauses.on = True
        try:
            t = time.perf_counter()
            state = recover(devs, mode="pallas")
            dt = time.perf_counter() - t
        finally:
            self.gc_pauses.on = False
            for d in devs:
                d.close()
        return state, dt, probes.since(self.compile_counter, snap)

    # --- reference ---------------------------------------------------------------
    def check(self, torn: bytes, state) -> reference.Verdict:
        if not self.record:
            raise RuntimeError("nothing was recorded to check")
        n_buf = int(self.cfg["log_devices"])
        streams = [reference.device_bytes(self.dev_dir, b) for b in range(n_buf)]
        spec_of = self.source.spec
        cuts = [reference.Cut(tags, [spec_of(t) for t in tags], workers,
                              committed, aborted)
                for tags, workers, committed, aborted in self.rec.cuts]
        read = {k for c in cuts for s in c.specs if s.values is not None
                for k in s.reads}
        loaded = self.schema.loaded(self.seed, read) if read else {}
        v = reference.replay_cuts(cuts, n_buf, streams, torn, loaded)
        both = {k: np.concatenate([self.window.answers[k], self.tail[k]])
                for k in self.tail}
        reference.check_tickets(v, both, spec_of, state.data, state.rsne,
                                self.device_writes.writes)
        v.checks["exec_errors"] = self.exec_errors
        v.checks["consistency_violations"] = self.schema.consistency(v.image)
        return v

    def free_program(self) -> None:
        """Stop the server and drop everything of the program it held (the
        table, the engine, the scheduler and its tickets), as a crash does,
        and collect, so the recovery runs beside the benchmark's compact
        record alone, as in a fresh process."""
        self.exec_errors = self.sched.n_exec_errors
        self.sched = None
        self.rec.inner = None
        self.fault = None
        self.be = None
        self.arrivals = None
        self.source.table = None
        gc.collect()

    def close(self) -> None:
        self.gc_pauses.close()
        self.ack_stamps.uninstall()
        if self.kernel_calls is not None:
            self.kernel_calls.uninstall()
        self._tmp.cleanup()


def memory_peak_bytes() -> int:
    """Peak device memory on the fullest chip (0 where not reported)."""
    import jax

    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices()))

