"""Near-zero-overhead structured stage tracer.

A process-local :class:`Tracer` records one row per pipeline-stage span —
``(stage, shard, device, batch_id, txn_span, t_start, t_end, bytes,
n_txn, aux)`` — into preallocated numpy ring buffers.  Hook points live in
the seven pipeline stages:

* ``BatchOCC`` validate / sequence / encode   (`repro.db.batch`)
* ``PoplarEngine`` publish + logger flush     (`repro.core.engine`)
* cross-shard prepare                         (`repro.shard.coordinator`)
* ``LogShipper`` ship + ``ReplicaApplier`` apply  (`repro.replica`)
* ``GroupCommitScheduler`` cut / ack          (`repro.serve.scheduler`)
* recovery decode / replay                    (`repro.core.recovery`)

plus the interpreter's own generation-1 and -2 cycle collections
(``gc`` rows, from a ``gc.callbacks`` hook), so a pause shows where it
fell inside the other stages.

Every hook is guarded by one attribute load on the module singleton::

    _trace = TRACER.enabled
    if _trace:
        _t0 = TRACER.begin(ST_...)
    ... stage work ...
    if _trace:
        TRACER.record(ST_..., t0=_t0, t1=time.perf_counter(), ...)

so the disabled tracer is a no-op: no allocation, no lock, no branch
beyond the bool test (pinned by ``tests/test_trace.py`` via a
``tracemalloc`` filter on this file).  When enabled, :meth:`Tracer.record`
claims a ring slot under a lock and writes ten scalar cells, a few
microseconds per *batch*-granular event.  :meth:`Tracer.begin` also opens
a ``repro.<stage>`` host event in any JAX profile being captured, and
``record`` closes it on the same thread, so the spans sit on the device
trace's clock (a profile counts from its own start; the offset of a span's
event from its ``t0`` is one constant per profile).

The **ticket table** is a second preallocated table, one row per
acknowledged scheduler ticket — ``(ssn, shard, device, t_submit, t_cut,
t_precommit, t_commit, t_ack)``, the stamps ``perf_counter`` seconds —
written with one :meth:`Tracer.record_many` per ack release round.  With
the flush rows of the ring, :meth:`TraceDump.ticket_stages` splits each
ticket's latency into queue, exec, flush and commit.

What tracing costs when on, measured on a TPU v5e host with a profile
running: a span 2.9 µs with its event (1.8 µs without), a ticket row
0.5-1.6 µs, a young collection's callback 0.5 µs; about 0.3% of the host
time of a TPC-C latency-cell window and 0.9% of a saturated YCSB one.  End
to end, that cell's traced commit p50 read 0.8-7.8% above the ring alone
on the same seeds, and about as much with every one of these hooks
switched off: the cost is not pinned to a hook (``PERF.md``, section 3).

``txn_span = (txn_lo, txn_hi)`` carries the SSN range a span covers (flush
spans: the DSN interval made durable; publish spans: the batch's SSN
range), which is what lets `repro.trace.dag` reconstruct durability edges
without any timestamps — the structural dump of two identical stepped runs
is byte-identical even though the wall-clock columns differ (``gc`` rows,
which depend on the heap and not on the run's structure, are left out of
it).
"""

from __future__ import annotations

import gc
import json
import threading
import time
import warnings
from dataclasses import dataclass
from typing import Dict, Optional

import numpy as np

# --- stage taxonomy ----------------------------------------------------------
ST_VALIDATE = 0    # BatchOCC: access gather + WW/RW/observed-SSN/lock masks
ST_SEQUENCE = 1    # BatchOCC: base-SSN segmented max + Txn bookkeeping
ST_ENCODE = 2      # BatchOCC: per-buffer reserve_batch + columnar framing
ST_PUBLISH = 3     # PoplarEngine.publish_batch: ring memcpy + queue pushes
ST_FLUSH = 4       # logger_tick: segment flushes to the device (IO)
ST_XPREPARE = 5    # CrossShardCoordinator.execute: one span per participant
ST_SHIP = 6        # LogShipper.poll: tail read + streaming columnar decode
ST_APPLY = 7       # ReplicaApplier.apply: vectorized fold into the table
ST_CUT = 8         # GroupCommitScheduler: batch cut + execute
ST_ACK = 9         # GroupCommitScheduler: durable ack release round
ST_RDECODE = 10    # recovery: per-(device, segment) columnar decode
ST_RREPLAY = 11    # recovery: last-writer-wins replay (or the fused pass)
ST_DRIVER = 12     # free-form driver work (benchmarks wrap workload gen)
ST_WRITEBACK = 13  # BatchOCC phase 2: table scatter under claimed locks
ST_GC = 14         # interpreter: one generation-1/2 collection (aux=gen,
#                    n_txn=objects collected); stops every thread

STAGE_NAMES = (
    "validate", "sequence", "encode", "publish", "flush", "xprepare",
    "ship", "apply", "cut", "ack", "rdecode", "rreplay", "driver",
    "writeback", "gc",
)

# the host event each stage's span opens in a JAX profile
EVENT_NAMES = tuple("repro." + n for n in STAGE_NAMES)

# stages that occupy a (GIL-serialized) CPU; ST_FLUSH occupies its device
# (ST_GC is no pipeline stage: it pauses whatever ran)
CPU_STAGES = frozenset(
    (ST_VALIDATE, ST_SEQUENCE, ST_ENCODE, ST_PUBLISH, ST_XPREPARE,
     ST_SHIP, ST_APPLY, ST_CUT, ST_ACK, ST_RDECODE, ST_RREPLAY, ST_DRIVER,
     ST_WRITEBACK)
)

_COLUMNS = (
    ("stage", np.int16), ("shard", np.int32), ("device", np.int32),
    ("batch", np.int64), ("txn_lo", np.int64), ("txn_hi", np.int64),
    ("t0", np.float64), ("t1", np.float64),
    ("nbytes", np.int64), ("n_txn", np.int64), ("aux", np.int64),
)

# one row per acknowledged ticket (:meth:`Tracer.record_many`)
TICKET_DTYPE = np.dtype([
    ("ssn", np.int64), ("shard", np.int32), ("device", np.int32),
    ("t_submit", np.float64), ("t_cut", np.float64),
    ("t_precommit", np.float64), ("t_commit", np.float64),
    ("t_ack", np.float64),
])
TICKET_COLUMNS = TICKET_DTYPE.names


class _Ctx(threading.local):
    """Ambient per-thread trace context: the executing batch id and shard,
    set by the batch executor so nested hooks (engine publish) can stamp
    their spans without threading ids through every call signature; and
    the profiler events this thread's open spans hold, by stage."""

    batch = -1
    shard = 0
    events: Optional[Dict] = None


@dataclass
class TicketDump:
    """The ticket table, oldest first: one row per acknowledged ticket.

    * ``shard`` / ``device`` — the shard and the buffer holding its
      record (``device`` -1: read-only, no record; ``shard`` -1:
      cross-shard, records on several shards);
    * ``t_submit`` — the client's call to ``submit`` (so the queue stage
      holds admission too: a regenerated spec's first build, the wait for
      the scheduler's lock); ``t_cut`` — the end of the cut that executed
      its committing attempt (a retried ticket's earlier attempts and
      backoff count as queue time);
    * ``t_precommit`` / ``t_commit`` — the ``Txn``'s own stamps (record
      buffered; durably committed by the commit rule);
    * ``t_ack`` — the start of the release round that answered it.
    """

    ssn: np.ndarray
    shard: np.ndarray
    device: np.ndarray
    t_submit: np.ndarray
    t_cut: np.ndarray
    t_precommit: np.ndarray
    t_commit: np.ndarray
    t_ack: np.ndarray
    dropped: int = 0

    @property
    def n(self) -> int:
        return len(self.ssn)

    @classmethod
    def from_rows(cls, rows: np.ndarray, dropped: int = 0) -> "TicketDump":
        return cls(**{c: np.ascontiguousarray(rows[c])
                      for c in TICKET_COLUMNS}, dropped=dropped)

    def to_dict(self) -> Dict:
        d = {c: getattr(self, c).tolist() for c in TICKET_COLUMNS}
        d["dropped"] = self.dropped
        return d

    @classmethod
    def from_dict(cls, d: Dict) -> "TicketDump":
        rows = np.zeros(len(d["ssn"]), TICKET_DTYPE)
        for c in TICKET_COLUMNS:
            rows[c] = d[c]
        return cls.from_rows(rows, d.get("dropped", 0))


@dataclass
class TraceDump:
    """An immutable snapshot of the tracer's rows, oldest first.

    Columns are plain numpy arrays aligned by row; ``dropped`` counts ring
    overwrites (rows lost to capacity).  ``structural_dict`` /
    ``canonical_bytes`` exclude the wall-clock columns and the ``gc``
    rows, so two identical stepped runs serialize byte-identically
    (`tests/test_trace.py`).  ``tickets`` is the ticket table.
    """

    stage: np.ndarray
    shard: np.ndarray
    device: np.ndarray
    batch: np.ndarray
    txn_lo: np.ndarray
    txn_hi: np.ndarray
    t0: np.ndarray
    t1: np.ndarray
    nbytes: np.ndarray
    n_txn: np.ndarray
    aux: np.ndarray
    dropped: int = 0
    tickets: Optional[TicketDump] = None

    @property
    def n(self) -> int:
        return len(self.stage)

    def duration(self) -> np.ndarray:
        return self.t1 - self.t0

    def makespan(self) -> float:
        """Wall time covered by the pipeline's spans (first start → last
        end; ``gc`` rows left out)."""
        keep = self.stage != ST_GC
        if not keep.any():
            return 0.0
        return float(self.t1[keep].max() - self.t0[keep].min())

    def without_gc(self) -> "TraceDump":
        """The pipeline's rows: the ``gc`` rows depend on the heap, not on
        the run's structure."""
        keep = self.stage != ST_GC
        return TraceDump(**{name: getattr(self, name)[keep]
                            for name, _ in _COLUMNS},
                         dropped=self.dropped, tickets=self.tickets)

    def durable_at(self, device, ssn, shard=0) -> np.ndarray:
        """When the record ``ssn`` on ``device`` of ``shard`` became
        durable: the end of the first flush span on that (shard, device)
        whose DSN after the flush (``txn_hi``) reaches ``ssn``; NaN where
        the ring holds none (or ``device`` or ``shard`` is negative).
        Broadcasts over array arguments."""
        device, ssn, shard = np.broadcast_arrays(
            np.asarray(device, np.int64), np.asarray(ssn, np.int64),
            np.asarray(shard, np.int64))
        out = np.full(device.shape, np.nan)
        flush = self.stage == ST_FLUSH
        ok = (device >= 0) & (shard >= 0)
        for sh, dev in {(a, b) for a, b in zip(shard[ok].tolist(),
                                               device[ok].tolist())}:
            rows = np.flatnonzero(flush & (self.shard == sh)
                                  & (self.device == dev))
            if not len(rows):
                continue
            rows = rows[np.argsort(self.t1[rows], kind="stable")]
            dsn = np.maximum.accumulate(self.txn_hi[rows])
            sel = (shard == sh) & (device == dev)
            k = np.searchsorted(dsn, ssn[sel], side="left")
            hit = k < len(rows)
            end = np.full(len(k), np.nan)
            end[hit] = self.t1[rows[k[hit]]]
            out[sel] = end
        return out

    def ticket_stages(self) -> Optional[Dict[str, np.ndarray]]:
        """Each acknowledged ticket's ``t_ack − t_submit``, split exactly
        into four stages (seconds, one entry per ticket row):

        * ``queue``  = ``t_cut − t_submit`` (admission and the wait for a
          cut);
        * ``exec``   = ``t_precommit − t_cut`` (the executor);
        * ``flush``  = ``durable − t_precommit`` (the group-commit flush);
        * ``commit`` = ``t_ack − durable`` (the DSN or CSN wait, the drain
          and the release).

        ``durable`` is :meth:`durable_at` held inside ``[t_precommit,
        t_commit]`` (the DSN is published a moment before a flush span's
        end is stamped); read-only rows take ``t_precommit``.  All four
        are NaN for a ticket admitted before the tracer was enabled; flush
        and commit are NaN where ``durable`` is unknown (a flush row lost
        to the ring, or a cross-shard ticket, whose records flush on
        several shards).  ``None`` without a ticket table.
        """
        tk = self.tickets
        if tk is None:
            return None
        read_only = (tk.device < 0) & (tk.shard >= 0)
        durable = np.where(read_only, tk.t_precommit,
                           self.durable_at(tk.device, tk.ssn, tk.shard))
        durable = np.minimum(np.maximum(durable, tk.t_precommit),
                             tk.t_commit)
        missing = (tk.t_submit <= 0) | (tk.t_cut <= 0)
        out = {"queue": tk.t_cut - tk.t_submit,
               "exec": tk.t_precommit - tk.t_cut,
               "flush": durable - tk.t_precommit,
               "commit": tk.t_ack - durable}
        for v in out.values():
            v[missing] = np.nan
        return out

    def structural_dict(self) -> Dict:
        """Timestamp-free row dump (the deterministic part of a trace)."""
        d = self.without_gc()
        return {
            "n": d.n,
            "dropped": d.dropped,
            "stage": d.stage.tolist(),
            "shard": d.shard.tolist(),
            "device": d.device.tolist(),
            "batch": d.batch.tolist(),
            "txn_lo": d.txn_lo.tolist(),
            "txn_hi": d.txn_hi.tolist(),
            "nbytes": d.nbytes.tolist(),
            "n_txn": d.n_txn.tolist(),
            "aux": d.aux.tolist(),
        }

    def to_dict(self) -> Dict:
        d = {"n": self.n, "dropped": self.dropped}
        for name, _ in _COLUMNS:
            d[name] = getattr(self, name).tolist()
        if self.tickets is not None:
            d["tickets"] = self.tickets.to_dict()
        return d

    def save(self, path: str, extra: Optional[Dict] = None) -> None:
        """Write the dump as JSON; ``extra`` merges additional top-level
        keys (e.g. ``run_metadata()`` provenance stamps — ``from_dict``
        ignores keys it does not know, so stamped dumps stay loadable)."""
        d = self.to_dict()
        if extra:
            d.update(extra)
        with open(path, "w") as f:
            json.dump(d, f)
            f.write("\n")

    @classmethod
    def from_dict(cls, d: Dict) -> "TraceDump":
        n = d["n"]
        tk = d.get("tickets")
        return cls(
            stage=np.asarray(d["stage"], np.int16),
            shard=np.asarray(d["shard"], np.int32),
            device=np.asarray(d["device"], np.int32),
            batch=np.asarray(d["batch"], np.int64),
            txn_lo=np.asarray(d["txn_lo"], np.int64),
            txn_hi=np.asarray(d["txn_hi"], np.int64),
            t0=np.asarray(d.get("t0", [0.0] * n), np.float64),
            t1=np.asarray(d.get("t1", [0.0] * n), np.float64),
            nbytes=np.asarray(d["nbytes"], np.int64),
            n_txn=np.asarray(d["n_txn"], np.int64),
            aux=np.asarray(d["aux"], np.int64),
            dropped=d.get("dropped", 0),
            tickets=TicketDump.from_dict(tk) if tk is not None else None,
        )

    @classmethod
    def load(cls, path: str) -> "TraceDump":
        with open(path) as f:
            return cls.from_dict(json.load(f))


def _unwind(buf: np.ndarray, n: int) -> np.ndarray:
    """A ring's rows oldest first, copied."""
    cap = len(buf)
    if n <= cap:
        return buf[:n].copy()
    head = n % cap
    return np.concatenate([buf[head:], buf[:head]])


def _profiler_annotation():
    """JAX's host-event context manager, or None without JAX."""
    try:
        from jax.profiler import TraceAnnotation
    except ImportError:  # pragma: no cover - jax is a dependency
        return None
    return TraceAnnotation


class Tracer:
    """Ring-buffer stage tracer.  One process-local instance (:data:`TRACER`)
    is shared by every hook; ``enabled`` is the single gate the hot paths
    test.  ``record`` is thread-safe (logger threads, shard threads and the
    scheduler loop all trace concurrently).

    The ticket table (as many rows as the ring, its own
    ``tickets_dropped`` count) and the ``gc.callbacks`` hook exist for the
    hot paths only while enabled: the scheduler writes tickets only behind
    ``enabled``, and :func:`enable` installs the hook that :func:`disable`
    removes.  Generation-0 collections are not spanned: there can be
    thousands a second, each of microseconds.
    """

    def __init__(self, capacity: int = 1 << 16):
        self.enabled = False
        self._lock = threading.Lock()
        self.ctx = _Ctx()
        self._annotation = None   # jax.profiler.TraceAnnotation once enabled
        self._gc_hooked = False
        self._alloc(capacity)

    def _alloc(self, capacity: int) -> None:
        assert capacity > 0
        self.capacity = capacity
        for name, dt in _COLUMNS:
            setattr(self, f"_{name}", np.zeros(capacity, dt))
        self.n = 0
        self.dropped = 0
        self._batch_seq = 0
        self._tickets = np.zeros(capacity, TICKET_DTYPE)
        self.n_tickets = 0
        self.tickets_dropped = 0
        self._gc_event = None
        self._gc_t0 = 0.0
        self._gc_pending: list = []   # gc rows that found the lock held

    def reset(self, capacity: Optional[int] = None) -> None:
        """Drop all recorded rows (and optionally resize the ring and the
        ticket table)."""
        with self._lock:
            self._alloc(capacity or self.capacity)

    def next_batch_id(self) -> int:
        """A process-unique batch id for one executor pass (monotone, reset
        with the tracer — stepped reruns see identical id sequences)."""
        with self._lock:
            self._batch_seq += 1
            return self._batch_seq

    # --- spans on the profiler's clock ---------------------------------------
    def begin(self, stage: int) -> float:
        """Start a span of ``stage`` on this thread; returns its ``t0``.
        While a JAX profile is being captured, a ``repro.<stage>`` host
        event opens here and closes at this thread's :meth:`record` (or
        :meth:`end`) of the same stage."""
        ann = self._annotation
        if ann is None or not ann.is_enabled():
            return time.perf_counter()
        events = self.ctx.events
        if events is None:
            events = self.ctx.events = {}
        stale = events.pop(stage, None)
        if stale is not None:           # its span raised before recording
            stale.__exit__(None, None, None)
        ev = events[stage] = ann(EVENT_NAMES[stage])
        ev.__enter__()
        return time.perf_counter()      # next to the event's own stamp

    def end(self, stage: int) -> None:
        """Close this thread's open ``stage`` event without a row (the work
        turned out empty)."""
        events = self.ctx.events
        if events:
            ev = events.pop(stage, None)
            if ev is not None:
                ev.__exit__(None, None, None)

    def record(
        self,
        stage: int,
        shard: int = 0,
        device: int = -1,
        batch: int = -1,
        txn_lo: int = -1,
        txn_hi: int = -1,
        t0: float = 0.0,
        t1: float = 0.0,
        nbytes: int = 0,
        n_txn: int = 0,
        aux: int = 0,
    ) -> None:
        self.end(stage)
        with self._lock:
            self._put(stage, shard, device, batch, txn_lo, txn_hi, t0, t1,
                      nbytes, n_txn, aux)

    def _put(self, stage, shard, device, batch, txn_lo, txn_hi, t0, t1,
             nbytes, n_txn, aux) -> None:
        """Write one ring row (the caller holds the lock)."""
        i = self.n % self.capacity
        if self.n >= self.capacity:
            self.dropped += 1
            # drops silently skew any cost model fit on the dump; keep
            # them visible in the online registry too (lazy import: the
            # obs package depends on trace, not vice versa)
            from ..obs.metrics import REGISTRY

            if REGISTRY.enabled:
                REGISTRY.count("trace.ring_drops")
        self._stage[i] = stage
        self._shard[i] = shard
        self._device[i] = device
        self._batch[i] = batch
        self._txn_lo[i] = txn_lo
        self._txn_hi[i] = txn_hi
        self._t0[i] = t0
        self._t1[i] = t1
        self._nbytes[i] = nbytes
        self._n_txn[i] = n_txn
        self._aux[i] = aux
        self.n += 1

    def record_many(self, arr: np.ndarray) -> None:
        """Append ticket rows, an array of :data:`TICKET_DTYPE`: one lock,
        at most two slice assignments.  The table is a ring: past capacity
        the oldest rows are dropped."""
        cap = self.capacity
        with self._lock:
            self.n_tickets += len(arr)
            self.tickets_dropped = max(0, self.n_tickets - cap)
            arr = arr[-cap:]            # only the newest ``cap`` can stay
            i = (self.n_tickets - len(arr)) % cap
            first = min(len(arr), cap - i)
            self._tickets[i:i + first] = arr[:first]
            self._tickets[:len(arr) - first] = arr[first:]

    # --- collector pauses ------------------------------------------------------
    def _on_gc(self, phase: str, info: Dict) -> None:
        """``gc.callbacks`` hook: one ``gc`` row per generation-1/2
        collection.  Runs on whichever thread collects, possibly inside
        this tracer's own locked section: a row that finds the lock held
        waits in ``_gc_pending`` for the next dump or collection."""
        gen = info["generation"]
        if not gen:
            return
        if phase == "start":
            if self.enabled:
                ann = self._annotation
                if ann is not None and ann.is_enabled():
                    self._gc_event = ann(EVENT_NAMES[ST_GC])
                    self._gc_event.__enter__()
                self._gc_t0 = time.perf_counter()
            return
        t0, self._gc_t0 = self._gc_t0, 0.0
        t1 = time.perf_counter()
        if self._gc_event is not None:
            self._gc_event.__exit__(None, None, None)
            self._gc_event = None
        if not (self.enabled and t0):
            return
        self._gc_pending.append((ST_GC, 0, -1, -1, -1, -1, t0, t1, 0,
                                 info["collected"], gen))
        if self._lock.acquire(blocking=False):
            try:
                self._put_gc_pending()
            finally:
                self._lock.release()

    def _put_gc_pending(self) -> None:
        while self._gc_pending:
            self._put(*self._gc_pending.pop(0))

    def _hook_gc(self, on: bool) -> None:
        if on and not self._gc_hooked:
            gc.callbacks.append(self._on_gc)
        elif not on and self._gc_hooked:
            gc.callbacks.remove(self._on_gc)
        self._gc_hooked = on

    def dump(self) -> TraceDump:
        """Snapshot the recorded rows oldest-first (ring order unwound).

        Warns when the ring wrapped: a dump with drops under-represents the
        oldest stages, so durations fit from it (``CostModel.fit``) are
        biased — re-trace with a larger ``enable(capacity=...)`` instead.
        """
        if self.dropped:
            warnings.warn(
                f"trace ring dropped {self.dropped} spans (capacity "
                f"{self.capacity}); the dump is a biased sample — re-trace "
                f"with a larger enable(capacity=...) before fitting",
                RuntimeWarning,
                stacklevel=2,
            )
        with self._lock:
            self._put_gc_pending()
            cols = {name: _unwind(getattr(self, f"_{name}"), self.n)
                    for name, _ in _COLUMNS}
            tickets = TicketDump.from_rows(
                _unwind(self._tickets, self.n_tickets), self.tickets_dropped)
            return TraceDump(**cols, dropped=self.dropped, tickets=tickets)


TRACER = Tracer()


def enable(capacity: int = 1 << 16) -> Tracer:
    """Arm the process tracer with a fresh ring and ticket table of
    ``capacity`` rows each, hook the collector, and emit profiler events
    while a JAX profile runs."""
    TRACER.reset(capacity)
    TRACER._annotation = _profiler_annotation()
    TRACER.enabled = True
    TRACER._hook_gc(True)
    return TRACER


def disable() -> TraceDump:
    """Disarm the tracer, unhook the collector and return the final
    snapshot."""
    TRACER.enabled = False
    TRACER._hook_gc(False)
    return TRACER.dump()
