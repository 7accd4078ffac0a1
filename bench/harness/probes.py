"""What the benchmark watches from outside the program.

* :class:`RecordingBackend` stands between the scheduler and its backend and
  keeps every executor call — the tags of the specs handed over, their
  workers, and the committed and aborted answers — as plain numbers for the
  plain reference (the specs themselves are kept by tag, compactly, by the
  schema's source).
* :class:`AckStamps` gives every ticket an event that notes the moment the
  scheduler answers the client, on the benchmark's clock: after the
  scheduler has seen the transaction committed.
* :class:`Answers` keeps, per ticket, what its client was told, as plain
  numbers, so no ticket outlives its answer.
* :class:`KernelCalls` wraps the kernel entry points of
  ``repro.kernels.ops`` (their callers import them at call time, so the
  wrapper sees every call) and keeps each call's shapes, from which
  :mod:`kernel_cost` counts bytes and operations.
* :class:`CompileCounter` counts JAX traces and backend compiles, so a run
  can show that nothing compiled inside its window.
* :class:`GcPauses` times the interpreter's cycle collections while on.
* :class:`DeviceWrites` stands at the log devices' ``write``: it notes
  when each write returned and how many bytes the device held after it, and
  cuts the power for the crash (later writes and seals are dropped).
"""

from __future__ import annotations

import gc
import sys
import threading
import time
from dataclasses import dataclass
from typing import Dict, List, Sequence, Tuple

import numpy as np

from repro.db.batch import TxnSpec


@dataclass(slots=True)
class Tagged(TxnSpec):
    """A spec as the benchmark's clients hand it over: ``tag`` names the
    attempt, so the cut record and the answers keep numbers, not specs."""

    tag: int = -1


# a ticket's answer, as Answers keeps it
ACKED, ABORTED, REJECTED, STUCK = 0, 1, 2, 3
_CODE = {"acked": ACKED, "aborted": ABORTED, "rejected": REJECTED}


class RecordingBackend:
    """Forward everything to ``inner``; keep each ``execute`` call as
    ``(tags, workers, committed (index, ssn, tid), aborted)``, tuples of
    plain numbers, which the collector soon stops tracking."""

    def __init__(self, inner, on: bool = True):
        self.inner = inner
        self.on = on
        self.cuts: List[Tuple] = []

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def execute(self, specs, worker_ids=None, max_rounds: int = 1):
        if max_rounds != 1:
            raise ValueError("the reference models single-round cuts only")
        out = self.inner.execute(specs, worker_ids=worker_ids,
                                 max_rounds=max_rounds)
        if self.on:
            if worker_ids is None:
                worker_ids = [i % self.inner.n_workers
                              for i in range(len(specs))]
            self.cuts.append((
                tuple([s.tag for s in specs]), tuple(worker_ids),
                tuple([(i, int(t.ssn), int(t.tid)) for i, t in out.committed]),
                tuple(out.aborted)))
        return out


class StampedEvent(threading.Event):
    """A ticket's event that notes when it was first set (``t_set``, 0 until
    then).  The scheduler sets it after it has marked the ticket terminal,
    so a stamp never precedes the answer it stands for."""

    t_set = 0.0

    def set(self) -> None:
        if not self.t_set:
            self.t_set = time.perf_counter()
        super().set()


class _Threading:
    """The ``threading`` module, with :class:`StampedEvent` as ``Event``."""

    Event = StampedEvent

    def __getattr__(self, name):
        return getattr(threading, name)


class AckStamps:
    """Make the scheduler module create :class:`StampedEvent` s: it gives
    each threaded ticket ``threading.Event()`` at submission."""

    def __init__(self, scheduler_class):
        self._mod = sys.modules[scheduler_class.__module__]
        self._orig = self._mod.threading
        self._mod.threading = _Threading()

    def uninstall(self) -> None:
        self._mod.threading = self._orig


def answered(ticket) -> bool:
    """The client has its answer (the ticket's event is stamped)."""
    return ticket._event.t_set > 0.0


class Answers:
    """Per ticket, in submission order: ``(index, code, stamp, ssn, tag)``,
    where the stamp is when the client was answered and the tag names the
    spec of its last attempt (-1 where it was rejected unexecuted)."""

    def __init__(self):
        self.rows: List[Tuple] = []

    def take(self, i: int, t) -> None:
        if not answered(t):
            self.rows.append((i, STUCK, 0.0, -1, -1))
            return
        tag = t.spec.tag if t.spec is not None else -1
        self.rows.append((i, _CODE[t.status], t._event.t_set, int(t.ssn), tag))

    def arrays(self, n: int) -> Dict[str, np.ndarray]:
        rows = sorted(self.rows)
        if [r[0] for r in rows] != list(range(n)):
            raise RuntimeError("a ticket's answer was kept twice or never")
        return {"code": np.asarray([r[1] for r in rows], np.int8),
                "t_ack": np.asarray([r[2] for r in rows], np.float64),
                "ssn": np.asarray([r[3] for r in rows], np.int64),
                "tag": np.asarray([r[4] for r in rows], np.int64)}


KERNELS = ("occ_seg_reduce", "fused_validate_sequence", "fused_replay_scan")


class KernelCalls:
    """Record ``(kernel, shapes, static args)`` of every call while on."""

    def __init__(self):
        self.calls: List[Tuple[str, Tuple, Dict]] = []
        self.on = False
        self._lock = threading.Lock()

    def install(self, ops) -> None:
        self._ops = ops
        self._orig = {name: getattr(ops, name) for name in KERNELS}
        for name, orig in self._orig.items():

            def wrapper(*args, _name=name, _orig=orig, **kw):
                if self.on:
                    shapes = tuple(tuple(getattr(a, "shape", ())) for a in args)
                    with self._lock:
                        self.calls.append((_name, shapes, dict(kw)))
                return _orig(*args, **kw)

            wrapper._cache_size = getattr(orig, "_cache_size", None)
            setattr(ops, name, wrapper)

    def uninstall(self) -> None:
        for name, orig in self._orig.items():
            setattr(self._ops, name, orig)

    def of(self, kernel: str) -> List[Tuple[Tuple, Dict]]:
        return [(s, kw) for k, s, kw in self.calls if k == kernel]


class CompileCounter:
    """Counts ``/jax/core/compile/*`` events after :meth:`install`: jaxpr
    traces (a jit function meets a shape it has not seen in this process)
    and backend compiles (an XLA compile, or a load of one from the
    persistent cache)."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.traces = 0
        self.backend_compiles = 0

    def install(self) -> None:
        import jax

        def listen(event: str, duration: float, **_kw) -> None:
            if event == self.TRACE:
                self.traces += 1
            elif event == self.BACKEND:
                self.backend_compiles += 1

        jax.monitoring.register_event_duration_secs_listener(listen)

    def snapshot(self) -> Tuple[int, int]:
        return self.traces, self.backend_compiles


def since(counter: CompileCounter, snap: Sequence[int]) -> Dict[str, int]:
    t, b = counter.snapshot()
    return {"traces": t - snap[0], "compiles_or_cache_loads": b - snap[1]}


class GcPauses:
    """Count and time CPython's cycle collections, per generation, while
    ``on`` (every thread stops for a collection)."""

    def __init__(self):
        self.on = False
        self.reset()
        self._t0 = 0.0
        gc.callbacks.append(self._cb)

    def reset(self) -> None:
        self.n = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self.longest = 0.0

    def _cb(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self.on:
            dt = time.perf_counter() - self._t0
            g = info["generation"]
            self.n[g] += 1
            self.seconds[g] += dt
            self.longest = max(self.longest, dt)

    def close(self) -> None:
        if self._cb in gc.callbacks:
            gc.callbacks.remove(self._cb)

    def summary(self) -> Dict:
        return {"collections": self.n, "seconds": self.seconds,
                "longest_s": self.longest}


class DeviceWrites:
    """Wrap each device's ``write`` and ``seal``; see the module docstring."""

    def __init__(self, devices):
        self.powered = True
        self.writes: List[List[Tuple[float, int]]] = [[] for _ in devices]
        for i, d in enumerate(devices):
            self._wrap(i, d)

    def _wrap(self, i: int, dev) -> None:
        write, seal = dev.write, dev.seal
        log = self.writes[i]
        total = [0]

        def logged_write(data: bytes) -> None:
            if not self.powered:
                return
            write(data)
            total[0] += len(data)
            log.append((time.perf_counter(), total[0]))

        def guarded_seal(last_ssn: int):
            return seal(last_ssn) if self.powered else None

        dev.write, dev.seal = logged_write, guarded_seal

    def cut_power(self) -> None:
        self.powered = False
