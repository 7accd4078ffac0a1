"""Open-loop group-commit scheduler: the OLTP serving front end.

Everything below the serving tier is a closed-loop driver calling
``execute_batch`` directly; this module models the million-client world the
paper's latency claims (§6, fig7) are about.  Async client sessions submit
*single* transactions; the scheduler coalesces arrivals into batches for
the array-native executor under a configurable latency budget (group
commit), applies admission control when the log or a shard saturates, and
retries validation losers with backoff (hot-key skew).  A client's commit
acknowledgment is released **only** once its record is durable *and*
committable under the Qww/Qwr watermark rule — the scheduler never acks a
transaction itself; it observes ``txn.committed``, which only
:meth:`repro.core.commit.CommitProtocol.drain` (or the cross-shard sweep,
which applies the same ``committable()`` predicate per participant) can
set.  Ack = durable ∧ committable, end to end.

Batch cutting is **strict-FIFO and conflict-free**: a cut is the longest
queue prefix in which no transaction touches (reads or writes) a key that
an earlier transaction of the cut writes, stopped at the first transaction
that does (head-of-line) or at ``max_batch``.  Reads of one key share a
cut, and so does a write after a read of its key: that pair has no RAW or
WAW dependency, and first-come-wins lets both win.  Two consequences:

* within a cut every transaction wins validation round 1 (no intra-batch
  first-come-wins losses), so a group-commit round never silently reorders
  admitted work — the per-key order of *conflicting* transactions (a RAW
  or WAW pair) is admission order;
* the device logs are therefore *invariant under cut points*: a read-only
  winner reserves no slot, and a later writer's SSN does not depend on it,
  so for a conflict-free arrival schedule any cut sequence produces
  byte-identical logs to one direct ``execute_batch`` of the same
  transactions, and for arbitrary schedules any two cut configurations
  produce byte-identical logs to each other.  The property tests pin both.

Two operating modes, mirroring the engine:

* **stepped** — :meth:`GroupCommitScheduler.step` advances one deterministic
  iteration: retry re-admission → batch cut → execute → flush (``tick``,
  optionally a chosen device subset) → drain → ack release.  No real
  clocks; time is the step counter.  Every scheduler decision is
  unit-testable and interleavings are reproducible.
* **threaded** — :meth:`start` runs the same loop against real clocks (the
  backend's logger threads flush on the group-commit timer; the scheduler
  loop cuts, drains, and releases acks).  Clients block on
  :meth:`Ticket.wait`.

Admission control is lossless-or-explicit: ``submit`` either admits (the
transaction is then *guaranteed* to terminate in ``ACKED`` or ``ABORTED``)
or returns ``REJECTED`` immediately — an explicit retry-later signal.
Saturation can never silently drop an admitted request: validation losers
re-enter the queue *ahead of* new admissions and exempt from the capacity
bound (re-admitting them through the bounded queue would drop them exactly
when the system is overloaded — the failure mode the overflow test pins).
"""

from __future__ import annotations

import threading
import time
import traceback
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..db.batch import TxnSpec
from ..obs.metrics import REGISTRY
from ..trace.span import ST_ACK, ST_CUT, TICKET_DTYPE, TRACER

# ticket lifecycle ----------------------------------------------------------
QUEUED = "queued"          # admitted, waiting for a batch cut
INFLIGHT = "inflight"      # executed (pre-committed), awaiting durable ack
RETRY_WAIT = "retry_wait"  # lost validation, backing off before re-queue
ACKED = "acked"            # durably committed, ack released to the client
ABORTED = "aborted"        # explicit abort after exhausting retries
REJECTED = "rejected"      # admission refused (queue full) — never queued

_TERMINAL = (ACKED, ABORTED, REJECTED)


@dataclass
class ServeConfig:
    """Scheduler knobs.  Step-denominated fields drive stepped mode,
    second-denominated ones threaded mode; both encode the same policy."""

    max_batch: int = 256              # cut size bound
    latency_budget_steps: int = 1     # stepped: cut when head has waited this
    latency_budget_s: float = 2e-3    # threaded: group-commit window
    queue_capacity: int = 4096        # admission bound (retries exempt)
    max_unacked: Optional[int] = None  # backpressure: stall cuts above this
    max_retries: int = 3              # attempts = 1 + max_retries
    backoff_steps: int = 1            # stepped retry backoff base (doubles)
    backoff_s: float = 5e-4           # threaded retry backoff base (doubles)
    max_rounds: int = 1               # rounds inside execute_batch (cuts are
    #                                   conflict-free, so 1 is exact)
    poll_s: float = 1e-4              # threaded loop idle poll


@dataclass
class Ticket:
    """One client transaction's journey through the serving tier."""

    client_id: int
    spec_fn: Callable[[], TxnSpec]   # regenerated per attempt (fresh reads)
    status: str = QUEUED
    spec: Optional[TxnSpec] = None   # the current attempt's materialized spec
    worker_id: int = -1              # assigned at admission, stable across retries
    attempts: int = 0
    txn: object = None               # Txn or XTxn once executed
    ssn: int = -1
    ack_seq: int = -1                # global ack order (release sequence)
    # timestamps: steps in stepped mode, perf_counter seconds in threaded
    t_submit: float = 0.0
    t_ack: float = 0.0
    # perf_counter seconds in both modes, stamped only while the tracer is
    # enabled (its ticket table): the client's call to submit (admission,
    # a regenerated spec's first build included), and the end of the cut
    # that ran the current attempt
    t_submit_s: float = 0.0
    t_cut: float = 0.0
    _backoff_until: float = 0.0
    _event: Optional[threading.Event] = None

    @property
    def done(self) -> bool:
        return self.status in _TERMINAL

    def wait(self, timeout: Optional[float] = None) -> str:
        """Block until the ticket reaches a terminal status (threaded mode)."""
        if self._event is not None and not self.done:
            self._event.wait(timeout)
        return self.status

    def latency(self) -> float:
        """Commit latency: submission → ack release (steps or seconds)."""
        return self.t_ack - self.t_submit


class GroupCommitScheduler:
    """Coalesces single-transaction submissions into group-commit batches.

    ``backend`` is a :class:`~repro.serve.backend.SingleBackend` or
    :class:`~repro.serve.backend.ShardedBackend`.  Construct, then either
    drive :meth:`step` deterministically or :meth:`start` the threaded loop.
    """

    def __init__(self, backend, cfg: Optional[ServeConfig] = None):
        self.backend = backend
        self.cfg = cfg or ServeConfig()
        self._lock = threading.Lock()
        self._queue: Deque[Ticket] = deque()
        self._n_admitted_queue = 0   # admission-counted entries (≤ capacity)
        self._inflight: List[Ticket] = []
        self._waiting: List[Ticket] = []   # backoff room
        self._admit_seq = 0          # round-robin worker assignment
        self._ack_seq = 0
        self.now_step = 0            # stepped-mode clock
        self._threaded = False
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # counters / instrumentation
        self.n_submitted = 0
        self.n_admitted = 0
        self.n_rejected = 0
        self.n_acked = 0
        self.n_aborted = 0
        self.n_retries = 0
        self.n_exec_errors = 0
        # traceback of the newest executor fault (None until one happens)
        self.last_exec_error: Optional[str] = None
        self.n_cuts = 0
        self.n_cut_txns = 0
        # why each cut ended: a conflicting ticket, max_batch, empty queue
        self.n_cut_stop_conflict = 0
        self.n_cut_stop_full = 0
        self.n_cut_stop_drained = 0
        self.n_acked_read_only = 0
        self.queue_samples: List[int] = []
        self._max_queue = 0
        self._max_unacked_seen = 0

    # --- client side --------------------------------------------------------
    def _now(self) -> float:
        return time.perf_counter() if self._threaded else float(self.now_step)

    def submit(
        self,
        spec: Optional[TxnSpec] = None,
        client_id: int = 0,
        make_spec: Optional[Callable[[], TxnSpec]] = None,
    ) -> Ticket:
        """Admit one transaction (or reject it, explicitly and immediately).

        Pass a static ``spec``, or ``make_spec`` for transactions whose spec
        must be regenerated per attempt (read-modify-write: observed SSNs
        and derived values go stale when a retry is needed, so each attempt
        re-reads).  The returned ticket terminates in exactly one of
        ``ACKED`` / ``ABORTED`` / ``REJECTED``.
        """
        assert (spec is None) != (make_spec is None), (
            "pass exactly one of spec / make_spec"
        )
        t_call = time.perf_counter() if TRACER.enabled else 0.0
        fn = make_spec if make_spec is not None else (lambda: spec)
        t = Ticket(client_id=client_id, spec_fn=fn, t_submit_s=t_call)
        if self._threaded:
            t._event = threading.Event()
        with self._lock:
            self.n_submitted += 1
            if self._n_admitted_queue >= self.cfg.queue_capacity:
                t.status = REJECTED
                self.n_rejected += 1
                if REGISTRY.enabled:
                    REGISTRY.count("serve.rejected")
                if t._event is not None:
                    t._event.set()
                return t
            t.spec = t.spec_fn()
            t.attempts = 1
            t.worker_id = self._admit_seq % self.backend.n_workers
            self._admit_seq += 1
            t.t_submit = self._now()
            self._queue.append(t)
            self._n_admitted_queue += 1
            self.n_admitted += 1
            self._max_queue = max(self._max_queue, len(self._queue))
        return t

    # --- scheduler internals ------------------------------------------------
    def _requeue_ready_retries(self, now: float) -> None:
        """Move backoff-expired retries to the *front* of the queue, oldest
        first.  Retries are already admitted: they bypass the capacity bound
        and do not increment the admission count (lossless-or-explicit)."""
        if not self._waiting:
            return
        ready = [t for t in self._waiting if t._backoff_until <= now]
        if not ready:
            return
        self._waiting = [t for t in self._waiting if t._backoff_until > now]
        for t in sorted(ready, key=lambda t: t.t_submit, reverse=True):
            t.status = QUEUED
            self._queue.appendleft(t)

    def _cut_due(self, now: float) -> bool:
        if not self._queue:
            return False
        cap = self.cfg.max_unacked
        if cap is not None and len(self._inflight) >= cap:
            return False  # durability lag backpressure: stall the cutter
        if len(self._queue) >= self.cfg.max_batch:
            return True
        budget = (
            self.cfg.latency_budget_steps
            if not self._threaded
            else self.cfg.latency_budget_s
        )
        head = self._queue[0]
        wait_from = max(head.t_submit, head._backoff_until)
        return now - wait_from >= budget

    def _cut(self) -> List[Ticket]:
        """Longest conflict-free FIFO prefix of the queue, ≤ max_batch.
        Stops at the first transaction that reads or writes a key an
        earlier transaction of the cut writes (the backend's
        ``cut_claims``) — per-key order of conflicting transactions equals
        admission order, which makes the log bytes independent of where
        cuts land.  Counts why the cut ended."""
        _trace = TRACER.enabled
        if _trace:
            _t0 = TRACER.begin(ST_CUT)
        cut: List[Ticket] = []
        written: set = set()
        claims = self.backend.cut_claims
        max_batch = self.cfg.max_batch
        while True:
            if len(cut) >= max_batch:
                self.n_cut_stop_full += 1
                break
            if not self._queue:
                self.n_cut_stop_drained += 1
                break
            t = self._queue[0]
            spec = t.spec
            if any(k in written for k in spec.reads) or any(
                k in written for k, _ in spec.writes
            ):
                self.n_cut_stop_conflict += 1
                break
            written.update(claims(spec))
            self._queue.popleft()
            self._n_admitted_queue -= 1
            cut.append(t)
        if _trace and cut:
            _t1 = time.perf_counter()
            for t in cut:
                t.t_cut = _t1
            TRACER.record(
                ST_CUT, t0=_t0, t1=_t1, n_txn=len(cut), aux=len(self._queue),
            )
        elif _trace:
            TRACER.end(ST_CUT)
        if REGISTRY.enabled:
            REGISTRY.gauge_set("serve.queue_depth", float(len(self._queue)))
            REGISTRY.count("serve.cut_txns", len(cut))
        return cut

    def _execute(self, cut: List[Ticket], now: float) -> None:
        outcome = self.backend.execute(  # slow path: outside the lock
            [t.spec for t in cut],
            worker_ids=[t.worker_id for t in cut],
            max_rounds=self.cfg.max_rounds,
        )
        with self._lock:
            self.n_cuts += 1
            self.n_cut_txns += len(cut)
            # in cut order: _inflight keeps execution order for the release
            for i, txn in sorted(outcome.committed, key=lambda c: c[0]):
                t = cut[i]
                t.txn = txn
                t.ssn = self._ssn_of(txn)
                t.status = INFLIGHT
                self._inflight.append(t)
            self._max_unacked_seen = max(
                self._max_unacked_seen, len(self._inflight)
            )
            for i in outcome.aborted:
                t = cut[i]
                if t.attempts > self.cfg.max_retries:
                    t.status = ABORTED
                    self.n_aborted += 1
                    if REGISTRY.enabled:
                        REGISTRY.count("serve.aborted")
                    if t._event is not None:
                        t._event.set()
                    continue
                # retry with exponential backoff; the spec is regenerated at
                # re-queue time so observed SSNs / derived values are fresh
                self.n_retries += 1
                if REGISTRY.enabled:
                    REGISTRY.count("serve.retries")
                backoff = (
                    self.cfg.backoff_steps
                    if not self._threaded
                    else self.cfg.backoff_s
                ) * (1 << (t.attempts - 1))
                t.attempts += 1
                t.status = RETRY_WAIT
                t._backoff_until = now + backoff
                t.spec = t.spec_fn()
                self._waiting.append(t)

    def _abort_cut(self, cut: List[Ticket], exc: BaseException) -> None:
        """Backend execution failed outright (engine error, not a validation
        loss): terminate the cut's still-pending tickets explicitly.  An
        admitted transaction must never be stranded in a non-terminal state —
        an explicit ABORTED is the honest outcome when the executor itself
        fails (lossless-or-explicit, applied to infrastructure faults).
        The fault's traceback is kept for :meth:`stats`."""
        with self._lock:
            self.n_exec_errors += 1
            self.last_exec_error = "".join(traceback.format_exception(exc))
            for t in cut:
                if not t.done and t.status != INFLIGHT:
                    t.status = ABORTED
                    self.n_aborted += 1
                    if t._event is not None:
                        t._event.set()

    @staticmethod
    def _ssn_of(txn) -> int:
        ssn = getattr(txn, "ssn", None)
        if ssn is not None:
            return int(ssn)
        # XTxn: order by the highest participant SSN (its commit point —
        # the last record that must become durable)
        return max(p.ssn for p in txn.parts)

    def _release_acks(self, now: float) -> int:
        """Release every in-flight transaction whose backend drain marked it
        durably committed, in execution order: cut by cut, admission order
        within a cut.  A RAW or WAW dependency ran in an earlier cut than
        its dependent (it must be written back before the dependent can
        read or overwrite it), so within one release round it acks first;
        and the order does not depend on where cuts land.  With the tracer
        enabled the round is an ``ack`` span, and each released ticket a
        row of its ticket table (``t_ack``: the span's start, after every
        released commit was seen)."""
        # one pass: a drain on a logger thread may commit a transaction
        # at any moment, and it must land in exactly one of the two lists
        ready: List[Ticket] = []
        rest: List[Ticket] = []
        for t in self._inflight:
            (ready if t.txn.committed else rest).append(t)
        if not ready:
            return 0
        _trace = TRACER.enabled
        if _trace:
            _t0 = TRACER.begin(ST_ACK)
        self._inflight = rest
        for t in ready:
            t.status = ACKED
            t.t_ack = now
            t.ack_seq = self._ack_seq
            self._ack_seq += 1
            self.n_acked += 1
            if not t.spec.writes:
                self.n_acked_read_only += 1
            if t._event is not None:
                t._event.set()
        if _trace:
            ssns = [t.ssn for t in ready]
            TRACER.record(
                ST_ACK, txn_lo=min(ssns), txn_hi=max(ssns),
                t0=_t0, t1=time.perf_counter(), n_txn=len(ready),
            )
            self._record_tickets(ready, _t0)
        if REGISTRY.enabled:
            REGISTRY.count("serve.acked", len(ready))
            # units follow the scheduler clock: steps (stepped) or seconds
            REGISTRY.observe_many("serve.ack_latency",
                                  [t.latency() for t in ready])
        return len(ready)

    @staticmethod
    def _record_tickets(ready: List[Ticket], t_ack: float) -> None:
        """One ticket-table row per released ticket, gathered column by
        column (no per-ticket objects) and written in one call.  A
        cross-shard ``XTxn`` has no single buffer or shard: -1 for both."""
        rows = np.empty(len(ready), TICKET_DTYPE)
        rows["ssn"] = [t.ssn for t in ready]
        rows["shard"] = [getattr(t.txn, "trace_shard", -1) for t in ready]
        rows["device"] = [getattr(t.txn, "buffer_id", -1) for t in ready]
        rows["t_submit"] = [t.t_submit_s for t in ready]
        rows["t_cut"] = [t.t_cut for t in ready]
        rows["t_precommit"] = [t.txn.t_precommit for t in ready]
        rows["t_commit"] = [t.txn.t_commit for t in ready]
        rows["t_ack"] = t_ack
        TRACER.record_many(rows)

    # --- stepped mode -------------------------------------------------------
    def step(self, tick_parts: Optional[Sequence[int]] = None) -> int:
        """One deterministic scheduler iteration:

        1. re-queue backoff-expired retries (ahead of new admissions);
        2. cut a batch if due (size, latency budget, backpressure);
        3. execute it (validate → sequence → publish, pre-commit);
        4. flush — one forced logger tick per buffer in ``tick_parts``
           (default: all; tests pass subsets to randomize DSN/CSN order);
        5. drain commit queues (the Qww/Qwr watermark rule runs here);
        6. release acks for durably committed transactions, in SSN order.

        Returns the number of acks released.  Wall clocks are never read;
        ``now_step`` is the clock.
        """
        assert not self._threaded, "step() is for stepped mode"
        self.now_step += 1
        now = float(self.now_step)
        with self._lock:
            self._requeue_ready_retries(now)
            if self._cut_due(now):
                cut = self._cut()
            else:
                cut = []
            self.queue_samples.append(len(self._queue))
        if cut:
            self._execute(cut, now)
        self.backend.tick(tick_parts)
        self.backend.drain()
        with self._lock:
            return self._release_acks(now)

    def run_until_drained(
        self, max_steps: int = 10_000, tick_parts: Optional[Sequence[int]] = None
    ) -> None:
        """Step until no admitted work remains in any room (test harness)."""
        for _ in range(max_steps):
            self.step(tick_parts)
            with self._lock:
                if not (self._queue or self._inflight or self._waiting):
                    return
        raise TimeoutError(
            f"scheduler not drained after {max_steps} steps: "
            f"queue={len(self._queue)} inflight={len(self._inflight)} "
            f"waiting={len(self._waiting)}"
        )

    # --- threaded mode ------------------------------------------------------
    def start(self) -> None:
        """Run threaded: backend logger threads + one scheduler loop thread.
        ``submit`` becomes thread-safe for any number of client threads."""
        self._threaded = True
        self._stop.clear()
        self.backend.start()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="serve-scheduler"
        )
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.is_set():
            now = time.perf_counter()
            with self._lock:
                self._requeue_ready_retries(now)
                cut = self._cut() if self._cut_due(now) else []
                self.queue_samples.append(len(self._queue))
            if cut:
                try:
                    self._execute(cut, time.perf_counter())
                except Exception as e:
                    # the loop must survive an executor fault: strand no
                    # admitted ticket, keep serving the rest of the queue
                    self._abort_cut(cut, e)
            self.backend.drain()
            with self._lock:
                released = self._release_acks(time.perf_counter())
            if not cut and not released:
                time.sleep(self.cfg.poll_s)

    def stop(self, quiesce: bool = True, timeout: float = 30.0) -> None:
        """Stop the loop.  With ``quiesce`` the backend flushes and commits
        everything outstanding first and remaining acks are released —
        a clean shutdown.  ``quiesce=False`` models a crash: in-flight
        transactions stay un-acked (crash tests kill the engine right
        after)."""
        if quiesce:
            # let the live loop drain the rooms itself first
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                with self._lock:
                    idle = not (self._queue or self._inflight or self._waiting)
                if idle:
                    break
                time.sleep(self.cfg.poll_s)
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
            self._thread = None
        if quiesce:
            # loop is dead now: final flush + drain + release race-free
            self.backend.quiesce(timeout=timeout)
            self.backend.drain()
            with self._lock:
                self._release_acks(time.perf_counter())
        self.backend.stop()

    # --- stats --------------------------------------------------------------
    def stats(self) -> Dict:
        ro_commits, ro_passed = self.backend.ro_commit_counts()
        with self._lock:
            qs = self.queue_samples
            return {
                "submitted": self.n_submitted,
                "admitted": self.n_admitted,
                "rejected": self.n_rejected,
                "acked": self.n_acked,
                "aborted": self.n_aborted,
                "retries": self.n_retries,
                "exec_errors": self.n_exec_errors,
                "last_exec_error": self.last_exec_error,
                "cuts": self.n_cuts,
                "mean_cut": self.n_cut_txns / self.n_cuts if self.n_cuts else 0.0,
                "cut_stop_conflict": self.n_cut_stop_conflict,
                "cut_stop_full": self.n_cut_stop_full,
                "cut_stop_drained": self.n_cut_stop_drained,
                "acked_read_only": self.n_acked_read_only,
                "ro_commits": ro_commits,
                "ro_commits_passed": ro_passed,
                "queue_depth": len(self._queue),
                "max_queue_depth": self._max_queue,
                "mean_queue_depth": sum(qs) / len(qs) if qs else 0.0,
                "max_unacked": self._max_unacked_seen,
                "backend_queue_depths": self.backend.queue_depths(),
                "saturated": self.backend.saturated(),
            }
