"""Commit protocol (paper §4.3).

Each worker owns three private commit containers:

* ``Qww`` — transactions with *only* write operations.  Committable as soon
  as their own record is durable: ``ssn <= DSN(buffer)``.
* ``Qwr`` — transactions with a read set and a write record (potential RAW
  dependencies).  Committable when ``ssn <= CSN`` where
  ``CSN = min over buffers of DSN`` — every RAW predecessor has a smaller
  SSN, hence is durable in *whichever* buffer holds it.
* the read-only heap — transactions with no write record
  (``buffer_id == -1``).  Committable on the same rule, ``ssn <= CSN``.

Qww and Qwr are FIFO deques drained from the head, which is exact: a
worker maps to one buffer and takes each of its slots in order
(``s_i = max(base_i, s_{i-1}) + 1``), so the SSNs in either deque are
monotone and a blocked head implies a blocked tail for the same watermark.
A read-only transaction takes no slot: its SSN is only the highest version
it read, so it may be far below the RMWs queued before it.  The paper's rule
is per transaction and asks for no queue order, so read-only transactions
sit in a min-heap on ``(ssn, push sequence)`` and each commits once its own
SSN is covered, whatever its worker's Qwr still holds.
"""

from __future__ import annotations

import heapq
import threading
import time
from collections import deque
from typing import Callable, Deque, List, Optional, Sequence, Tuple

from .log_buffer import LogBuffer
from .txn import Txn


class CommitQueues:
    """Per-worker Qww / Qwr deques and read-only heap."""

    def __init__(self, worker_id: int):
        self.worker_id = worker_id
        self.qww: Deque[Txn] = deque()
        self.qwr: Deque[Txn] = deque()
        # read-only heap entries: (ssn, push sequence, Qwr pushes before
        # it, txn) — the third field tells whether Qwr's head is older
        self.ro: List[Tuple[int, int, int, Txn]] = []
        self._ro_seq = 0
        self._qwr_pushed = 0
        # read-only commits, and those made while an older Qwr entry was
        # still blocked (which a FIFO drain would have held back)
        self.ro_commits = 0
        self.ro_commits_passed = 0
        # Queues are worker-private in the paper; a lock keeps them safe if a
        # separate committer thread drains them (engine option).
        self.lock = threading.Lock()

    def _put(self, txn: Txn) -> None:
        if txn.buffer_id < 0:
            heapq.heappush(self.ro, (txn.ssn, self._ro_seq, self._qwr_pushed,
                                     txn))
            self._ro_seq += 1
        elif txn.write_only:
            self.qww.append(txn)
        else:
            self.qwr.append(txn)
            self._qwr_pushed += 1

    def push(self, txn: Txn) -> None:
        with self.lock:
            self._put(txn)

    def push_batch(self, txns: Sequence[Txn]) -> None:
        """Enqueue a batch under one lock acquisition (batched forward path).
        ``txns`` must be in SSN order per deque, which holds for any slice of
        a batch allocated through ``reserve_batch`` (SSNs are monotone in
        batch order per buffer)."""
        with self.lock:
            for txn in txns:
                self._put(txn)

    def pending(self) -> int:
        with self.lock:
            return len(self.qww) + len(self.qwr) + len(self.ro)

    def pop_read_only(self, wm: int) -> List[Txn]:
        """Pop every read-only txn with ``ssn <= wm``, least SSN first, and
        count them.  The caller holds :attr:`lock` and has drained Qwr
        against the same watermark, so a Qwr head left is blocked."""
        ro = self.ro
        out: List[Txn] = []
        # Qwr's push index of its head; with Qwr empty it is past every mark
        head = self._qwr_pushed - len(self.qwr)
        while ro and ro[0][0] <= wm:
            _, _, mark, txn = heapq.heappop(ro)
            out.append(txn)
            self.ro_commits_passed += head < mark
        self.ro_commits += len(out)
        return out


class CommitProtocol:
    """Drains commit queues against the DSN/CSN watermarks."""

    def __init__(self, buffers: List[LogBuffer], on_commit: Optional[Callable[[Txn], None]] = None):
        self.buffers = buffers
        self.on_commit = on_commit
        self._csn = 0
        self._csn_lock = threading.Lock()

    # --- Algorithm 2, AdvancingCSN ----------------------------------------
    def advance_csn(self) -> int:
        csn = min(b.dsn for b in self.buffers) if self.buffers else 0
        with self._csn_lock:
            if csn > self._csn:
                self._csn = csn
            return self._csn

    @property
    def csn(self) -> int:
        return self._csn

    # --- commit stage -------------------------------------------------------
    def committable(self, ssn: int, has_reads: bool, buffer_id: int = -1) -> bool:
        """The watermark rule, factored out of :meth:`drain` so external
        coordinators (the sharded engine's cross-shard commit, which applies
        this same test *per participant shard*) share one definition:

        * write-only  — own-buffer durability: ``ssn <= DSN(buffer_id)``;
        * with reads  — global committability: ``ssn <= CSN`` (every RAW
          predecessor has a smaller SSN, hence is durable in whichever
          buffer holds it; read-only txns pass ``buffer_id=-1``).
        """
        if has_reads:
            return ssn <= self.advance_csn()
        return ssn <= self.buffers[buffer_id].dsn

    def _commit(self, txn: Txn) -> None:
        txn.t_commit = time.perf_counter()   # stamped before it is seen
        txn.committed = True
        if self.on_commit is not None:
            self.on_commit(txn)

    def drain(self, queues: CommitQueues) -> int:
        """Commit every currently-committable transaction for one worker
        (the :meth:`committable` rule, with the CSN hoisted out of the Qwr
        and read-only loops — it only grows during a drain).  Returns the
        number committed."""
        n = 0
        with queues.lock:
            # Qww: own-buffer durability only
            while queues.qww:
                txn = queues.qww[0]
                if txn.ssn <= self.buffers[txn.buffer_id].dsn:
                    queues.qww.popleft()
                    self._commit(txn)
                    n += 1
                else:
                    break
            # Qwr: global committability (CSN)
            csn = self.advance_csn()
            while queues.qwr:
                txn = queues.qwr[0]
                if txn.ssn <= csn:
                    queues.qwr.popleft()
                    self._commit(txn)
                    n += 1
                else:
                    break
            # read-only: the same CSN, each on its own SSN
            if queues.ro:
                for txn in queues.pop_read_only(csn):
                    self._commit(txn)
                    n += 1
        return n
