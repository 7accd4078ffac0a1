"""OLTP serving tier: open-loop group commit over the Poplar engines.

* :class:`~repro.serve.scheduler.GroupCommitScheduler` — coalesces
  single-transaction client submissions into batched executor calls under a
  latency budget, with admission control, retry-with-backoff, and acks
  gated on the Qww/Qwr committable() rule (ack = durable ∧ committable).
* :class:`~repro.serve.backend.SingleBackend` /
  :class:`~repro.serve.backend.ShardedBackend` — the executor stacks the
  scheduler drives (BatchOCC vectorized/pallas, ScalarBatchOCC, or a
  ShardedEngine).
* :class:`~repro.serve.driver.OpenLoopDriver` — Poisson open-loop client
  sessions with coordinated-omission-safe latency accounting.
"""

from .backend import ExecOutcome, ShardedBackend, SingleBackend
from .driver import DriverReport, OpenLoopDriver, run_stepped_schedule
from .scheduler import (
    ABORTED,
    ACKED,
    INFLIGHT,
    QUEUED,
    REJECTED,
    RETRY_WAIT,
    GroupCommitScheduler,
    ServeConfig,
    Ticket,
)

__all__ = [
    "GroupCommitScheduler",
    "ServeConfig",
    "Ticket",
    "SingleBackend",
    "ShardedBackend",
    "ExecOutcome",
    "OpenLoopDriver",
    "DriverReport",
    "run_stepped_schedule",
    "QUEUED",
    "INFLIGHT",
    "RETRY_WAIT",
    "ACKED",
    "ABORTED",
    "REJECTED",
]
