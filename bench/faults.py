"""Faults planted under the served path, to show the comparison fails.

Each fault wraps a ``SingleBackend`` and breaks one thing the configuration
guarantees; ``CellRun(..., wrap=FAULTS[name])`` puts it between the
scheduler and the backend.  ``early_ack`` is the control: it acknowledges a
transaction as soon as it is executed, before its record is durable, which
breaks "ack = durable and committable".  The others are the faults of the
served path that the benchmark's tests plant one at a time:

* ``state_unchanged`` — the log devices take every flush and keep nothing;
* ``ack_at_write_start`` — each log device answers a write as it starts and
  performs it only when the next write comes (a volatile write cache), so a
  record counts as durable, and its client is acknowledged, before the write
  that holds it has returned;
* ``half_batch``      — each cut executes its first half only and answers
  the rest as aborted;
* ``answer_altered``  — the SSN of a cut's first winner is changed where it
  is produced.

One chip holds the whole deployment, so there is no exchange between chips
to leave out.
"""

from __future__ import annotations


class _Wrap:
    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)


class EarlyAck(_Wrap):
    def execute(self, specs, worker_ids=None, max_rounds=1):
        out = self.inner.execute(specs, worker_ids=worker_ids,
                                 max_rounds=max_rounds)
        for _, t in out.committed:
            t.committed = True
        return out


class StateUnchanged(_Wrap):
    def __init__(self, inner):
        super().__init__(inner)
        for d in inner.engine.devices:
            d.write = lambda data: None


class AckAtWriteStart(_Wrap):
    def __init__(self, inner):
        super().__init__(inner)
        for d in inner.engine.devices:
            write, held = d.write, []

            def cached(data, _write=write, _held=held):
                if _held:
                    _write(_held.pop())
                _held.append(data)

            d.write = cached


class HalfBatch(_Wrap):
    def execute(self, specs, worker_ids=None, max_rounds=1):
        half = (len(specs) + 1) // 2
        out = self.inner.execute(
            specs[:half], worker_ids=None if worker_ids is None
            else worker_ids[:half], max_rounds=max_rounds)
        out.aborted = list(out.aborted) + list(range(half, len(specs)))
        return out


class _Altered:
    """A committed transaction as the client is told of it, one SSN off."""

    def __init__(self, txn):
        self.txn = txn
        self.ssn = txn.ssn + 1
        self.tid = txn.tid

    @property
    def committed(self) -> bool:
        return self.txn.committed


class AnswerAltered(_Wrap):
    def execute(self, specs, worker_ids=None, max_rounds=1):
        out = self.inner.execute(specs, worker_ids=worker_ids,
                                 max_rounds=max_rounds)
        if out.committed:
            i, txn = out.committed[0]
            out.committed[0] = (i, _Altered(txn))
        return out


FAULTS = {
    "early_ack": EarlyAck,
    "state_unchanged": StateUnchanged,
    "ack_at_write_start": AckAtWriteStart,
    "half_batch": HalfBatch,
    "answer_altered": AnswerAltered,
}
