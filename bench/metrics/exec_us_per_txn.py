"""Executor host time per committed transaction, in microseconds: the
``BatchOCC`` validate, sequence, encode and write-back spans of the window
(``repro.trace``), over the winners its sequence spans count."""

from repro.trace.span import ST_ENCODE, ST_SEQUENCE, ST_VALIDATE, ST_WRITEBACK


def read(run):
    s = run.spans
    if s is None or not s.n:
        return None
    stages = (ST_VALIDATE, ST_SEQUENCE, ST_ENCODE, ST_WRITEBACK)
    busy = sum(float((s.t1 - s.t0)[s.stage == st].sum()) for st in stages)
    won = int(s.n_txn[s.stage == ST_SEQUENCE].sum())
    return busy / won * 1e6 if won else None
