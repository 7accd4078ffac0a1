"""Validation retries the scheduler made per acknowledged transaction over
the window (``GroupCommitScheduler.stats()``)."""


def read(run):
    acked = run.stats["acked"]
    return run.stats["retries"] / acked if acked else None
