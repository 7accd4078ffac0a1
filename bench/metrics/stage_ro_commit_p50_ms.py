"""Median time from a read-only ticket's pre-commit to its ack, in ms:
``t_ack − t_precommit`` over the ticket rows with no record (``device``
< 0) on one shard (``shard`` >= 0), the commit stage of
``TraceDump.ticket_stages`` for those rows: the wait for the CSN to reach
the version it read, the drain and the release."""

import numpy as np


def read(run):
    tk = getattr(run.spans, "tickets", None)
    if tk is None:
        return None
    read_only = (tk.device < 0) & (tk.shard >= 0)
    x = run.spans.ticket_stages()["commit"][read_only]
    x = x[np.isfinite(x)]
    return float(np.median(x)) * 1e3 if len(x) else None
