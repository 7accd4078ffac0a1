"""Median time from an acknowledged ticket's call to ``submit`` to the end
of the cut that ran its committing attempt, in ms: admission and the wait
in the scheduler's queue, ``t_cut − t_submit`` of the traced window's
ticket table (``TraceDump.ticket_stages``)."""

import numpy as np


def read(run):
    stages = getattr(run.spans, "ticket_stages", lambda: None)()
    if stages is None:
        return None
    x = stages["queue"]
    x = x[np.isfinite(x)]
    return float(np.median(x)) * 1e3 if len(x) else None
