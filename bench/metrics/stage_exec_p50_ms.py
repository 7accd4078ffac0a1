"""Median time an acknowledged ticket spent in the executor, in ms: from
the end of its cut to its record being buffered, ``t_precommit − t_cut``
of the traced window's ticket table (``TraceDump.ticket_stages``)."""

import numpy as np


def read(run):
    stages = getattr(run.spans, "ticket_stages", lambda: None)()
    if stages is None:
        return None
    x = stages["exec"]
    x = x[np.isfinite(x)]
    return float(np.median(x)) * 1e3 if len(x) else None
