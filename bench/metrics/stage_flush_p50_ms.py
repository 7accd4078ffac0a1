"""Median time an acknowledged ticket's record waited for the
group-commit flush that made it durable, in ms: ``durable − t_precommit``,
where ``durable`` is the end of the first flush span on its device whose
DSN reaches its SSN (``TraceDump.durable_at``; read-only tickets: 0)."""

import numpy as np


def read(run):
    stages = getattr(run.spans, "ticket_stages", lambda: None)()
    if stages is None:
        return None
    x = stages["flush"]
    x = x[np.isfinite(x)]
    return float(np.median(x)) * 1e3 if len(x) else None
