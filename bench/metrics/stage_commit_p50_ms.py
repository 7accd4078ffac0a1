"""Median time from an acknowledged ticket's record being durable to its
ack, in ms: ``t_ack − durable``, the commit rule's wait (its own
buffer's DSN, or the CSN over every buffer for a ticket with reads), the
drain and the release (``TraceDump.ticket_stages``)."""

import numpy as np


def read(run):
    stages = getattr(run.spans, "ticket_stages", lambda: None)()
    if stages is None:
        return None
    x = stages["commit"]
    x = x[np.isfinite(x)]
    return float(np.median(x)) * 1e3 if len(x) else None
