"""99th percentile of how late the open-loop client submitted, in ms:
actual submit time minus scheduled arrival, over the window's arrivals."""

import numpy as np


def read(run):
    w = run.window
    if not w.open_loop or not len(w.submitted):
        return None
    return float(np.percentile((w.submitted - w.scheduled) * 1e3, 99))
