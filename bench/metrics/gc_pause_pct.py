"""Share of the measured window the interpreter spent in generation-1 and
-2 collections, in percent: the ``gc`` spans of the traced window
(``repro.trace``), clipped to the window, over its length."""

import numpy as np
from repro.trace.span import STAGE_NAMES


def read(run):
    s = run.spans
    if s is None or "gc" not in STAGE_NAMES:
        return None
    rows = s.stage == STAGE_NAMES.index("gc")
    if not rows.any():
        return None
    lo, hi = run.window.t0, run.window.t_end
    paused = np.clip(s.t1[rows], lo, hi) - np.clip(s.t0[rows], lo, hi)
    return 100.0 * float(paused.sum()) / (hi - lo)
