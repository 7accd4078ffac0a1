"""Share of its roofline the Pallas ``occ_seg_reduce`` kernel reached in the
traced window, in percent (bytes from the recorded call shapes,
``harness/kernel_cost.py``; device time from the trace)."""


def read(run):
    return run.roofline_pct("occ_seg_reduce")
