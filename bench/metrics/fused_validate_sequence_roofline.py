"""Share of its roofline the fused ``fused_validate_sequence`` round
reached in the traced window, in percent."""


def read(run):
    return run.roofline_pct("fused_validate_sequence")
