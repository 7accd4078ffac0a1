"""Time the pallas recovery's fused pipeline spent merging each tile's
winners into the recovered image, in seconds
(``RecoveryReport.fused_apply_s``)."""


def read(run):
    rep = run.recovery
    if rep is None or not rep.fused:
        return None
    return getattr(rep, "fused_apply_s", None)
