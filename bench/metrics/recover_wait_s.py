"""Time the pallas recovery's fused pipeline spent blocked on the next
decoded tile, in seconds: host decode on its critical path
(``RecoveryReport.fused_wait_s``)."""


def read(run):
    rep = run.recovery
    if rep is None or not rep.fused:
        return None
    return getattr(rep, "fused_wait_s", None)
