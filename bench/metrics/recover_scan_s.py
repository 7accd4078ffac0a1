"""Time the pallas recovery's fused pipeline spent finding each tile's
winners, in seconds: the compiled ``fused_replay_scan`` with its
transfers (a host reduction for tiles under 1,024 lanes)
(``RecoveryReport.fused_scan_s``)."""


def read(run):
    rep = run.recovery
    if rep is None or not rep.fused:
        return None
    return getattr(rep, "fused_scan_s", None)
