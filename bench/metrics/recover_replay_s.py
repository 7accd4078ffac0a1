"""``RecoveryReport.replay_s`` of the run's ``recover(mode="pallas")``: on
the fused path decode and replay are pipelined and all of it is here."""


def read(run):
    rep = run.recovery
    return rep.replay_s if rep is not None and rep.replay_s > 0 else None
