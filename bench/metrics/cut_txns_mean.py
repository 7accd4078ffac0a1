"""Mean transactions per scheduler cut over the window
(``GroupCommitScheduler.stats()["mean_cut"]``)."""


def read(run):
    return run.stats["mean_cut"] if run.stats["cuts"] else None
