"""Share of the scheduler's cuts that ended at a ticket touching a key
written earlier in the cut, in %: 100 × ``cut_stop_conflict`` ÷ ``cuts``
(``GroupCommitScheduler.stats()``); ``None`` where the scheduler does not
count why a cut ended."""


def read(run):
    st = run.stats
    if "cut_stop_conflict" not in st or not st["cuts"]:
        return None
    return 100.0 * st["cut_stop_conflict"] / st["cuts"]
