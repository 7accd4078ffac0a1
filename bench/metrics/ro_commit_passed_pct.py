"""Share of read-only commits made while an older transaction of the same
worker's Qwr was still blocked on the CSN, in %: 100 × ``ro_commits_passed``
÷ ``ro_commits`` (``GroupCommitScheduler.stats()``); the commits a FIFO
drain of Qwr would have held back.  ``None`` where the program does not
count them, or no read-only transaction committed."""


def read(run):
    st = run.stats
    if "ro_commits_passed" not in st or not st.get("ro_commits"):
        return None
    return 100.0 * st["ro_commits_passed"] / st["ro_commits"]
