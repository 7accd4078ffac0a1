"""Bytes of whole frames on the log devices after the crash, over the value
bytes those frames carry (framing, keys and heartbeats are the overhead)."""


def read(run):
    v = run.verdict
    return v.log_bytes / v.user_bytes if v.user_bytes else None
