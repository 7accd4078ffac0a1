"""Bytes and operations each OLTP kernel call needs, counted from its shapes.

Only what the computation cannot do without is counted: reading its inputs
once and writing its outputs once, and one combining operation per input
lane.  Scratch state a particular implementation keeps (the fused round's
``cap``-wide first-writer table, the one-hot kernel's ``lanes x slots``
compares) is not counted, so a later implementation that drops it is not
credited with more than 100% of the roofline.

Arrays are int32 (4 bytes) on the device; boolean outputs are one byte.
"""

from __future__ import annotations

from typing import Dict, Tuple

I32 = 4


def occ_seg_reduce(shapes: Tuple, kw: Dict) -> Tuple[float, float]:
    """Segmented min/max: ``key_id[N], val[N] -> out[n_slots]``."""
    (n,) = shapes[0]
    slots = int(kw["n_slots"])
    return float(2 * n * I32 + slots * I32), float(n)


def fused_validate_sequence(shapes: Tuple, kw: Dict) -> Tuple[float, float]:
    """``acc[6, n_txn*k], a_len[n_txn] -> survive[n_txn] (bool),
    bases[n_txn]``; per lane: first-writer min, three validity compares,
    the survive and the base-SSN reductions."""
    rows, lanes = shapes[0]
    (n_txn,) = shapes[1]
    nbytes = rows * lanes * I32 + n_txn * I32 + n_txn * (1 + I32)
    return float(nbytes), float(6 * lanes)


def fused_replay_scan(shapes: Tuple, kw: Dict) -> Tuple[float, float]:
    """``scan[3, N] -> (ssn, pos)[n_slots]``: one combine per lane."""
    rows, lanes = shapes[0]
    slots = int(kw["n_slots"])
    return float(rows * lanes * I32 + 2 * slots * I32), float(lanes)


COST = {
    "occ_seg_reduce": occ_seg_reduce,
    "fused_validate_sequence": fused_validate_sequence,
    "fused_replay_scan": fused_replay_scan,
}


def least_seconds(kernel: str, shapes: Tuple, kw: Dict, peaks: Dict
                  ) -> Tuple[float, str]:
    """The least time the chip could take for one call, and which bound
    sets it: ``max(bytes / HBM bandwidth, ops / integer peak)``.  The chip's
    int32 vector rate is not published; its int8 peak is the highest integer
    rate it has, so the ops bound is never longer than the true one."""
    nbytes, ops = COST[kernel](shapes, kw)
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_ops = ops / peaks["int8_ops_per_s"]
    return (t_mem, "bytes") if t_mem >= t_ops else (t_ops, "ops")
