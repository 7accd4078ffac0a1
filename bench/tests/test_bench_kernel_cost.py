"""Bytes and operations of the three kernels against hand counts."""

import pytest

import _paths  # noqa: F401
from harness import kernel_cost

PEAKS = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}


def test_occ_seg_reduce():
    # 256 int32 keys + 256 int32 values in, 64 int32 slots out
    assert kernel_cost.occ_seg_reduce(((256,), (256,)), {"n_slots": 64}) == (
        256 * 4 * 2 + 64 * 4, 256)


def test_fused_validate_sequence():
    # (6, 4096) int32 lanes + 4096 int32 lengths in; 4096 bools and 4096
    # int32 bases out; 6 operations per lane
    nbytes, ops = kernel_cost.fused_validate_sequence(
        ((6, 4096), (4096,)), {"n_txn": 4096, "k": 1, "cap": 1 << 24})
    assert nbytes == 6 * 4096 * 4 + 4096 * 4 + 4096 * 1 + 4096 * 4
    assert ops == 6 * 4096


def test_fused_replay_scan():
    # (3, 2048) int32 lanes in; (ssn, pos) int32 per slot of 4096 out
    assert kernel_cost.fused_replay_scan(((3, 2048),), {"n_slots": 4096}) == (
        3 * 2048 * 4 + 2 * 4096 * 4, 2048)


@pytest.mark.parametrize("kernel,shapes,kw", [
    ("occ_seg_reduce", ((256,), (256,)), {"n_slots": 256}),
    ("fused_validate_sequence", ((6, 4096), (4096,)), {"n_txn": 4096}),
    ("fused_replay_scan", ((3, 2048),), {"n_slots": 4096})])
def test_memory_bound_on_the_v5e(kernel, shapes, kw):
    t, bound = kernel_cost.least_seconds(kernel, shapes, kw, PEAKS)
    nbytes, _ = kernel_cost.COST[kernel](shapes, kw)
    assert bound == "bytes"
    assert t == pytest.approx(nbytes / 819e9)
