"""The trace reduction on a small trace recorded on a TPU v5e
(``bench/record_trace.py``: three rounds of occ_seg_reduce min and max,
fused_validate_sequence at a 16M-lane table, fused_replay_scan)."""

import os

import pytest

import _paths  # noqa: F401
from harness import trace_reduce

TRACE = os.path.join(os.path.dirname(__file__), "data", "kernels.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return trace_reduce.load(TRACE)


def test_one_chip_and_the_window_annotation(trace):
    assert len(trace.chips) == 1
    assert trace.window == (49007657.0, 71492345.0)
    assert trace.window_ns == pytest.approx(22484688.0)


@pytest.mark.parametrize("kernel,runs", [
    ("occ_seg_reduce", 6), ("fused_validate_sequence", 3),
    ("fused_replay_scan", 3)])
def test_kernel_runs_counted_over_the_whole_trace(trace, kernel, runs):
    n, ns = trace.kernel(kernel)
    assert n == runs
    assert ns > 0


def test_kernel_time_is_the_sum_of_its_module_events(trace):
    mods = [(s, e) for m, s, e in trace.chips[0].modules
            if m == "fused_validate_sequence"]
    assert trace.kernel("fused_validate_sequence")[1] == pytest.approx(
        sum(e - s for s, e in mods))


def test_busy_is_the_union_of_ops_inside_the_window(trace):
    lo, hi = trace.window
    iv = sorted((max(s, lo), min(e, hi)) for s, e in trace.chips[0].ops
                if min(e, hi) > max(s, lo))
    covered, end = 0.0, lo
    for s, e in iv:                       # a second, naive union
        if e > end:
            covered += e - max(s, end)
            end = e
    assert trace.busy_ns() == pytest.approx(covered)
    assert 0 < trace.busy_ns() < trace.window_ns


def test_idle_gaps_and_busy_tile_the_window(trace):
    gaps = trace.idle_gaps(k=10_000)
    assert sum(e - s for s, e in gaps) + trace.busy_ns() == pytest.approx(
        trace.window_ns)
    top = trace.idle_gaps(k=3)
    assert [e - s for s, e in top] == sorted(
        (e - s for s, e in gaps), reverse=True)[:3]


def test_module_base_strips_jit_and_program_id():
    assert trace_reduce.module_base(
        "jit_occ_seg_reduce(1522063152254680248)") == "occ_seg_reduce"
    assert trace_reduce.module_base("fusion.3") == "fusion.3"
