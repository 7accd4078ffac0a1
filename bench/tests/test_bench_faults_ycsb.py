"""The comparison fails when the served path is broken underneath it: the
control (acknowledging before the record is durable) and each fault the
cells can have, planted between the scheduler and the backend.  One cell
per file, so the two run on separate test workers."""

import pytest

import _tiny
from faults import FAULTS


CELL = "ycsb_zipf_lat"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_comes_out_not_correct(fault, cell=CELL):
    result, lines = _tiny.run(cell, wrap=FAULTS[fault], seconds=1.0)
    assert result["correct"] is False, (fault, result["checks"])
    assert any(not line.endswith(": 0 (limit 0)") for line in lines)


def test_same_setup_without_a_fault_is_correct(cell=CELL):
    result, _ = _tiny.run(cell, seconds=1.0)
    assert result["correct"], result["checks"]


def test_control_fails_the_ack_durability_check(cell=CELL):
    result, _ = _tiny.run(cell, wrap=FAULTS["early_ack"], seconds=1.0)
    assert result["checks"]["acked_before_durable"]["value"] > 0


def test_acking_at_write_start_fails_the_ack_durability_check(cell=CELL):
    result, _ = _tiny.run(cell, wrap=FAULTS["ack_at_write_start"],
                          seconds=1.0)
    assert result["checks"]["acked_before_durable"]["value"] > 0
