"""The comparison fails when the served path is broken underneath it: the
control (acknowledging before the record is durable) and each fault the
cells can have, planted between the scheduler and the backend.  One cell
per file, so the two run on separate test workers."""

import pytest

import _tiny
from faults import FAULTS


CELL = "tpcc_lat"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_comes_out_not_correct(fault, cell=CELL):
    result, lines = _tiny.run(cell, wrap=FAULTS[fault], seconds=1.0)
    assert result["correct"] is False, (fault, result["checks"])
    assert any(not line.endswith(": 0 (limit 0)") for line in lines)


def test_same_setup_without_a_fault_is_correct(cell=CELL):
    result, _ = _tiny.run(cell, seconds=1.0)
    assert result["correct"], result["checks"]


class WritebackAltered:
    """After each cut, flip the last (filler) byte of the first winner's
    first written row in the table, its SSN kept: later reads see a value
    no write produced, while the log and every consistency condition stay
    as they were."""

    def __init__(self, inner):
        self.inner = inner

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def execute(self, specs, worker_ids=None, max_rounds=1):
        out = self.inner.execute(specs, worker_ids=worker_ids,
                                 max_rounds=max_rounds)
        if out.committed:
            table = self.inner.table
            key = specs[out.committed[0][0]].writes[0][0]
            with table.mutex:
                row = table.row_of(key)
                v = table.values[row]
                table.values[row] = v[:-1] + bytes([v[-1] ^ 1])
        return out


def test_a_wrong_value_under_the_right_ssn_is_caught(cell=CELL):
    result, _ = _tiny.run(cell, wrap=WritebackAltered, seconds=1.0)
    assert result["correct"] is False
    assert result["checks"]["read_value_mismatch"]["value"] > 0
    assert result["checks"]["consistency_violations"]["value"] == 0
