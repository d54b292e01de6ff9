"""The comparison that decides `correct`, shown to fail: the cell's
control and each fault its timed path can have, planted underneath a
rehearsal run at test size, turns `correct` false; the unbroken run is
correct."""

import pytest

from perfbench_tiny import run_cell
from perfbench.harness import faults

READ = "hotdoc100k.catchup"
# With half the records left out the connected summarizer meets an insert
# past its text's end and the run ends with no result line, which the
# driver counts as a failed run.
ENDS_RUN = {"read.half_batch"}


def test_unbroken_run_is_correct(tiny_root):
    line = run_cell(tiny_root, READ)
    assert line["correct"] is True, line["checks"]
    assert line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"
    assert line["checks"]["summaries_checked"]["value"] >= 1


@pytest.mark.parametrize("fault", sorted(faults.INSTALL))
def test_broken_run_is_not_correct(tiny_root, fault):
    undo = faults.INSTALL[fault]()
    try:
        line = run_cell(tiny_root, READ, must_report=False)
    finally:
        undo()
    if fault in ENDS_RUN:
        assert line is None
        return
    assert line["correct"] is False, (fault, line["checks"])
