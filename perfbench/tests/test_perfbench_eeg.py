"""The pencil cell end to end at a tiny size on the CPU."""

import pytest

from perfbench.tests import tiny


@pytest.mark.parametrize("trace", [False, True])
def test_cell_prints_a_well_formed_correct_line(trace):
    run, line = tiny.run(tiny.EEG, trace=trace)
    tiny.assert_well_formed(line, run)
    assert line["correct"] is True and line["failed"] == 0
    assert run.window.compiles == 0
    assert line["attempted"] == len(run.window.requests) >= 10
