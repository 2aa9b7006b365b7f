"""The whole-field cell end to end at a tiny size on the CPU, and its data."""

import numpy as np
import pytest

from perfbench import harness
from perfbench.tests import tiny


@pytest.mark.parametrize("trace", [False, True])
def test_cell_prints_a_well_formed_correct_line(trace):
    run, line = tiny.run(tiny.NYX, trace=trace)
    tiny.assert_well_formed(line, run)
    assert line["correct"] is True and line["failed"] == 0
    assert run.window.compiles == 0
    assert line["attempted"] == len(run.window.requests) >= 2


def test_every_field_is_the_same_values_in_another_arrangement():
    gen = harness.load_module(harness.HERE / "configs" / "nyx.py")
    cfg = {"edge": 16, "alpha": 2.0, "sigma": 1.5}
    seed = 2**31 + 5
    a, b, c = gen.make(cfg, "1", 0, seed), gen.make(cfg, "1", 1, seed), gen.make(cfg, "1", 0, 7)
    assert a.dtype == np.float32 and a.shape == (16, 16, 16)
    assert np.array_equal(a, gen.make(cfg, "1", 0, seed))
    for other in (b, c):
        assert not np.array_equal(a, other)
        assert np.array_equal(np.sort(a, axis=None), np.sort(other, axis=None))
    # lognormal: positive, median 1, the range set by the normal's extreme quantiles
    assert a.min() > 0 and np.median(a) == pytest.approx(1.0, rel=1e-3)
