"""The traffic generator: every seed offers the same work in another order."""

from collections import Counter

import numpy as np
import pytest

from perfbench import loadgen

STEADY = {"loop": "open", "arrivals": "poisson", "rate_per_s": 50.0,
          "sizes": {"1": 0.25, "2": 0.25, "3": 0.25, "4": 0.25}}


def test_seeds_share_the_multiset_of_gaps_and_sizes():
    a = loadgen.schedule(STEADY, 1, 20.0)
    b = loadgen.schedule(STEADY, 2**31 + 12345, 20.0)
    assert [x.due_s for x in a] != [x.due_s for x in b]
    assert len(a) == len(b) == 1000
    assert Counter(x.size for x in a) == Counter(x.size for x in b) == Counter(
        {"1": 250, "2": 250, "3": 250, "4": 250})
    ga, gb = np.diff([x.due_s for x in a]), np.diff([x.due_s for x in b])
    assert np.mean(ga) == pytest.approx(1 / 50.0, rel=0.05)
    # each schedule leaves out one gap (its first): all the others are shared
    only_a = Counter(np.round(ga, 9)) - Counter(np.round(gb, 9))
    assert sum(only_a.values()) <= 1


def test_the_same_seed_gives_the_same_schedule():
    assert loadgen.schedule(STEADY, 7, 5.0) == loadgen.schedule(STEADY, 7, 5.0)


def test_onoff_bursts_keep_the_mean_rate_and_stay_silent_when_off():
    burst = dict(STEADY, arrivals="onoff", on_s=1.0, off_s=3.0)
    s = loadgen.schedule(burst, 3, 40.0)
    assert len(s) == pytest.approx(50.0 * 40.0, rel=0.02)
    assert all((x.due_s % 4.0) < 1.0 + 1e-9 for x in s)


def test_a_closed_loop_has_no_schedule():
    with pytest.raises(ValueError):
        loadgen.schedule({"loop": "closed", "in_flight": 3}, 1, 5.0)
