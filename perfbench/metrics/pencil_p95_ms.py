"""95th percentile, over every request due in the window, of the time from
when it was due to its response; a request that never came counts as
waiting until the harness gave up on it."""

import numpy as np


def read(run):
    w = run.window
    if run.cfg["kind"] != "pencils" or run.traffic.get("loop") != "open" or not w.requests:
        return None
    give_up = w.end + run.grace_s
    lat = [(r.done if r.done is not None else give_up) - r.due for r in w.requests]
    return float(np.percentile(lat, 95)) * 1e3
