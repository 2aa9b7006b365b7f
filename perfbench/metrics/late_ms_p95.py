"""95th percentile of how late the load generator submitted the window's
requests (submit time minus due time): a starved generator shows here."""

import numpy as np


def read(run):
    w = run.window
    late = [r.submit - r.due for r in w.requests if r.due is not None and r.submit is not None]
    return float(np.percentile(late, 95)) * 1e3 if late else None
