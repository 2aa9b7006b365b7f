"""Share of the roofline of one whole-field POCS iteration: the least time
the chip could take for it (perfbench/workcount.py, the peak table) over the
measured device time per iteration (metrics/pocs_ms_per_iter.py)."""

from pathlib import Path

from perfbench import workcount
from perfbench.harness import load_module


def read(run):
    per_iter = load_module(Path(__file__).with_name("pocs_ms_per_iter.py"))
    t = per_iter.seconds_per_iteration(run)
    if not t:
        return None
    shape = (int(run.cfg["edge"]),) * 3
    roof = workcount.roofline_seconds(workcount.pocs_iteration(shape), run.peaks())
    return 100.0 * roof["seconds"] / t
