"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's operation intervals) / (window)."""


def read(run):
    if run.summary is None or run.cfg["kind"] != "field":
        return None
    return 100.0 * run.summary.idle_share
