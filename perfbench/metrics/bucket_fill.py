"""Pencil requests the service completed in the window per unit of work
that step() retired there: how many requests one fused device call held."""


def read(run):
    w = run.window
    if run.cfg["kind"] != "pencils" or not w.units:
        return None
    return w.counters["completed"] / w.units
