"""The service's front_s stage clock over the window, per request it
completed in the window (FFCzService.timers and counters, read from outside)."""


def read(run):
    w = run.window
    if run.cfg["kind"] != "pencils" or not w.counters["completed"]:
        return None
    return w.timers["front_s"] / w.counters["completed"] * 1e3
