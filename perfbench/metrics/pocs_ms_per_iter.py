"""Device milliseconds per POCS iteration: the device time of the jitted
whole-field loop (XLA module ``jit__alternating_projection``) in its
executions wholly inside the traced window, over their number times the
mean iterations of the fields completed in the window."""

MODULE = "alternating_projection"


def seconds_per_iteration(run):
    s = run.summary
    done = run.window.completed
    if s is None or run.cfg["kind"] != "field" or not done:
        return None
    calls = sum(v for m, v in s.module_calls.items() if MODULE in m)
    busy = sum(v for m, v in s.module_whole_s.items() if MODULE in m)
    if not calls:
        return None
    iters = sum(r.resp.stats.iterations for r in done) / len(done)
    return busy / (calls * iters)


def read(run):
    t = seconds_per_iteration(run)
    return None if t is None else t * 1e3
