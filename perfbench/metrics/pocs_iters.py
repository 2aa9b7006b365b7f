"""Mean POCS iterations of the fields completed in the window
(RequestStats.iterations)."""


def read(run):
    done = run.window.completed
    if run.cfg["kind"] != "field" or not done:
        return None
    return sum(r.resp.stats.iterations for r in done) / len(done)
