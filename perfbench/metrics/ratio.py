"""Input bytes over blob bytes, summed over the compressions completed in
the window: what a faster path must not give up."""


def read(run):
    done = run.window.completed
    out = sum(len(r.resp.payload) for r in done)
    return sum(run.kind.in_bytes(r) for r in done) / out if out else None
