"""Decimal GB of float32 field input whose compression completed in the
window, over the window's length (a closed loop's window is whole requests)."""


def read(run):
    if run.cfg["kind"] != "field":
        return None
    w = run.window
    return sum(run.kind.in_bytes(r) for r in w.completed) / w.seconds / 1e9
