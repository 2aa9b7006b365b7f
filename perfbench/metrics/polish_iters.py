"""Float64 round trips per polish: the program's ``ffcz.polish.round``
spans that overlap the traced window over its ``ffcz.polish`` spans that
overlap it (0.0 when every polish exits before its first round)."""

SOURCE = ("span", "ffcz.polish")


def read(run):
    if run.program is None or run.cfg["kind"] != "field":
        return None
    return run.program.rounds_per_polish()
