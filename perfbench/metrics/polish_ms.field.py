"""Milliseconds of the host float64 polish (rebuild, round trips, recount)
per field: the program's ``ffcz.polish`` spans (encode worker) clipped to
the traced window, over the fields the service completed there."""

from perfbench import stages

SOURCE = ("span", "ffcz.polish")


def read(run):
    return stages.ms_per_field(run, SOURCE[1])
