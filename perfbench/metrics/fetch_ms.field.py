"""Milliseconds of copying the edit state to the host in float64 per field:
the program's ``ffcz.fetch`` spans (encode worker) clipped to the traced
window, over the fields the service completed there."""

from perfbench import stages

SOURCE = ("span", "ffcz.fetch")


def read(run):
    return stages.ms_per_field(run, SOURCE[1])
