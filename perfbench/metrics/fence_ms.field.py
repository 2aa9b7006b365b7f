"""Milliseconds the encode worker waited on the device fence per field: the
program's ``ffcz.fence`` spans clipped to the traced window, over the fields
the service completed there."""

from perfbench import stages

SOURCE = ("span", "ffcz.fence")


def read(run):
    return stages.ms_per_field(run, SOURCE[1])
