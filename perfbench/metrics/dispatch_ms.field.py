"""Milliseconds of the POCS dispatch (copy to the device and enqueue) per
field: the program's ``ffcz.dispatch`` spans clipped to the traced window,
over the fields the service completed there."""

from perfbench import stages


def read(run):
    return stages.ms_per_field(run, "ffcz.dispatch")
