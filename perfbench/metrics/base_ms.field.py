"""Milliseconds of the base codec (compress, decompress and its error) per
field: the program's ``ffcz.base`` spans (scheduler thread) clipped to the
traced window, over the fields the service completed there."""

from perfbench import stages

SOURCE = ("span", "ffcz.base")


def read(run):
    return stages.ms_per_field(run, SOURCE[1])
