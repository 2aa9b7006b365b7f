"""Milliseconds of PLAN per field: the program's ``ffcz.plan`` spans
(scheduler thread) clipped to the traced window, over the fields the
service completed there (perfbench/stages.py)."""

from perfbench import stages

SOURCE = ("span", "ffcz.plan")


def read(run):
    return stages.ms_per_field(run, SOURCE[1])
