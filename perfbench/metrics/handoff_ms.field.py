"""Mean milliseconds a field waited from the end of its FRONT to the start
of its BACK on the encode worker (``RequestStats.handoff_s``), over the
window's completed fields submitted after it opened
(``stages.admitted_in_window``)."""

from perfbench import stages

SOURCE = ("stat", "handoff_s")


def read(run):
    return stages.mean_wait_ms(run, SOURCE[1])
