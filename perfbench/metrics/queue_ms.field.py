"""Mean milliseconds a field waited from admission to the start of its
FRONT (``RequestStats.queue_s``, on the service clock), over the window's
completed fields submitted after it opened (``stages.admitted_in_window``)."""

from perfbench import stages

SOURCE = ("stat", "queue_s")


def read(run):
    return stages.mean_wait_ms(run, SOURCE[1])
