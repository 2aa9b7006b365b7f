"""Seconds from the start of the process to the opening of the window:
imports, data, service, warm-up and every compile."""


def read(run):
    return run.setup_s
