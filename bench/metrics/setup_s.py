"""Seconds from the process's start until the window opens: imports, device
start, banks, compile or cache load, warm-up."""


def read(run):
    return run.setup_s
