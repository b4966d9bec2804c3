"""Simulated events of every sweep in the window over the wall seconds of
those sweeps, each timed from the `run_grid` call until its result is back."""


def read(run):
    return float(sum(int(sw.events.sum()) for sw in run.sweeps)) / sum(
        sw.seconds for sw in run.sweeps
    )
