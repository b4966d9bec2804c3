"""The trips of the slowest of the units that run side by side over their
mean, summed over the window's sweeps: worlds under vmap (lockstep lanes),
devices under mesh; one device running its worlds in turn (map) reads 1."""

from bench.metrics import lane_trips


def read(run):
    lanes = [lane_trips(sw, run.strategy) for sw in run.sweeps]
    return float(sum(x.max() for x in lanes)) / float(sum(x.mean() for x in lanes))
