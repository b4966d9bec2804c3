"""One reader per metric: ``<name>.py`` defines ``read(run)``, which returns
the metric's value from a `bench.harness.Run`, or None where the run holds
nothing for it to read. `bench.harness.read_metric` finds a reader by the
metric's name in BENCHMARK.json."""

import numpy as np


def critical_trips(sweep, strategy: str) -> dict:
    """Device id -> the while-loop trips that bound that device's time in
    one sweep: the slowest lane under ``vmap`` (lanes run in lockstep), the
    sum of its worlds under ``map`` and ``mesh`` (one world after another)."""
    out: dict = {}
    for dev, trips in zip(sweep.lane_device, sweep.trips):
        t = int(trips)
        out[dev] = max(out.get(dev, 0), t) if strategy == "vmap" else out.get(dev, 0) + t
    return out


def lane_trips(sweep, strategy: str) -> np.ndarray:
    """Trips of each unit that runs side by side with the others: each world
    under ``vmap``, each device's worlds together otherwise."""
    if strategy == "vmap":
        return np.asarray(sweep.trips, np.int64)
    return np.asarray(list(critical_trips(sweep, strategy).values()), np.int64)
