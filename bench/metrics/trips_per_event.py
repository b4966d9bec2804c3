"""While-loop trips per simulated event, over every sweep of the window: the
window plan's work (one trip per window or per event it could not drain)."""


def read(run):
    return float(sum(int(sw.trips.sum()) for sw in run.sweeps)) / sum(
        int(sw.events.sum()) for sw in run.sweeps
    )
