"""Percent of the traced sweep's wall seconds in which a device had nothing
to run, averaged over the devices that held worlds. A device's busy time in
the sweep is what the trace shows before its loop's first trip, plus its
loop's traced busy time per trip times its critical-path trips of the
sweep (`critical_trips`); the trace itself covers the sweep's first
seconds only. That holds under ``vmap``, where the traced trips are the
lockstep trips of every lane at once. Under ``map`` and ``mesh`` a device
runs its worlds one after another, and the trace holds only the first
trips of its first world, which cost more than the sweep's mean trip
(``ycsb.fig5.mesh4`` on four TPU v5e chips: about 240 us against at most
173): there it gives nothing."""

from bench.metrics import critical_trips


def read(run):
    traced = [sw for sw in run.sweeps if sw.traced]
    if run.reduced is None or not traced or run.strategy != "vmap":
        return None
    sw = traced[0]
    shares = []
    for dev, trips in critical_trips(sw, run.strategy).items():
        lp = run.reduced.loops.get(dev)
        if lp is None:
            return None
        busy = lp.pre_busy_s + lp.busy_s / lp.trips * trips
        shares.append(1.0 - busy / sw.seconds)
    return 100.0 * sum(shares) / len(shares)
