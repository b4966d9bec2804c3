"""Device busy microseconds per while-loop trip on the busiest device: its
busy time from the loop's first traced trip to the trace's end over the
trips the trace holds there, both read from the device trace alone."""


def read(run):
    if run.reduced is None:
        return None
    used = {d for sw in run.sweeps for d in sw.lane_device}
    loops = {d: lp for d, lp in run.reduced.loops.items() if d in used}
    if not loops:
        return None
    lp = max(loops.values(), key=lambda x: x.busy_s)
    return lp.busy_s / lp.trips * 1e6
