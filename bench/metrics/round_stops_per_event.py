"""Windows ended by a round advance per simulated event, over every sweep of
the window: the share of `trips_per_event` that interactive rounds cost the
window plan (each advance dispatches the next round's statements, so it is
pinned and ends its window). One-shot transactions read 0. A program whose
`STOP_REASONS` has no ``round`` reason gives nothing."""

import numpy as np


def read(run):
    from repro.core.engine.state import STOP_REASONS

    if "round" not in STOP_REASONS:
        return None
    i = STOP_REASONS.index("round")
    stops = sum(int(np.asarray(sw.result.states.win_stops)[..., i].sum()) for sw in run.sweeps)
    return float(stops) / sum(int(sw.events.sum()) for sw in run.sweeps)
