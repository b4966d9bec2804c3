"""Host spans: named, timed stretches of a run's host path.

`span(name, into)` marks a stretch of host code on the profiler's host
plane (a `jax.profiler.TraceAnnotation`, on the clock of the device's ops)
and adds its `time.perf_counter` seconds to ``into[name]``. Spans are
always on; with no profiler running an annotation costs well under a
microsecond. `RunResult.phases` holds the dict a run filled.

The spans of `Simulator.run_grid`, nested in this order:

    repro.run_grid      the whole call
      repro.stack       `Grid.bank_stack`, the bank check, `Grid.worlds`
      repro.device      the jitted batch call until its states are ready
      repro.gather      the state batch's device-to-host copy
      repro.summarize   one metric dict per world

`Simulator.run` and `Simulator.resume` open the last three. A run's
`wall_s` is their sum (`wall_s`).

The device loop carries `jax.named_scope`s in the same spirit (op
metadata only; the compiled program is otherwise unchanged): the
innermost of ``repro/pick``, ``repro/plan``, ``repro/chain``,
``repro/apply``, ``repro/locks`` and ``repro/hotspot`` names the phase of
a loop trip that an op belongs to.
"""

from __future__ import annotations

import contextlib
import time

import jax

WALL_PHASES = ("repro.device", "repro.gather", "repro.summarize")


@contextlib.contextmanager
def span(name: str, into: dict):
    """Annotate the enclosed host code as ``name`` and add its seconds to
    ``into[name]``."""
    with jax.profiler.TraceAnnotation(name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            into[name] = into.get(name, 0.0) + time.perf_counter() - t0


def wall_s(phases: dict) -> float:
    """A run's wall seconds: its device, gather and summarize spans."""
    return sum(phases.get(k, 0.0) for k in WALL_PHASES)
