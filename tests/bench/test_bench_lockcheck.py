"""The model of strict two-phase locking against lock states built by hand,
and the device-trace readers of `us_per_trip` and `idle_share`."""

from __future__ import annotations

from types import SimpleNamespace

import numpy as np
import pytest

from bench import lockcheck, trace
from bench.metrics import critical_trips
from bench.ref.state import OP_DONE, OP_EXEC, OP_HOLD, OP_NONE, OP_WAIT

from conftest import ROOT  # noqa: F401  (puts the repo on the import path)


def _state(ops):
    """A [T=len(ops), K=2] lock state from rows of (state, key, write, enq)."""
    width = 2
    rows = [list(r) + [(OP_NONE, -1, False, 0)] * (width - len(r)) for r in ops]
    field = lambda i, dt: np.array([[op[i] for op in r] for r in rows], dt)  # noqa: E731
    return SimpleNamespace(
        op_state=field(0, np.int8), op_key=field(1, np.int32),
        op_write=field(2, bool), op_enq=field(3, np.int32),
    )


@pytest.mark.parametrize(
    "ops, want",
    [
        # readers share; a writer waits behind them; a later reader queues behind it
        ([[(OP_HOLD, 7, False, 1)], [(OP_EXEC, 7, False, 2)], [(OP_WAIT, 7, True, 3)],
          [(OP_WAIT, 7, False, 4)]], 0),
        # a writer holds alone; released and cancelled ops hold nothing
        ([[(OP_EXEC, 7, True, 1), (OP_DONE, 8, True, 0)], [(OP_DONE, 7, True, 0)]], 0),
        # a writer holds beside a reader: one pair
        ([[(OP_HOLD, 7, False, 1)], [(OP_EXEC, 7, True, 2)]], 1),
        # two writers and a reader hold one record: three pairs
        ([[(OP_HOLD, 7, True, 1)], [(OP_EXEC, 7, True, 2)], [(OP_HOLD, 7, False, 3)]], 3),
        # a reader holds though a writer has waited since before it came
        ([[(OP_WAIT, 7, True, 1)], [(OP_EXEC, 7, False, 2)]], 1),
        # readers may overtake a waiting reader: no conflict
        ([[(OP_WAIT, 7, False, 1)], [(OP_EXEC, 7, False, 2)]], 0),
        # one transaction's ops on two records, and ties in time
        ([[(OP_HOLD, 7, True, 5), (OP_HOLD, 9, False, 5)], [(OP_WAIT, 7, False, 5)],
          [(OP_EXEC, 9, False, 6)]], 0),
    ],
)
def test_violations_count_the_pairs_2pl_forbids(ops, want):
    assert lockcheck.violations(_state(ops)) == want


def _run(loops, seconds, lane_device, trips, strategy="vmap"):
    sweep = SimpleNamespace(
        traced=True, seconds=seconds, lane_device=lane_device, trips=np.array(trips),
    )
    reduced = trace.Reduced(window_s=1.0, busy_s={}, idle_gaps=[], device_ops=[], loops=loops)
    return SimpleNamespace(strategy=strategy, sweeps=[sweep], reduced=reduced)


def _reader(name):
    import importlib

    return importlib.import_module(f"bench.metrics.{name}").read


def test_us_per_trip_and_idle_share_read_the_loop():
    loops = {0: trace.Loop(pre_busy_s=0.1, busy_s=0.5, trips=400)}
    run = _run(loops, seconds=2.0, lane_device=[0, 0, 0], trips=[900, 1500, 1200])
    assert critical_trips(run.sweeps[0], "vmap") == {0: 1500}
    assert _reader("us_per_trip")(run) == pytest.approx(1250.0)
    # busy: 0.1 s before the loop, 1500 trips of 1.25 ms in it, of a 2 s sweep
    assert _reader("idle_share")(run) == pytest.approx(100 * (1 - (0.1 + 1.875) / 2.0))


@pytest.mark.parametrize("strategy", ["map", "mesh"])
def test_idle_share_reads_nothing_where_a_device_runs_its_worlds_in_turn(strategy):
    """The trace holds the first trips of a device's first world only; they
    stand for no other world's trips."""
    loops = {d: trace.Loop(pre_busy_s=0.1, busy_s=0.06, trips=250) for d in range(4)}
    run = _run(loops, seconds=6.8, lane_device=[0, 0, 1, 1, 2, 2, 3, 3], trips=[7000] * 8,
               strategy=strategy)
    assert _reader("us_per_trip")(run) == pytest.approx(240.0)
    assert _reader("idle_share")(run) is None


def test_device_readers_find_nothing_without_a_loop():
    run = _run({}, seconds=2.0, lane_device=[0], trips=[10])
    assert _reader("us_per_trip")(run) is None
    assert _reader("idle_share")(run) is None
    run.reduced = None
    assert _reader("us_per_trip")(run) is None
