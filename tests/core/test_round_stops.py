"""The `round` stop reason: windows ended by an interactive round advance.

A round advance (the fan-in that completes a non-final round and dispatches
the next round's statements) is pinned like every other non-drainable event;
`round` only relabels those stops out of `nondrainable`. So on one-shot
transactions it reads 0 and every other reason keeps its count, and on
three-round transactions `round + nondrainable` is the old `nondrainable`
count, while the windows themselves do not change.
"""

import numpy as np
import pytest

from repro.core import workloads
from repro.core.engine import Grid, Simulator
from repro.core.engine.state import OP_NONE, OP_PENDING, STOP_REASONS

PRESETS = ("ssp", "ssp-local", "scalardb", "geotp")
RTT_MS = (0.0, 27.0, 73.0, 251.0)

# win_stops of each world (PRESETS order) before `round` existed, indexed by
# the first ten STOP_REASONS; map and vmap read the same
BEFORE = {
    1: [[3, 41, 61, 7, 0, 0, 0, 0, 0, 18], [3, 61, 55, 8, 0, 0, 0, 0, 0, 27],
        [1, 15, 24, 4, 0, 0, 0, 0, 0, 12], [1, 45, 118, 3, 21, 0, 0, 0, 0, 7]],
    3: [[2, 60, 53, 4, 0, 0, 0, 0, 0, 27], [3, 64, 44, 2, 0, 0, 0, 0, 0, 32],
        [2, 19, 35, 2, 1, 0, 0, 0, 0, 26], [1, 34, 87, 2, 25, 0, 0, 0, 0, 4]],
}
ROUND = STOP_REASONS.index("round")
NONDRAINABLE = STOP_REASONS.index("nondrainable")


def _bank(rounds):
    return workloads.make_ycsb_bank(
        workloads.YCSBConfig(
            num_ds=4, records_per_node=2000, ops_per_txn=6, dist_ratio=0.6,
            theta=0.9, seed=5, rounds=rounds,
        ),
        terminals=8, txns_per_terminal=32,
    )


@pytest.fixture(scope="module")
def runs():
    """(rounds, strategy) -> the RunResult of the four presets."""
    out = {}
    banks = {r: _bank(r) for r in BEFORE}
    sim = Simulator.from_bank(banks[1], horizon_s=2.0, warmup_s=0.0)
    grid = Grid.cross(preset=PRESETS, rtt_ms=(RTT_MS,))
    for rounds, bank in banks.items():
        for strategy in ("map", "vmap"):
            out[rounds, strategy] = sim.run_grid(grid, bank, strategy=strategy)
    return out


def test_round_is_the_last_reason():
    assert STOP_REASONS[:10] == (
        "horizon", "nondrainable", "scheduled", "lock_key", "dm_row",
        "dm_col", "rel_op", "cap", "fault", "sched_chain",
    )
    assert ROUND == 10 and len(STOP_REASONS) == 11


@pytest.mark.parametrize("strategy", ["map", "vmap"])
def test_one_round_reads_zero_and_keeps_every_count(runs, strategy):
    stops = np.asarray(runs[1, strategy].states.win_stops)
    assert stops.shape == (len(PRESETS), 11)
    assert (stops[:, ROUND] == 0).all()
    np.testing.assert_array_equal(stops[:, :ROUND], np.asarray(BEFORE[1]))


@pytest.mark.parametrize("strategy", ["map", "vmap"])
def test_three_rounds_relabel_nondrainable_stops(runs, strategy):
    res = runs[3, strategy]
    stops = np.asarray(res.states.win_stops)
    assert (stops[:, ROUND] > 0).all()
    relabelled = stops[:, :ROUND].copy()
    relabelled[:, NONDRAINABLE] += stops[:, ROUND]
    np.testing.assert_array_equal(relabelled, np.asarray(BEFORE[3]))
    np.testing.assert_array_equal(stops.sum(axis=1), np.asarray(res.states.windows))
    assert res.drain["window_stops"]["round"] == int(stops[:, ROUND].sum())


def test_round_stops_agree_between_map_and_vmap(runs):
    for rounds in BEFORE:
        np.testing.assert_array_equal(
            np.asarray(runs[rounds, "map"].states.win_stops),
            np.asarray(runs[rounds, "vmap"].states.win_stops),
        )


@pytest.mark.parametrize("strategy", ["map", "vmap"])
def test_later_rounds_are_not_dispatched(runs, strategy):
    # an op of a round its terminal has not reached yet holds no lock, waits
    # for none and has not left the middleware: it is still PENDING (or an
    # empty slot)
    st = runs[3, strategy].states
    op_round = np.asarray(st.op_round).astype(np.int64)
    cur = np.asarray(st.cur_round).astype(np.int64)[:, :, None]
    op_state = np.asarray(st.op_state)
    ahead = op_round > cur
    assert ahead.any()  # some terminal stands between rounds at the horizon
    assert np.isin(op_state[ahead], (OP_NONE, OP_PENDING)).all()
    assert (op_state[ahead] == OP_PENDING).any()
