"""Host spans and device scopes: `RunResult.phases`, the spans' nesting on
the profiler's host plane, and the named scopes the compiled loop carries
in its op metadata."""

import pathlib
import re

import jax
import pytest

from repro.core import workloads
from repro.core.engine import Grid, Simulator, placement
from repro.core.engine.spans import WALL_PHASES

T, K, D, N = 8, 4, 2, 32
GRID_SPANS = ("repro.stack", "repro.device", "repro.gather", "repro.summarize")
SCOPES = {"pick", "plan", "chain", "apply", "locks", "hotspot"}


def _bank(seed):
    cfg_w = workloads.YCSBConfig(
        num_ds=D, records_per_node=2000, ops_per_txn=K, dist_ratio=0.5, theta=0.9, seed=seed,
    )
    return workloads.make_ycsb_bank(cfg_w, terminals=T, txns_per_terminal=N)


@pytest.fixture(scope="module")
def sim_grid():
    banks = [_bank(1), _bank(2)]
    sim = Simulator.from_bank(banks[0], horizon_s=0.2, warmup_s=0.0)
    cells = [dict(preset=p, rtt_ms=(10.0, 100.0)) for p in ("ssp", "geotp") for _ in banks]
    grid = Grid(cells, banks=banks * 2)
    sim.run_grid(grid)  # compile outside the measured calls
    return sim, grid


def _assert_wall_is_the_spans(res):
    assert set(WALL_PHASES) <= set(res.phases)
    assert all(v > 0 for v in res.phases.values())
    assert res.wall_s == pytest.approx(sum(res.phases[k] for k in WALL_PHASES), rel=1e-12)


def test_run_grid_fills_phases_without_a_profiler(sim_grid):
    sim, grid = sim_grid
    res = sim.run_grid(grid)
    assert set(res.phases) == {"repro.run_grid", *GRID_SPANS}
    _assert_wall_is_the_spans(res)
    # the whole call holds its parts
    assert res.phases["repro.run_grid"] >= sum(res.phases[k] for k in GRID_SPANS)


def test_run_and_resume_fill_phases(sim_grid):
    sim, _ = sim_grid
    bank = _bank(1)
    one = sim.run(Grid([dict(preset="geotp", rtt_ms=(10.0, 100.0))]).world(0), bank)
    assert set(one.phases) == set(WALL_PHASES)
    _assert_wall_is_the_spans(one)
    more = sim.resume(one, horizon_s=0.3)
    assert set(more.phases) == set(WALL_PHASES)
    _assert_wall_is_the_spans(more)


def _host_spans(log_dir) -> list:
    """(name, start_ns, end_ns) of the `repro.*` events on the host line
    that holds `repro.run_grid`."""
    path = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))[-1]
    pd = jax.profiler.ProfileData.from_file(str(path))
    for line in pd.find_plane_with_name("/host:CPU").lines:
        ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
        if any(n == "repro.run_grid" for n, _, _ in ev):
            return [x for x in ev if x[0].startswith("repro.")]
    raise AssertionError("no host line holds repro.run_grid")


def test_run_grid_spans_nest_in_order_on_one_host_line(sim_grid, tmp_path):
    sim, grid = sim_grid
    with jax.profiler.trace(str(tmp_path)):
        res = sim.run_grid(grid)
    _assert_wall_is_the_spans(res)
    spans = _host_spans(tmp_path)
    [(_, lo, hi)] = [s for s in spans if s[0] == "repro.run_grid"]
    parts = sorted((s for s in spans if s[0] in GRID_SPANS), key=lambda s: s[1])
    assert [n for n, _, _ in parts] == list(GRID_SPANS)
    for (_, s, e), (_, s2, _) in zip(parts, parts[1:] + [("", hi, hi)]):
        assert lo <= s <= e <= s2 <= hi


@pytest.mark.parametrize("strategy", ["vmap", "map"])
def test_the_lowered_loop_carries_every_scope(sim_grid, strategy):
    sim, grid = sim_grid
    worlds = grid.worlds()
    cfg = placement.placement_cfg(sim._cfg_for(worlds.faults), strategy)
    lowered = placement._sim_batch_fresh.lower(cfg, grid.bank_stack(), worlds, 0, strategy, 1)
    text = lowered.as_text(debug_info=True)
    names = re.findall(r'loc\("([^"]*)"', text)
    assert SCOPES <= {s for n in names for s in re.findall(r"repro/([a-z]+)", n)}
    # the loop condition's pick is scoped inside the while loop's condition
    assert any("while/cond" in n and "repro/pick" in n for n in names)
