"""Run the engine's main path once on a TPU and check it bit for bit.

The deployment is GeoTP §VII's YCSB set-up at the repo's own settings:
four data sources at 0/27/73/251 ms RTT (`netmodel.PAPER_RTT_MS`),
1,000,000 records per node, Zipf θ=0.9, 20% distributed transactions,
5 ops per transaction and a 256-transaction bank per terminal
(`benchmarks.common.ycsb_bank`), 128 terminals (the largest count of the
``--full`` fig5 sweep), and a `Grid` of the fig5 presets ssp, ssp-local,
scalardb and geotp × seeds 0 and 1: 8 worlds, each over a 10 s horizon
with 2 s of warmup.

    python chip_smoke.py          # one chip
    python chip_smoke.py --mesh   # every chip of the host

On one chip it runs, through `Simulator.run_grid` / `Simulator.run`:

  (a) the grid under ``vmap``, the placement ``auto`` picks on one
      accelerator;
  (b) the same grid under ``map`` on the same chip;
  (c) the sequential reference (``drain=False``: the `_step` loop) on the
      ssp and geotp seed-0 worlds.

Every world's final `SimState` must match leaf by leaf and bit for bit
between (a) and (b) and between (a)/(b) and (c), outside the path telemetry
that each step mode counts its own way; (a) and (b) must also report the
same drain telemetry. Every world must commit and fire no no-op event.

With ``--mesh`` it runs only the grid under ``mesh`` over every chip and
under ``map`` on chip 0, and requires them to match per world in every
leaf.

Each strategy is named explicitly: ``auto`` would turn a one-chip run into
a mesh run on a host with several chips. Everything runs in this one
process, which owns the chip. Each program is compiled before it runs, and
its compile seconds are reported as set-up; the run seconds are one run on
one chip, not a benchmark. The last line of stdout is a JSON object,
printed only when every check held. Without a TPU the script exits nonzero
before it runs anything.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.common import ycsb_bank  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core.engine import Grid, Simulator, batch, drain_stats, placement  # noqa: E402
from repro.core.netmodel import PAPER_RTT_MS  # noqa: E402

PRESETS = ("ssp", "ssp-local", "scalardb", "geotp")
SEEDS = (0, 1)
TERMINALS = 128
HORIZON_S = 10.0
WARMUP_S = 2.0
REFERENCE_CELLS = (("ssp", 0), ("geotp", 0))
# SimState leaves each step mode counts its own way
# (tests/core/test_differential.py); every other leaf must match
PATH_TELEMETRY = ("drained", "windows", "win_stops", "fused", "chained")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def compile_clock():
    """Sum the seconds this process spends in XLA backend compiles from now
    on; returns a function that reads the sum."""
    total = [0.0]

    def on_event(event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += duration_secs

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return lambda: total[0]


def mismatched_leaves(a, b, ignore=PATH_TELEMETRY) -> list:
    """Names of the leaves of two final states that differ in any bit."""
    a = a._replace(**{k: getattr(b, k) for k in ignore})
    if jax.tree_util.tree_structure(a) != jax.tree_util.tree_structure(b):
        return ["<tree structure>"]
    out = []
    for (path, x), y in zip(
        jax.tree_util.tree_flatten_with_path(a)[0], jax.tree_util.tree_leaves(b)
    ):
        x, y = np.asarray(x), np.asarray(y)
        if x.dtype != y.dtype or x.shape != y.shape or x.tobytes() != y.tobytes():
            out.append(jax.tree_util.keystr(path))
    return out


def world_drain(res, i: int) -> dict:
    """Drain telemetry of world i, less `plan_fused`, which says only which
    step mode ran."""
    d = drain_stats(res.world(i), horizon_us=res.cfg.horizon_us)
    d.pop("plan_fused")
    return d


def liveness_problems(name: str, res) -> list:
    return [
        f"{name} world {i} {cell}: commits={m['commits']} noops={m['noops']}"
        for i, (cell, m) in enumerate(zip(res.cells, res.metrics))
        if m["commits"] <= 0 or m["noops"] != 0
    ]


def compare_grids(name_a, ra, name_b, rb, ignore=PATH_TELEMETRY) -> list:
    """Per-world mismatches between two runs of one grid."""
    problems = []
    for i, cell in enumerate(ra.cells):
        bad = mismatched_leaves(ra.world(i), rb.world(i), ignore)
        if bad:
            problems.append(f"{name_a} != {name_b} world {i} {cell}: {bad}")
        da, db = world_drain(ra, i), world_drain(rb, i)
        if da != db:
            keys = sorted(k for k in da if da[k] != db[k])
            problems.append(
                f"{name_a} != {name_b} drain telemetry world {i} {cell}: "
                + ", ".join(f"{k} {da[k]} vs {db[k]}" for k in keys)
            )
    return problems


def run_grid_timed(sim, grid, bank, strategy: str, compiled_s):
    """Compile, then run, the program `sim.run_grid` runs for `strategy`."""
    ndev = placement.mesh_device_count(strategy)
    cfg = placement.placement_cfg(sim.cfg, strategy)
    t0 = time.perf_counter()
    placement._sim_batch_fresh.lower(
        cfg, bank, grid.worlds(), None, strategy, ndev
    ).compile()
    t1 = time.perf_counter()
    c0 = compiled_s()
    res = sim.run_grid(grid, bank, strategy=strategy)
    jax.block_until_ready(res.states)
    t2 = time.perf_counter()
    d = res.drain
    log(
        f"{strategy}: compile {t1 - t0} s (set-up); run {t2 - t1} s; "
        f"{d['events']} events, {d['loop_iters']} loop iters, mean window "
        f"{d['mean_window_len']}, drain hit {d['drain_hit_rate']}; "
        f"compile s inside the run: {compiled_s() - c0}"
    )
    return res


def deployment(terminals, horizon_s, warmup_s, presets, seeds):
    """(bank, grid, simulator) of the YCSB deployment at the given size."""
    bank = ycsb_bank(terminals)
    grid = Grid.cross(preset=presets, seed=seeds)
    return bank, grid, Simulator.from_bank(bank, horizon_s=horizon_s, warmup_s=warmup_s)


def one_chip_phases(
    terminals=TERMINALS,
    horizon_s=HORIZON_S,
    warmup_s=WARMUP_S,
    presets=PRESETS,
    seeds=SEEDS,
    reference_cells=REFERENCE_CELLS,
    compiled_s=lambda: 0.0,
) -> list:
    """Phases (a), (b) and (c) on the default device; returns the problems
    found (empty when every check held)."""
    t0 = time.perf_counter()
    bank, grid, sim = deployment(terminals, horizon_s, warmup_s, presets, seeds)
    log(f"bank and grid set-up {time.perf_counter() - t0} s")

    ra = run_grid_timed(sim, grid, bank, "vmap", compiled_s)
    rb = run_grid_timed(sim, grid, bank, "map", compiled_s)
    problems = liveness_problems("vmap", ra) + liveness_problems("map", rb)
    problems += compare_grids("vmap", ra, "map", rb)

    ref_ix = [
        i for i, c in enumerate(grid.cells)
        if (c["preset"], c["seed"]) in reference_cells
    ]
    if not ref_ix:
        return problems + [f"no grid cell is a reference cell {reference_cells}"]
    ref = Simulator.from_bank(
        bank, horizon_s=horizon_s, warmup_s=warmup_s, drain=False
    )
    t0 = time.perf_counter()
    batch._sim_world_fresh.lower(ref.cfg, bank, grid.world(ref_ix[0])).compile()
    log(f"reference: compile {time.perf_counter() - t0} s (set-up)")
    for i in ref_ix:
        cell = grid.cells[i]
        t0 = time.perf_counter()
        c0 = compiled_s()
        rc = ref.run(grid.world(i), bank, labels=cell)
        jax.block_until_ready(rc.states)
        m = rc.metrics[0]
        log(
            f"reference world {i} {cell}: run {time.perf_counter() - t0} s; "
            f"{m['events']} events, {m['commits']} commits; compile s inside "
            f"the run: {compiled_s() - c0}"
        )
        problems += liveness_problems("reference", rc)
        for name, res in (("vmap", ra), ("map", rb)):
            bad = mismatched_leaves(res.world(i), rc.states)
            if bad:
                problems.append(f"{name} != reference world {i} {cell}: {bad}")
    return problems


def mesh_phases(
    terminals=TERMINALS,
    horizon_s=HORIZON_S,
    warmup_s=WARMUP_S,
    presets=PRESETS,
    seeds=SEEDS,
    compiled_s=lambda: 0.0,
) -> list:
    """The grid under ``mesh`` over every device and under ``map`` on
    device 0; returns the problems found."""
    bank, grid, sim = deployment(terminals, horizon_s, warmup_s, presets, seeds)
    rm = run_grid_timed(sim, grid, bank, "mesh", compiled_s)
    rb = run_grid_timed(sim, grid, bank, "map", compiled_s)
    problems = liveness_problems("mesh", rm) + liveness_problems("map", rb)
    n = jax.device_count()
    on = {s.device for s in rm.states.iters.addressable_shards}
    log(f"mesh: {len(grid)} worlds on {len(on)} of {n} devices: {sorted(map(str, on))}")
    if len(on) != n:
        problems.append(f"mesh placed the worlds on {len(on)} of {n} devices")
    if rb.states.iters.devices() != {jax.devices()[0]}:
        problems.append(f"map ran on {rb.states.iters.devices()}, not device 0")
    return problems + compare_grids("mesh", rm, "map", rb, ignore=())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--mesh",
        action="store_true",
        help="run only the grid under mesh over every chip and under map on "
        "chip 0, and compare them",
    )
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: no TPU (JAX found platform {dev.platform!r}); "
            "nothing was run",
            file=sys.stderr,
        )
        return 1
    if args.mesh and len(devices) < 2:
        print("chip_smoke: --mesh needs more than one chip", file=sys.stderr)
        return 1
    log(f"device_kind={dev.device_kind} platform={dev.platform} count={len(devices)}")
    log(f"compile cache: {use_compile_cache()}")
    log(
        f"deployment: {len(PAPER_RTT_MS)} data sources at {PAPER_RTT_MS} ms "
        f"RTT, 1000000 records/node, zipf 0.9, 20% distributed, 5 ops/txn, "
        f"256 txns/terminal, {TERMINALS} terminals; grid {PRESETS} x seeds "
        f"{SEEDS}; horizon {HORIZON_S} s, warmup {WARMUP_S} s"
    )
    log("the times below are from one run, not a benchmark")
    compiled_s = compile_clock()
    if args.mesh:
        problems = mesh_phases(compiled_s=compiled_s)
    else:
        problems = one_chip_phases(compiled_s=compiled_s)
    if problems:
        for p in problems:
            print(f"chip_smoke: FAILED: {p}", file=sys.stderr)
        return 1
    log("every compared world matched bit for bit")
    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(devices),
                },
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
