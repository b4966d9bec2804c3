"""Execution placement layer: where a stacked world batch actually runs.

Every multi-world sweep follows one protocol — **stack** the `WorldSpec` /
`Bank` pytrees on a leading [B] axis (`Grid.worlds()` / `Grid.bank_stack()`),
**place** them on the execution substrate, **run** the compiled engine over
every lane, **gather** the final `SimState` batch back — and this module owns
the "place + run" step behind a small strategy table:

| strategy | placement | lane execution |
|---|---|---|
| ``map``  | one device | `lax.map` — sequential lanes, scalar control flow (cond-gated windowed drain); the fastest single-host CPU strategy |
| ``vmap`` | one device | `jax.vmap` — lockstep lanes through the branchless fused windowed drain (`fused._omni_window`); the accelerator strategy |
| ``mesh`` | 1-D ``worlds`` jax mesh over N devices (`launch.mesh.make_worlds_mesh`) | `shard_map`: the batch shards on its leading axis (`dist.sharding.worlds_pspec` NamedSharding rules), each device sweeps its slice with the map-strategy body — zero cross-device communication, since worlds are independent and `WorldSpec` isolates per-world network state |
| ``auto`` | resolved by `resolve_strategy` | mesh when >1 device is visible, vmap on a single accelerator, map on single-host CPU |

Grids whose cell count does not divide the mesh device count get **padding
lanes** (cells repeated modulo B). Pad lanes run like any other lane but are
sliced off before the final state batch is returned, so no telemetry path —
`summarize_batch`, `drain_stats`, `RunResult.rows()` — ever sees them.

Entry points are jit-cached per (shape-key, bank-axis, strategy,
device-count): `_sim_batch_fresh` fuses init+run for fresh sweeps,
`_run_batch` continues donated states (`Simulator.resume`). Strategies are
bitwise-identical per cell — mesh shards execute the exact map-strategy body,
asserted under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in
tests/core/test_placement.py, so the contract holds on CPU-only CI.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.dist.sharding import place_worlds, worlds_pspec
from repro.launch.mesh import make_worlds_mesh

from repro.core.engine.batch import run
from repro.core.engine.metrics import summarize_batch
from repro.core.engine.spans import span
from repro.core.engine.state import SimConfig, SimState, WorldSpec, init_state_world

STRATEGIES = ("map", "vmap", "mesh")


def resolve_strategy(
    strategy: str,
    *,
    device_count: int | None = None,
    backend: str | None = None,
) -> str:
    """Resolve ``"auto"`` to a concrete strategy — THE decision table.

    * ``mesh`` when more than one device is visible (every extra device is a
      free lane multiplier: worlds are independent, so sharding the grid is
      pure scale-out);
    * ``vmap`` on a single accelerator (lockstep lanes amortize the fused
      window plan across the batch);
    * ``map`` on single-host CPU (scalar control flow wins there — vmap still
      trails map on CPU, see BENCH `vmap_vs_map`).

    Explicit strategies pass through unchanged; unknown names raise.
    ``device_count`` / ``backend`` default to the live jax runtime and exist
    so the table is unit-testable without faking devices.
    """
    if strategy in STRATEGIES:
        return strategy
    if strategy != "auto":
        raise ValueError(
            f"unknown strategy {strategy!r} (choose from "
            f"{('auto',) + STRATEGIES})"
        )
    n = jax.device_count() if device_count is None else device_count
    if n > 1:
        return "mesh"
    b = jax.default_backend() if backend is None else backend
    return "vmap" if b in ("tpu", "gpu") else "map"


def mesh_device_count(strategy: str, mesh_devices: int | None = None) -> int:
    """Devices the resolved strategy will place lanes on (1 off-mesh).

    The returned count is a static jit argument, so compile caching is per
    (shape-key, strategy, device-count) — forcing a different count (e.g. a
    4-device mesh on an 8-device host) compiles its own program.
    """
    if strategy != "mesh":
        return 1
    return jax.device_count() if mesh_devices is None else int(mesh_devices)


def placement_cfg(cfg: SimConfig, strategy: str) -> SimConfig:
    """The strategy's engine configuration. Lockstep lanes execute every
    `lax.switch`/`cond` branch per iteration, so the vmap strategy routes
    through the branchless fused windowed drain (`lockstep=True`) — honoring
    `cfg.drain` via `_omni_window` instead of silently downgrading it.
    Bitwise-identical trajectories either way. Map and mesh keep the scalar
    cond-gated path."""
    if strategy == "vmap":
        return dataclasses.replace(cfg, lockstep=True)
    return cfg


# ---------------------------------------------------------------------------
# lane runners (place + run)
# ---------------------------------------------------------------------------


def _batch_over(one, bank, xs, bank_axis, strategy):
    """Single-device placement: map `one(bank_lane, x_lane)` over the batch.

    strategy "vmap" runs lanes in lockstep through the branchless windowed
    drain (`_omni_window`) — one fused pass per iteration, no switch/cond, so
    the window plan amortizes across lanes (the accelerator path); "map" runs
    lanes sequentially inside ONE compiled call (scalar control flow takes
    the window plan's cond-gated route and per-world cost stays flat as the
    grid widens — the fastest CPU strategy).
    """
    if strategy == "vmap":
        return jax.vmap(one, in_axes=(bank_axis, 0))(bank, xs)
    if bank_axis is None:
        return jax.lax.map(lambda x: one(bank, x), xs)
    return jax.lax.map(lambda bx: one(*bx), (bank, xs))


def _mesh_over(one, bank, xs, bank_axis, ndev):
    """Mesh placement: shard the batch's leading axis over a 1-D ``worlds``
    mesh and sweep each slice with the map-strategy body under `shard_map`.

    Worlds are independent (per-world network state lives in `WorldSpec`), so
    the sharded program contains zero cross-device collectives. When the lane
    count does not divide ``ndev`` the batch is padded by repeating cells
    modulo B; pad lanes are sliced off before returning, so their telemetry
    never reaches `summarize_batch` / `drain_stats` / `RunResult.rows()`.
    """
    mesh = make_worlds_mesh(ndev)
    B = jax.tree_util.tree_leaves(xs)[0].shape[0]
    Bp = -(-B // ndev) * ndev
    if Bp != B:
        idx = jnp.arange(Bp) % B
        xs = jax.tree_util.tree_map(lambda x: x[idx], xs)
        if bank_axis is not None:
            bank = jax.tree_util.tree_map(lambda x: x[idx], bank)
    xs = place_worlds(xs, mesh)
    if bank_axis is not None:
        bank = place_worlds(bank, mesh)
    body = jax.shard_map(
        lambda b, x: _batch_over(one, b, x, bank_axis, "map"),
        mesh=mesh,
        in_specs=(worlds_pspec(bank_axis is not None), worlds_pspec(True)),
        out_specs=worlds_pspec(True),
        check_vma=False,
    )
    out = body(bank, xs)
    if Bp != B:
        out = jax.tree_util.tree_map(lambda x: x[:B], out)
    return out


def _place_over(one, bank, xs, bank_axis, strategy, ndev):
    if strategy == "mesh":
        return _mesh_over(one, bank, xs, bank_axis, ndev)
    return _batch_over(one, bank, xs, bank_axis, strategy)


# ---------------------------------------------------------------------------
# jit-cached entry points (per shape-key x bank-axis x strategy x devices)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5))
def _sim_batch_fresh(
    cfg: SimConfig, bank, worlds: WorldSpec, bank_axis, strategy, ndev=1
):
    def one(b, w):
        return run(cfg, b, init_state_world(cfg, w))

    return _place_over(one, bank, worlds, bank_axis, strategy, ndev)


@functools.partial(jax.jit, static_argnums=(0, 3, 4, 5), donate_argnums=(2,))
def _run_batch(cfg: SimConfig, bank, states: SimState, bank_axis, strategy, ndev=1):
    return _place_over(
        lambda b, st: run(cfg, b, st), bank, states, bank_axis, strategy, ndev
    )


def simulate_batch(
    cfg: SimConfig,
    bank,
    worlds: WorldSpec,
    *,
    bank_batched: bool = False,
    states: SimState | None = None,
    strategy: str = "auto",
    mesh_devices: int | None = None,
    phases: dict | None = None,
):
    """Run a batch of worlds as one batched (possibly sharded) device call.

    cfg:    shared static config (shapes/horizon); `cfg.proto` only provides
            defaults — the per-world knobs come from `worlds.dyn`.
    bank:   one Bank shared by every world, or (bank_batched=True) a Bank
            whose leaves carry a leading [B] axis (e.g. per-seed workloads).
    worlds: WorldSpec with a leading [B] axis on every leaf (`stack_worlds`).
    strategy: "map" / "vmap" / "mesh" / "auto" — see the module docstring
            table; "auto" resolves through `resolve_strategy`.
    mesh_devices: mesh-strategy device count override (default: all visible
            devices); ignored off-mesh.
    phases: dict the `repro.device`, `repro.gather` and `repro.summarize`
            spans add their seconds to (see `spans`).

    Returns (final_states [B-batched], list of B metric dicts). Fresh runs
    fuse init+run into one compiled call; continuation runs (states given)
    donate the incoming state buffer, so sweeps of any size reuse memory.
    """
    strategy = resolve_strategy(strategy)
    ndev = mesh_device_count(strategy, mesh_devices)
    cfg = placement_cfg(cfg, strategy)
    bank_axis = 0 if bank_batched else None
    phases = {} if phases is None else phases
    with span("repro.device", phases):
        if states is None:
            states = _sim_batch_fresh(cfg, bank, worlds, bank_axis, strategy, ndev)
        else:
            states = _run_batch(cfg, bank, states, bank_axis, strategy, ndev)
        # the gather below waits here anyway: the block adds no sync
        states = jax.block_until_ready(states)
    return states, summarize_batch(cfg, states, phases)
