"""Run loop and single-world entry points.

`run` drives one of the four step modes to the horizon inside a
`lax.while_loop`; `simulate` is the jit-cached single-world entry point.
Multi-world sweeps live in `placement` (the map/vmap/mesh strategy layer —
`simulate_batch` below is a thin legacy alias into it); the `api.Simulator`
facade builds on both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.workloads import Bank

from repro.core.engine.metrics import summarize
from repro.core.engine.omni import _omni_step
from repro.core.engine.state import (
    SimConfig,
    SimState,
    WorldSpec,
    init_state,
    init_state_world,
    _times_flat,
)
from repro.core.engine.apply import _drain_step
from repro.core.engine.fused import _omni_window
from repro.core.engine.step import _step

def run(cfg: SimConfig, bank: Bank, state: SimState) -> SimState:
    """Run until the horizon (or the event budget) is exhausted.

    With cfg.drain the event budget is approximate: a drained window may
    overshoot max_events by (window-1) events.
    """
    if cfg.lockstep:
        step = _omni_window if cfg.drain else _omni_step
    else:
        step = _drain_step if cfg.drain else _step

    @jax.named_scope("repro/pick")
    def cond(s: SimState):
        nxt = jnp.min(_times_flat(s))
        return (nxt < jnp.int32(cfg.horizon_us)) & (s.iters < cfg.max_events)

    def body(s: SimState):
        return step(cfg, bank, s)

    return jax.lax.while_loop(cond, body, state)


_run_jit = jax.jit(run, static_argnums=(0,))


@functools.partial(jax.jit, static_argnums=(0,))
def _sim_world_fresh(cfg: SimConfig, bank: Bank, world: WorldSpec) -> SimState:
    """Fused init+run for ONE world — the `api.Simulator.run` fast path."""
    return run(cfg, bank, init_state_world(cfg, world))


def simulate(
    cfg: SimConfig,
    bank: Bank,
    tau_true_us,
    tau_ds_us,
    jitter_milli: int = 0,
    exec_scale_milli=None,
    state: SimState | None = None,
    faults=None,
    replica_tau=None,
    repl_lag_us=0,
):
    """Convenience wrapper: init (or continue) + run + summarize.

    `faults` is a [cfg.max_faults, 6] typed schedule of (t_start_us, kind,
    endpoint_a, endpoint_b, t_end_us, severity) rows — legacy
    [cfg.max_faults, 3] crash triples are widened (see `state.pad_faults`);
    only meaningful on fresh runs of a fault-carrying config, as are the
    replica axes `replica_tau` ([D] replica-link RTTs, INF_US = no replica)
    and `repl_lag_us`.
    """
    if state is None:
        state = init_state(
            cfg, tau_true_us, tau_ds_us, jitter_milli, exec_scale_milli,
            faults=faults, replica_tau=replica_tau, repl_lag_us=repl_lag_us,
        )
    state = _run_jit(cfg, bank, state)
    return state, summarize(cfg, state)


# ---------------------------------------------------------------------------
# multi-world sweeps — the strategy dispatch moved to `placement` (the
# map/vmap/mesh execution-placement layer); this alias keeps the historical
# `engine.simulate_batch` / `batch.simulate_batch` entry point working.
# ---------------------------------------------------------------------------


def simulate_batch(
    cfg: SimConfig,
    bank: Bank,
    worlds: WorldSpec,
    *,
    bank_batched: bool = False,
    states: SimState | None = None,
    strategy: str = "auto",
    mesh_devices: int | None = None,
):
    """Run a batch of worlds as one batched device call — see
    `placement.simulate_batch` (strategies: map / vmap / mesh / auto)."""
    from repro.core.engine import placement

    return placement.simulate_batch(
        cfg,
        bank,
        worlds,
        bank_batched=bank_batched,
        states=states,
        strategy=strategy,
        mesh_devices=mesh_devices,
    )
