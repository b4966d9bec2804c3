"""The plain reference the benchmark compares the program with.

It is the engine's sequential step mode (`_step`: one earliest event per
trip, through a switch over the event handlers) together with the state,
lock, hotspot, network, scheduler and protocol modules it calls, copied
from the program as they stood when the benchmark was defined, with only
their imports rewritten. The program may change from here on; this copy
does not. It imports nothing of the program and takes from it nothing but
the cell's parameters: the bank arrays come from `bench.gen`.

`simulate` runs one world of a deployment to its horizon, one event at a
time, and returns its final state (as host arrays) and its metric dict;
`start` and `finish` are its two halves, so that several worlds can run on
several devices at once.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.ref.metrics import summarize
from bench.ref.protocols import PRESETS
from bench.ref.state import SimConfig, SimState, _times_flat, init_state_world, make_world
from bench.ref.step import _step
from bench.ref.workloads import Bank

__all__ = ["Bank", "SimConfig", "SimState", "config", "finish", "simulate", "start"]


def config(deploy: dict, bank_shape: tuple, num_ds: int) -> SimConfig:
    """The sequential engine's static configuration for a deployment."""
    T, N, K = bank_shape
    return SimConfig(
        terminals=T,
        max_ops=K,
        num_ds=num_ds,
        bank_txns=N,
        proto=PRESETS["geotp"],
        hot_capacity=deploy["hot_capacity"],
        warmup_us=int(deploy["warmup_s"] * 1e6),
        horizon_us=int(deploy["horizon_s"] * 1e6),
        drain=False,
        lockstep=False,
    )


def run(cfg: SimConfig, bank: Bank, state: SimState) -> SimState:
    """One event per trip until the horizon (or the event budget)."""

    def cond(s: SimState):
        nxt = jnp.min(_times_flat(s))
        return (nxt < jnp.int32(cfg.horizon_us)) & (s.iters < cfg.max_events)

    return jax.lax.while_loop(cond, lambda s: _step(cfg, bank, s), state)


@functools.partial(jax.jit, static_argnums=(0,))
def _fresh(cfg: SimConfig, bank: Bank, world) -> SimState:
    return run(cfg, bank, init_state_world(cfg, world))


def start(deploy: dict, bank: dict, cell: dict, device=None, fresh=_fresh):
    """Dispatch one world's run: (its configuration, its final state still
    on the device).

    deploy: the configuration's `deployment` block (horizon, warmup,
    hot-table capacity); bank: the generator's arrays; cell: the world's
    preset, RTT vector (ms) and jitter. `device` is where it runs (default:
    JAX's default device); `fresh` is the jitted init-and-run (the control
    passes its own).
    """
    cfg = config(deploy, np.shape(bank["key"]), int(bank["num_ds"]))
    with jax.default_device(device or jax.devices()[0]):
        world = make_world(cell["preset"], tuple(cell["rtt_ms"]), jitter_milli=cell["jitter_milli"])
        ref_bank = Bank(
            key=jnp.asarray(bank["key"], jnp.int32),
            write=jnp.asarray(bank["write"], bool),
            ds=jnp.asarray(bank["ds"], jnp.int8),
            round_id=jnp.asarray(bank["round_id"], jnp.int8),
            valid=jnp.asarray(bank["valid"], bool),
            is_dist=jnp.asarray(bank["is_dist"], bool),
            num_records=int(bank["num_records"]),
            num_ds=int(bank["num_ds"]),
        )
        return cfg, fresh(cfg, ref_bank, world)


def finish(cfg: SimConfig, state: SimState):
    """(final state as numpy arrays, metric dict) of a dispatched run."""
    state = jax.tree_util.tree_map(np.asarray, state)
    return state, summarize(cfg, state)


def simulate(deploy: dict, bank: dict, cell: dict, device=None, fresh=_fresh):
    """(final state as numpy arrays, metric dict) of one world (`start`,
    then `finish`)."""
    return finish(*start(deploy, bank, cell, device, fresh))
