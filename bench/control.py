"""The control of the check: the reference with one stated guarantee broken.

The configurations state serializable isolation by strict two-phase
locking: a write takes an exclusive lock, so it waits while another
transaction holds the record shared. The control lets a write proceed over
shared holders (it still waits for an exclusive holder and for queued
waiters), the kind of shortcut that would tempt a faster window plan. Put
in the program's place, it has to come out as not correct.

    python bench/control.py --workload ycsb.fig5 --seeds 11 12 13

runs, for each seed, a sweep of the cell's worlds on banks drawn from the
seed through the control and through the reference, and prints the numbers
the check compares (one JSON line per seed, then a summary line). It runs
on the machine's default device; it is a measurement of the check, not part
of a benchmark run.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp

ROOT = pathlib.Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))

from bench import ref  # noqa: E402
from bench.ref import handlers  # noqa: E402
from bench.ref.netmodel import INF_US  # noqa: E402
from bench.ref.state import (  # noqa: E402
    OP_EXEC,
    OP_HOLD,
    OP_WAIT,
    _exec_us,
    _lock_wait_deadline,
    init_state_world,
)


def attempt_lock_shared_writes(cfg, s, t, k):
    """`locks._attempt_lock` with the exclusive mode broken: a write request
    is granted while others hold the record shared."""
    r = s.op_key[t, k]
    d = s.op_ds[t, k]
    st = s.op_state
    on_r = s.op_key == r
    holder = (st == OP_EXEC) | (st == OP_HOLD)
    x_held = jnp.any(holder & on_r & s.op_write)
    waiter = jnp.any((st == OP_WAIT) & on_r)
    ok = ~x_held & ~waiter

    exec_t = s.now + _exec_us(cfg, s, d)
    return s._replace(
        op_state=s.op_state.at[t, k].set(jnp.where(ok, OP_EXEC, OP_WAIT).astype(jnp.int8)),
        op_time=s.op_time.at[t, k].set(
            jnp.where(ok, exec_t, _lock_wait_deadline(s.dyn, s.now))
        ),
        op_enq=s.op_enq.at[t, k].set(s.now),
        first_lock=s.first_lock.at[t, d].min(jnp.where(ok, s.now, INF_US)),
    )


@contextlib.contextmanager
def broken_exclusive_writes():
    """Trace the reference's lock attempt as the control's while inside."""
    saved = handlers._attempt_lock
    handlers._attempt_lock = attempt_lock_shared_writes
    try:
        yield
    finally:
        handlers._attempt_lock = saved


@functools.partial(jax.jit, static_argnums=(0,))
def _fresh_control(cfg, bank, world):
    return ref.run(cfg, bank, init_state_world(cfg, world))


def simulate_control(deploy, bank, cell, device=None):
    """`ref.simulate` with the control's lock attempt."""
    with broken_exclusive_writes():
        return ref.simulate(deploy, bank, cell, device, fresh=_fresh_control)


def control_inputs(config: dict, traffic: dict, seed: int):
    """A sweep of the cell's shape on banks drawn from ``seed`` (the cell's
    own banks are fixed; the control's seeds should differ)."""
    from bench import harness
    from bench.gen import derive_seed, make_bank

    dep = config["deployment"]
    pool = [
        make_bank(config["bank"], dep["terminals"], dep["txns_per_terminal"], derive_seed(seed, r))
        for r in range(len(traffic["bank_seeds"]))
    ]
    return harness.sweep_inputs(config, traffic, pool)


def readings(cell_name: str, seeds, root=ROOT, log=print) -> list:
    """The check's numbers with the control in the program's place: for
    each seed, every world of a sweep of the cell's shape."""
    from bench import harness

    _, config, traffic = harness.load_cell(harness.load_spec(root), cell_name, root)
    out = []
    for seed in seeds:
        inputs = control_inputs(config, traffic, seed)
        t0 = time.perf_counter()
        worlds = []
        for cell, bank in zip(inputs.cells, inputs.banks):
            state, metrics = simulate_control(config["deployment"], bank, cell)
            worlds.append((cell, bank, state, metrics))
        res = harness.check(config, worlds)
        row = {
            "seed": seed,
            "worlds": res["compared"],
            "failed": res["failed"],
            **{k: v["value"] for k, v in res["numbers"].items()},
            "seconds": time.perf_counter() - t0,
        }
        log(json.dumps(row))
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    rows = readings(args.workload, args.seeds)
    names = [k for k in rows[0] if k not in ("seed", "worlds", "failed", "seconds")]
    smallest = {k: min(r[k] for r in rows) for k in names}
    print(json.dumps({"workload": args.workload, "device": jax.devices()[0].device_kind,
                      "smallest": smallest}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
