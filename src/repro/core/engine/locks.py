"""Lock-table primitives: FIFO-fair 2PL over the op arrays.

Lock state is fully derived from the op arrays — record r is X-locked iff
some EXEC/HOLD op writes it, S-locked iff some EXEC/HOLD op reads it — so
there is no separate lock table to keep consistent. These three primitives
are the single source of lock semantics for every step mode: the sequential
handlers call them directly, the branchless omnibus step and the fused
windowed pass reuse `_grant_decision` for the grant set.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.netmodel import INF_US

from repro.core.engine.state import (
    OP_DONE,
    OP_EXEC,
    OP_HOLD,
    OP_NONE,
    OP_WAIT,
    SimConfig,
    SimState,
    _exec_us,
    _lock_wait_deadline,
)


@jax.named_scope("repro/locks")
def _attempt_lock(cfg: SimConfig, s: SimState, t, k) -> SimState:
    """Op (t,k) is at its data source and requests its lock (FIFO-fair).

    Lock state is derived from the op arrays: record r is X-locked iff some
    EXEC/HOLD op writes it, S-locked iff some EXEC/HOLD op reads it. A new
    request must queue behind any existing waiter (fair FIFO, as in the
    MySQL/PG record-lock wait queues the paper's data sources use)."""
    r = s.op_key[t, k]
    w = s.op_write[t, k]
    d = s.op_ds[t, k]
    st = s.op_state
    on_r = s.op_key == r
    holder = (st == OP_EXEC) | (st == OP_HOLD)
    x_held = jnp.any(holder & on_r & s.op_write)
    s_held = jnp.any(holder & on_r & ~s.op_write)
    waiter = jnp.any((st == OP_WAIT) & on_r)
    ok = jnp.where(w, ~x_held & ~s_held, ~x_held) & ~waiter

    exec_t = s.now + _exec_us(cfg, s, d)
    s = s._replace(
        op_state=s.op_state.at[t, k].set(
            jnp.where(ok, OP_EXEC, OP_WAIT).astype(jnp.int8)
        ),
        op_time=s.op_time.at[t, k].set(
            jnp.where(ok, exec_t, _lock_wait_deadline(s.dyn, s.now))
        ),
        op_enq=s.op_enq.at[t, k].set(s.now),
        first_lock=s.first_lock.at[t, d].min(jnp.where(ok, s.now, INF_US)),
    )
    return s


@jax.named_scope("repro/locks")
def _grant_decision(held, rel_keys, flat_state, flat_key, flat_write, flat_enq):
    """FIFO-compatible grant set for a release's keys: [T*K] `granted` mask.

    held/rel_keys: [K] the releasing row's held mask + keys (non-held = -2);
    flat_*: the [T*K] post-cancel op views. Grant rules: all shared waiters
    enqueued before the earliest exclusive waiter (unless an exclusive holder
    remains), else the earliest exclusive waiter (if no holder of either mode
    remains). Single source for the sequential handler, the branchless
    omnibus step and the fused windowed pass — the four step modes must agree
    bitwise on grant fairness.
    """
    holderf = (flat_state == OP_EXEC) | (flat_state == OP_HOLD)
    waitf = flat_state == OP_WAIT
    eq = flat_key[None, :] == rel_keys[:, None]  # [K, T*K]
    rem_x = jnp.any(eq & holderf[None, :] & flat_write[None, :], axis=1)
    rem_s = jnp.any(eq & holderf[None, :] & ~flat_write[None, :], axis=1)
    M = held[:, None] & eq & waitf[None, :]
    exq = jnp.where(M & flat_write[None, :], flat_enq[None, :], INF_US)
    ex_min = jnp.min(exq, axis=1)  # [K]
    enq = jnp.where(M, flat_enq[None, :], INF_US)
    grant_s = M & ~flat_write[None, :] & (enq < ex_min[:, None]) & ~rem_x[:, None]
    any_s = jnp.any(grant_s, axis=1)
    x_row = jnp.argmin(exq, axis=1)
    grant_x_ok = (ex_min < INF_US) & ~any_s & ~rem_x & ~rem_s
    grant_x = (
        jax.nn.one_hot(x_row, M.shape[1], dtype=bool)
        & grant_x_ok[:, None]
        & M
        & flat_write[None, :]
    )
    return jnp.any(grant_s | grant_x, axis=0)  # [T*K]


@jax.named_scope("repro/locks")
def _release_and_grant(cfg: SimConfig, s: SimState, t, d) -> SimState:
    """Release every lock txn t holds at data source d, cancel its remaining
    ops there, and grant waiting requests FIFO-compatibly."""
    K = cfg.max_ops
    T = cfg.terminals
    row_state = s.op_state[t]
    mine = (row_state != OP_NONE) & (s.op_ds[t] == d.astype(s.op_ds.dtype))
    held = mine & ((row_state == OP_EXEC) | (row_state == OP_HOLD))
    rel_keys = jnp.where(held, s.op_key[t], -2)  # -2 matches nothing

    # cancel all my ops at d (this *is* the release: lock state is op-derived)
    s = s._replace(
        op_state=s.op_state.at[t].set(
            jnp.where(mine, OP_DONE, row_state).astype(jnp.int8)
        ),
        op_time=s.op_time.at[t].set(jnp.where(mine, INF_US, s.op_time[t])),
    )

    # ---- grant waiters on the released keys (post-release views) ----------
    flat_state = s.op_state.reshape(-1)
    flat_key = s.op_key.reshape(-1)
    flat_write = s.op_write.reshape(-1)
    flat_enq = s.op_enq.reshape(-1)
    flat_ds = s.op_ds.reshape(-1)
    granted = _grant_decision(
        held, rel_keys, flat_state, flat_key, flat_write, flat_enq
    )

    exec_t = s.now + _exec_us(cfg, s, flat_ds.astype(jnp.int32))
    new_fstate = jnp.where(granted, OP_EXEC, flat_state).astype(jnp.int8)
    new_ftime = jnp.where(granted, exec_t, s.op_time.reshape(-1))
    s = s._replace(
        op_state=new_fstate.reshape(T, K), op_time=new_ftime.reshape(T, K)
    )
    # first-lock bookkeeping for grantees
    gt = jnp.arange(T * K, dtype=jnp.int32) // K
    fl = s.first_lock.reshape(-1)
    idx = jnp.where(granted, gt * cfg.num_ds + flat_ds.astype(jnp.int32), T * cfg.num_ds)
    fl_pad = jnp.concatenate([fl, jnp.full((1,), INF_US, jnp.int32)])
    fl_pad = fl_pad.at[idx].min(jnp.where(granted, s.now, INF_US))
    s = s._replace(first_lock=fl_pad[: T * cfg.num_ds].reshape(T, cfg.num_ds))
    return s
