"""Seed-reference step mode: single earliest event through a 12-way switch."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.workloads import Bank

from repro.core.engine.faults import _h_fault, _h_hb
from repro.core.engine.handlers import (
    _h_start_txn,
    _h_send_commits,
    _h_op_arrive,
    _h_op_timeout,
    _h_op_exec_done,
    _h_sub_dispatch,
    _h_dm_round_in,
    _h_ds_prep_cmd,
    _h_ds_prepared,
    _h_ds_finish,
    _h_dm_fin,
    _h_noop,
)
from repro.core.engine.state import (
    OP_ENROUTE,
    OP_WAIT,
    OP_EXEC,
    SUB_SCHED,
    SUB_ROUND_REPLY,
    SUB_PREP_CMD,
    SUB_PREPARING,
    SUB_VOTE,
    SUB_COMMIT_CMD,
    SUB_ACK,
    SUB_LOCAL_COMMIT,
    SUB_ABORT_PEER,
    SUB_ABORT_ACK,
    T_IDLE,
    T_COMMIT_LOG,
    SimConfig,
    SimState,
    _times_flat,
)

# handler ids — state-twin events (reply/vote, the three lock-releasing DS
# events, the two completion acks) share one fused branch each, so the
# dispatch switch compiles 12 bodies instead of 16 (14 with fault injection)
# and lockstep (vmap) lanes execute that much less per step
(
    H_START,
    H_SEND_COMMITS,
    H_OP_ARRIVE,
    H_OP_TIMEOUT,
    H_OP_EXEC,
    H_SUB_DISPATCH,
    H_DM_ROUND,
    H_DS_PREP_CMD,
    H_DS_PREPARED,
    H_DS_FINISH,
    H_DM_FIN,
    H_NOOP,
    H_FAULT,
    H_HB,
) = range(14)

_SUB_HANDLER = np.full(18, H_NOOP, np.int32)
_SUB_HANDLER[SUB_SCHED] = H_SUB_DISPATCH
_SUB_HANDLER[SUB_ROUND_REPLY] = H_DM_ROUND
_SUB_HANDLER[SUB_PREP_CMD] = H_DS_PREP_CMD
_SUB_HANDLER[SUB_PREPARING] = H_DS_PREPARED
_SUB_HANDLER[SUB_VOTE] = H_DM_ROUND
_SUB_HANDLER[SUB_COMMIT_CMD] = H_DS_FINISH
_SUB_HANDLER[SUB_ACK] = H_DM_FIN
_SUB_HANDLER[SUB_LOCAL_COMMIT] = H_DS_FINISH
_SUB_HANDLER[SUB_ABORT_PEER] = H_DS_FINISH
_SUB_HANDLER[SUB_ABORT_ACK] = H_DM_FIN

_OP_HANDLER = np.full(8, H_NOOP, np.int32)
_OP_HANDLER[OP_ENROUTE] = H_OP_ARRIVE
_OP_HANDLER[OP_WAIT] = H_OP_TIMEOUT
_OP_HANDLER[OP_EXEC] = H_OP_EXEC

_TERM_HANDLER = np.full(5, H_NOOP, np.int32)
_TERM_HANDLER[T_IDLE] = H_START
_TERM_HANDLER[T_COMMIT_LOG] = H_SEND_COMMITS

@jax.named_scope("repro/apply")
def _step(cfg: SimConfig, bank: Bank, s: SimState) -> SimState:
    """Process the single earliest event (one fused argmin over all queues).

    The seed-reference step mode, selected by ``SimConfig(drain=False,
    lockstep=False)``: every other mode must stay bitwise-identical to this
    one. The concatenated view orders terminal < subtxn < op < fault < hb
    events, and flat argmin picks the first occurrence — the exact tie-break
    order of the original three-scan picker, at a third of the reduction
    cost. The fault/heartbeat tail sections exist only when
    ``cfg.max_faults > 0``; a fault-free config compiles the tail-free
    program unchanged.
    """
    T, D, K, F = cfg.terminals, cfg.num_ds, cfg.max_ops, cfg.max_faults
    M0 = T + T * D + T * K
    with jax.named_scope("repro/pick"):
        flat = _times_flat(s)
        i = jnp.argmin(flat).astype(jnp.int32)
        t_now = flat[i]
        is_term = i < T
        is_sub = ~is_term & (i < T + T * D)
        j_sub = i - T
        j_op = i - T - T * D
        t = jnp.where(is_term, i, jnp.where(is_sub, j_sub // D, j_op // K))
        idx = jnp.where(is_sub, j_sub % D, jnp.where(is_term, 0, j_op % K))
        if F:
            is_fault = (i >= M0) & (i < M0 + F)
            is_hb = i >= M0 + F
            is_tail = is_fault | is_hb
            # tail events carry their own index in `t` (fault row / DS id);
            # clamp the row used for the state-table lookups below
            t = jnp.where(is_fault, i - M0, jnp.where(is_hb, i - M0 - F, t))
            t_look = jnp.where(is_tail, 0, t)
        else:
            t_look = t

    sub_h = jnp.asarray(_SUB_HANDLER)[s.sub_state[t_look, jnp.minimum(idx, D - 1)]]
    op_h = jnp.asarray(_OP_HANDLER)[s.op_state[t_look, jnp.minimum(idx, K - 1)]]
    term_h = jnp.asarray(_TERM_HANDLER)[jnp.minimum(s.phase[t_look], 4)]
    hid = jnp.where(is_term, term_h, jnp.where(is_sub, sub_h, op_h))
    if F:
        hid = jnp.where(is_fault, H_FAULT, jnp.where(is_hb, H_HB, hid))

    s = s._replace(now=t_now, iters=s.iters + 1)

    handlers = [
        _h_start_txn,
        _h_send_commits,
        _h_op_arrive,
        _h_op_timeout,
        _h_op_exec_done,
        _h_sub_dispatch,
        _h_dm_round_in,
        _h_ds_prep_cmd,
        _h_ds_prepared,
        _h_ds_finish,
        _h_dm_fin,
        _h_noop,
    ]
    if F:
        handlers += [_h_fault, _h_hb]
    branches = [lambda ss, tt, ii, h=h: h(cfg, bank, ss, tt, ii) for h in handlers]
    return jax.lax.switch(hid, branches, s, t, idx)
