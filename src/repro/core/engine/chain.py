"""Two-pass chain admission: follow-ups across the scheduling fence.

The windowed-drain planner (`window._window_plan`) used to end every window
at the first event whose handler schedules work inside the window's time
range (`scheduled` stopper) — on tie-heavy geo workloads the dominant
terminator by far. This module is the second pass that absorbs those
fence stops: each op candidate that gets (or already holds) a lock grant
spawns up to `CHAIN_DEPTH` *virtual exec completions* (its own statement,
then each next queued same-DS statement the sequential chain handler would
un-queue), and each prepare command spawns its log-flush follow-up. The
virtual entities merge with the candidates into one strict
(time, flat index, is-follow-up) order; a shared running-min prefix scan
over that entity space decides admission for candidates and follow-ups
alike, and every admitted follow-up is materialized by the apply pass with
exactly the iteration number (hash salt) and timestamp the sequential loop
would have assigned.

Entity layout throughout: ``[W candidates | CHAIN_DEPTH exec blocks of W
(generation-major) | W prepare-flush]``, ``E = W + CHAIN_DEPTH*W + W``.

Everything here is W-sized gathers and [E, E] elementwise reductions —
bitwise-identical between the map and lockstep plan routes, which both
consult only candidate slots and entity keys.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.netmodel import INF_US
from repro.core.engine.state import (
    N_STOP_REASONS,
    OP_EXEC,
    OP_WAIT,
    SUB_PREP_CMD,
    _SALT_MUL,
    SimState,
    _delay_salted,
    _lock_wait_deadline,
    _mw_send,
    _round_done_transition,
)

# Chain-admission depth: up to this many generations of virtual exec
# completions per op candidate join the window (a granted arrival's own
# completion is generation 1; each chained statement's completion one more).
# Longer chains split across iterations via the running-min rule, exactly
# like a window hitting PLAN_CAP.
CHAIN_DEPTH = 3

# stop-reason codes — indices into SimState.win_stops / state.STOP_REASONS
(
    STOP_HORIZON,
    STOP_NONDRAINABLE,
    STOP_SCHEDULED,
    STOP_LOCK_KEY,
    STOP_DM_ROW,
    STOP_DM_COL,
    STOP_REL_OP,
    STOP_CAP,
    STOP_FAULT,
    STOP_SCHED_CHAIN,
) = range(N_STOP_REASONS)

i32 = jnp.int32


class _PlanVals(NamedTuple):
    """Everything the masked window pass (and the fused lockstep pass) needs:
    per-event ranks/salts, pre-state categories, the per-event values each
    drainable handler would compute sequentially, the per-fan-in decision
    tensors, and the prefix outcome. Produced by `window._window_plan` (which
    re-exports this type), consumed by `apply._apply_window` and
    `fused._omni_window`."""

    # window candidates: the W lex-smallest events, rank order. The decoded
    # coordinates are carried here so the applier's release pass reads the
    # same decode the planner's waiter probe used (single source of truth).
    cand_i: jax.Array  # [W] flat event indices
    cand_is_sub: jax.Array  # [W] candidate is a subtxn slot
    cand_t_sub: jax.Array  # [W] its terminal (0 when not a sub slot)
    cand_d_sub: jax.Array  # [W] its DS column (0 when not a sub slot)
    # ranks of the flat (time, index) order + per-event iteration numbers
    pos_term: jax.Array  # [T]
    pos_sub: jax.Array  # [T,D]
    pos_op: jax.Array  # [T,K]
    iters_term: jax.Array
    iters_sub: jax.Array
    iters_op: jax.Array
    # pre-state event categories
    cat_log: jax.Array
    cat_sched: jax.Array
    cat_prep: jax.Array
    cat_preparing: jax.Array
    cat_commit: jax.Array
    cat_ack: jax.Array
    cat_prog: jax.Array
    dm_cat: jax.Array
    f_cat: jax.Array
    cat_arr: jax.Array
    cat_exec: jax.Array
    # op events: lock decisions + chained statements
    ok: jax.Array  # [T,K] lock grant for an arrival at this slot
    arr_state: jax.Array
    arr_time: jax.Array
    has_next: jax.Array
    tgt3: jax.Array  # [T,K,K] source op chains to target op
    ok_chain: jax.Array
    chain_state: jax.Array
    chain_time: jax.Array
    # exec round completions
    time_rd: jax.Array  # [T,D]
    new_sub_state: jax.Array
    new_sub_time: jax.Array
    aborting_td: jax.Array
    # DM dispatch + DS-side 2PC legs
    arrival_td: jax.Array
    eff_arrival_td: jax.Array  # [T,D] first-statement fire time (TIGA deadline)
    fast_disp_td: jax.Array  # [T,D] TIGA in-slack flag at dispatch
    has_c: jax.Array
    first_c: jax.Array
    prep_time: jax.Array
    vote_t: jax.Array
    # DM fan-ins, slot-accurate: per-fan-in decision tensors on the
    # cumulative row view (pre-state + earlier in-window self-updates)
    dm_self: jax.Array  # [T,D] the fan-in's own-slot state write
    ready_chiller_j: jax.Array  # [T,D] (j = the fan-in's sub column)
    advance_j: jax.Array
    send_c_j: jax.Array
    send_p_j: jax.Array
    log_t_j: jax.Array
    done_ack_j: jax.Array
    done_abk_j: jax.Array
    dt_commit3: jax.Array  # [T,D,D] (fan-in j commits to every DS d)
    dt_prepare3: jax.Array
    log_term_j: jax.Array  # [T,D]
    # terminal commit-log flush broadcast times
    dt_log: jax.Array  # [T,D]
    # DS finish (commit apply / peer-abort release)
    ack_t: jax.Array
    rel_waiter_td: jax.Array
    # chained follow-up entities (two-pass plan). Exec-chain entities live at
    # [W, CHAIN_DEPTH]: entity (r, g) is the g-th virtual exec completion of
    # op candidate r's chain; prepare-flush entities at [W].
    fu_win: jax.Array  # [W,G] admitted exec-chain follow-ups
    fu_term: jax.Array  # [W] seed terminal (op candidates; 0 elsewhere)
    fu_d: jax.Array  # [W] seed DS column
    fu_u: jax.Array  # [W,G] entity completion times u_g
    fu_comp_k: jax.Array  # [W,G] op column the entity completes (-> HOLD)
    fu_att_has: jax.Array  # [W,G] entity attempts a next queued statement
    fu_att_k: jax.Array  # [W,G] that statement's op column
    fu_att_ok: jax.Array  # [W,G] its lock grant
    fu_att_state: jax.Array  # [W,G] OP_EXEC / OP_WAIT
    fu_att_time: jax.Array  # [W,G] grant exec time / wait deadline
    fu_rd: jax.Array  # [W,G] entity completes the round (LEL accounting)
    fu_rd_wr: jax.Array  # [W,G] ... and the sub-slot write lands (~aborting)
    fu_rd_state: jax.Array  # [W,G]
    fu_rd_time: jax.Array  # [W,G]
    pfu_win: jax.Array  # [W] admitted prepare-flush follow-ups
    pfu_vote_t: jax.Array  # [W] their salted vote send time
    n_chained: jax.Array  # scalar: follow-up entities admitted this window
    # prefix outcome
    pinned_term: jax.Array
    pinned_sub: jax.Array
    pinned_op: jax.Array
    win_term: jax.Array  # [T] window membership
    win_sub: jax.Array  # [T,D]
    win_op: jax.Array  # [T,K]
    win_hb: jax.Array  # [D] in-window heartbeat probes (zeros when F == 0)
    hb_fire: jax.Array  # [D] probe fires (target unreachable at its slot time)
    n_win: jax.Array  # scalar: events in the maximal window
    use: jax.Array  # scalar: window holds >= 2 events
    t_last: jax.Array  # scalar: timestamp of the window's last event
    stop_code: jax.Array  # scalar: STOP_* reason of the event that ended it


class _ChainEnts(NamedTuple):
    """Virtual follow-up entities of one window plan (pre-admission)."""

    e_c: jax.Array  # [W] per-statement exec cost of the seed's DS
    u_all: jax.Array  # [W,G+1] completion times u_1..u_{G+1}
    u: jax.Array  # [W,G] = u_all[:, :G]
    arr_c: jax.Array  # [W] candidate is a statement arrival
    chn_c: jax.Array  # [W] candidate is a chaining exec completion
    seed_ca: jax.Array  # [W] granted arrival seed
    ca_m: jax.Array  # [W,1] seed_ca broadcast column
    att_k: jax.Array  # [W,G] op column entity g attempts
    att_has: jax.Array  # [W,G] that attempt exists
    att_ok_t: jax.Array  # [W,G] its lock grant
    comp_k: jax.Array  # [W,G] op column entity g completes
    fu_idx: jax.Array  # [W,G] flat slot ids of the completions
    fu_valid: jax.Array  # [W,G] entity exists and is order-safe
    pre_mis: jax.Array  # [W] misordered first child -> conflict the seed
    fu_conf_child: jax.Array  # [W,G] misordered child conflicts entity g
    prep_t_c: jax.Array  # [W] prepare-flush follow-up time
    pfu_valid: jax.Array  # [W] prepare-flush entity exists


@jax.named_scope("repro/chain")
def chain_entities(
    dyn, sst, exec_t, evt_op, cand_t, cand_i, t_w1,
    is_op_c, is_sub_c, op_flat_c, sub_flat_c, t_op_c, k_op_c,
    cat_arr, do_chain_cat, ok_self_c, ok_tgt, tgt_k, tgt_ex,
    T: int, D: int, K: int,
) -> _ChainEnts:
    """Build the virtual follow-up entities of each op/prepare candidate.

    Each op candidate that gets (or already holds) a grant spawns up to
    CHAIN_DEPTH virtual exec completions: entity g completes comp_k[g] at
    u_g = t_seed + g * exec_us and then attempts the next queued statement
    (CA seeds — granted arrivals — complete their own slot first; CX seeds
    — chaining exec completions — start at their queue target). All times
    here are salt-free, so merged ranks are computable before any salted
    value; the grants query the pre-state lock table, exact because every
    touched key enters the first-touch dup rule.
    """
    G = CHAIN_DEPTH
    W = cand_t.shape[0]
    e_c = (exec_t - evt_op).reshape(-1)[op_flat_c]  # [W] per-statement cost
    gg = jnp.arange(1, G + 2, dtype=i32)
    u_all = cand_t[:, None] + gg[None, :] * e_c[:, None]  # [W,G+1]: u_1..u_{G+1}
    u = u_all[:, :G]
    arr_c = is_op_c & cat_arr.reshape(-1)[op_flat_c]
    chn_c = is_op_c & do_chain_cat.reshape(-1)[op_flat_c]
    seed_ca = arr_c & ok_self_c
    seed_cx = chn_c & ok_tgt[:, 0]
    ca_m = seed_ca[:, None]
    # entity g attempts target column j = g-1 (CA) / g (CX) and completes
    # the column its parent attempted (CA entity 1 completes the seed's own
    # statement; CX entity 1 completes the seed's queue target)
    att_k = jnp.where(ca_m, tgt_k[:, :G], tgt_k[:, 1:])  # [W,G]
    att_has = jnp.where(ca_m, tgt_ex[:, :G], tgt_ex[:, 1:])
    att_ok_t = jnp.where(ca_m, ok_tgt[:, :G], ok_tgt[:, 1:])
    comp_k = jnp.where(
        ca_m,
        jnp.concatenate([k_op_c[:, None], tgt_k[:, : G - 1]], axis=1),
        tgt_k[:, :G],
    )  # [W,G]
    # raw validity chain: seed admissible, every prior attempt granted, and
    # the completion time strictly inside the candidate time range
    valid_list = [(seed_ca | seed_cx) & (u[:, 0] < t_w1)]
    for g in range(1, G):
        valid_list.append(
            valid_list[-1]
            & att_has[:, g - 1]
            & att_ok_t[:, g - 1]
            & (u[:, g] < t_w1)
        )
    valid0 = jnp.stack(valid_list, axis=1)  # [W,G]
    # order guard: each virtual completion must sort strictly after its
    # parent under the (time, flat index, is-follow-up) key — zero-exec-cost
    # edges can invert it. A misordered child is dropped from the plan and
    # its parent marked conflicted, so the window stops before the parent
    # (the child does not exist sequentially until the parent runs).
    fu_idx = (T + T * D) + t_op_c[:, None] * K + comp_k  # [W,G] flat slot ids
    par_t = jnp.concatenate([cand_t[:, None], u[:, : G - 1]], axis=1)
    par_idx = jnp.concatenate([cand_i[:, None], fu_idx[:, : G - 1]], axis=1)
    par_fu = jnp.concatenate(
        [jnp.zeros((W, 1), bool), jnp.ones((W, G - 1), bool)], axis=1
    )
    ord_ok = (par_t < u) | (
        (par_t == u) & ((par_idx < fu_idx) | ((par_idx == fu_idx) & ~par_fu))
    )
    fu_ord = jnp.cumprod(ord_ok.astype(i32), axis=1).astype(bool)
    fu_valid = valid0 & fu_ord
    ord_pref = jnp.concatenate([jnp.ones((W, 1), bool), fu_ord[:, :-1]], axis=1)
    mis = valid0 & ord_pref & ~ord_ok
    pre_mis = mis[:, 0]  # misordered first child -> conflict the candidate
    fu_conf_child = jnp.concatenate(
        [mis[:, 1:], jnp.zeros((W, 1), bool)], axis=1
    )  # misordered child of entity g+1 -> conflict entity g+1's slot
    # prepare-flush follow-up: PREP_CMD -> PREPARING fires log_flush_us
    # later on the same slot (salt-free time), then sends the salted vote
    prep_cat_c = is_sub_c & (sst == SUB_PREP_CMD).reshape(-1)[sub_flat_c]
    prep_t_c = cand_t + dyn.log_flush_us
    pfu_valid = prep_cat_c & (prep_t_c < t_w1)
    return _ChainEnts(
        e_c=e_c, u_all=u_all, u=u, arr_c=arr_c, chn_c=chn_c,
        seed_ca=seed_ca, ca_m=ca_m, att_k=att_k, att_has=att_has,
        att_ok_t=att_ok_t, comp_k=comp_k, fu_idx=fu_idx, fu_valid=fu_valid,
        pre_mis=pre_mis, fu_conf_child=fu_conf_child, prep_t_c=prep_t_c,
        pfu_valid=pfu_valid,
    )


class _ChainRanks(NamedTuple):
    """Merged (candidate + follow-up) rank order of one window plan."""

    ent_t: jax.Array  # [E] entity times (invalid keyed past every real slot)
    ent_b: jax.Array  # [E,E] strict order: entity a processed before b
    mrank: jax.Array  # [E] merged ranks (a permutation)
    mrank_pre: jax.Array  # [W]
    mrank_fu: jax.Array  # [W,G]
    mrank_pfu: jax.Array  # [W]


@jax.named_scope("repro/chain")
def merged_ranks(cand_t, cand_i, c: _ChainEnts, BIG, maxi) -> _ChainRanks:
    """Candidates + follow-ups in one (time, flat index, is-follow-up)
    order. Keys are unique (invalid follow-ups are keyed past every real
    slot), so B is a strict total order and mrank a permutation; admitted
    follow-ups shift the sequential iteration number (hash salt) of every
    later candidate."""
    G = CHAIN_DEPTH
    W = cand_t.shape[0]
    NFU = G * W + W
    fuv_f = c.fu_valid.T.reshape(-1)  # g-major [G*W]
    ent_valid_fu = jnp.concatenate([fuv_f, c.pfu_valid])
    ord_f = jnp.arange(NFU, dtype=i32)
    ent_t_fu = jnp.where(
        ent_valid_fu, jnp.concatenate([c.u.T.reshape(-1), c.prep_t_c]), maxi
    )
    ent_idx_fu = jnp.where(
        ent_valid_fu,
        jnp.concatenate([c.fu_idx.T.reshape(-1), cand_i]),
        BIG + ord_f,
    )
    ent_t = jnp.concatenate([cand_t, ent_t_fu])
    ent_idx = jnp.concatenate([cand_i, ent_idx_fu])
    ent_fu = jnp.concatenate([jnp.zeros((W,), bool), jnp.ones((NFU,), bool)])
    ent_b = (ent_t[:, None] < ent_t[None, :]) | (
        (ent_t[:, None] == ent_t[None, :])
        & (
            (ent_idx[:, None] < ent_idx[None, :])
            | (
                (ent_idx[:, None] == ent_idx[None, :])
                & (~ent_fu[:, None] & ent_fu[None, :])
            )
        )
    )  # [E,E]: entity a processed before entity b
    mrank = jnp.sum(ent_b, axis=0, dtype=i32)
    return _ChainRanks(
        ent_t=ent_t,
        ent_b=ent_b,
        mrank=mrank,
        mrank_pre=mrank[:W],
        mrank_fu=mrank[W : W + G * W].reshape(G, W).T,  # [W,G]
        mrank_pfu=mrank[W + G * W :],
    )


class _ChainEffects(NamedTuple):
    """What each admitted follow-up writes, with the salt/timestamp it
    would have had sequentially."""

    att_state_fu: jax.Array  # [W,G] OP_EXEC / OP_WAIT at the attempt target
    att_time_fu: jax.Array  # [W,G] grant exec time / wait deadline
    rd_fu: jax.Array  # [W,G] chain ends -> round completes at (t, d)
    abort_c2: jax.Array  # [W] seed's sub slot is peer-aborting
    rd_state_fu: jax.Array  # [W,G]
    rd_time_fu: jax.Array  # [W,G]
    rd_wr_fu: jax.Array  # [W,G] round write lands (~aborting)
    vote2: jax.Array  # [W] salted vote send time of the prepare flush


@jax.named_scope("repro/chain")
def chain_effects(
    s: SimState, F: int, c: _ChainEnts,
    t_op_c, d_op_c, t_sub_c, d_sub_c, iters_fu, iters_pfu,
    is_final_td, aborting_td, centr_t, fast_t,
) -> _ChainEffects:
    u = c.u
    att_state_fu = jnp.where(c.att_ok_t, OP_EXEC, OP_WAIT)
    att_time_fu = jnp.where(
        c.att_ok_t, u + c.e_c[:, None], _lock_wait_deadline(s.dyn, u)
    )
    rd_fu = c.fu_valid & ~c.att_has  # chain ends -> round completes at (t, d)
    fin_c = is_final_td[t_op_c, d_op_c]
    abort_c2 = aborting_td[t_op_c, d_op_c]
    if F:
        rb2, rt2 = _mw_send(
            s, s.on_repl[t_op_c, d_op_c][:, None], d_op_c[:, None], u
        )
    else:
        rb2, rt2 = u, s.tau_true[d_op_c][:, None]
    reply2 = rb2 + _delay_salted(
        s.jitter_milli, rt2, iters_fu * _SALT_MUL + jnp.int32(37)
    )
    prep2 = u + s.dyn.lan_rtt_us + s.dyn.log_flush_us
    local2 = u + s.dyn.log_flush_us
    rd_state_fu, rd_time_fu = _round_done_transition(
        s.dyn,
        fin_c[:, None],
        centr_t[t_op_c][:, None],
        reply2,
        prep2,
        local2,
        fast_t[t_op_c][:, None],
    )
    rd_wr_fu = rd_fu & ~abort_c2[:, None]
    vsalt2 = iters_pfu * _SALT_MUL + jnp.int32(43)
    if F:
        vb2, vt2 = _mw_send(s, s.on_repl[t_sub_c, d_sub_c], d_sub_c, c.prep_t_c)
    else:
        vb2, vt2 = c.prep_t_c, s.tau_true[d_sub_c]
    vote2 = vb2 + _delay_salted(s.jitter_milli, vt2, vsalt2)
    return _ChainEffects(
        att_state_fu=att_state_fu, att_time_fu=att_time_fu, rd_fu=rd_fu,
        abort_c2=abort_c2, rd_state_fu=rd_state_fu, rd_time_fu=rd_time_fu,
        rd_wr_fu=rd_wr_fu, vote2=vote2,
    )


class _Admission(NamedTuple):
    """Prefix outcome of the shared entity-space scan."""

    n_win: jax.Array  # scalar: entities (== sequential events) admitted
    use: jax.Array  # scalar: window holds >= 2 events
    t_last: jax.Array  # scalar: timestamp of the window's last entity
    stop_code: jax.Array  # scalar STOP_* reason
    win_term: jax.Array  # [T]
    win_sub: jax.Array  # [T,D]
    win_op: jax.Array  # [T,K]
    win_hb: jax.Array  # [D] (zeros when F == 0)
    fu_win: jax.Array  # [W,G] admitted exec-chain follow-ups
    pfu_win: jax.Array  # [W] admitted prepare-flush follow-ups
    n_chained: jax.Array  # scalar: follow-up entities admitted


@jax.named_scope("repro/chain")
def entity_admission(
    dyn, c: _ChainEnts, r: _ChainRanks, eff: _ChainEffects,
    conf_cand_base, code_cand, n_cand, fu_dup, hit_all, horizon_i, maxi,
    T: int, D: int, K: int, M0: int, F: int,
) -> _Admission:
    """Shared entity-space prefix scan (both plan routes).

    Candidates and chain entities merge into one strict (time, flat index,
    is-follow-up) order; the running-min rule runs over the [E, E] strict
    order matrix, so admitted follow-ups absorb the "scheduled" events
    their parents used to fence on.
    """
    G = CHAIN_DEPTH
    W = conf_cand_base.shape[0]
    E = W + G * W + W
    conf_cand = conf_cand_base | c.pre_mis
    # absorb override: a seed whose first follow-up (or prepare flush) was
    # admitted no longer schedules anything itself — the entity carries the
    # scheduled time forward (INF when the chain keeps going)
    n_pre = jnp.where(c.fu_valid[:, 0] | c.pfu_valid, INF_US, n_cand)
    child_valid = jnp.concatenate(
        [c.fu_valid[:, 1:], jnp.zeros((W, 1), bool)], axis=1
    )
    n_fu = jnp.where(
        c.att_has,
        jnp.where(
            c.att_ok_t,
            jnp.where(child_valid, INF_US, c.u_all[:, 1:]),
            _lock_wait_deadline(dyn, c.u),
        ),
        jnp.where(eff.abort_c2[:, None], INF_US, eff.rd_time_fu),
    )
    n_fu = jnp.where(c.fu_valid, n_fu, INF_US)
    n_pfu = jnp.where(c.pfu_valid, eff.vote2, INF_US)
    ent_n = jnp.concatenate([n_pre, n_fu.T.reshape(-1), n_pfu])
    fu_code = jnp.where(
        ~c.fu_valid,
        STOP_CAP,
        jnp.where(
            c.u >= horizon_i,
            STOP_HORIZON,
            jnp.where(fu_dup, STOP_LOCK_KEY, STOP_SCHED_CHAIN),
        ),
    ).astype(i32)
    pfu_code = jnp.where(
        ~c.pfu_valid,
        STOP_CAP,
        jnp.where(c.prep_t_c >= horizon_i, STOP_HORIZON, STOP_SCHED_CHAIN),
    ).astype(i32)
    ent_code = jnp.concatenate([code_cand, fu_code.T.reshape(-1), pfu_code])
    ent_conf = jnp.concatenate(
        [
            conf_cand,
            (fu_dup | c.fu_conf_child).T.reshape(-1),
            jnp.zeros((W,), bool),
        ]
    )
    einc = r.ent_b | jnp.eye(E, dtype=bool)
    cmin_e = jnp.min(jnp.where(einc, ent_n[:, None], maxi), axis=0)
    good = (cmin_e > r.ent_t) & (r.ent_t < horizon_i) & ~ent_conf
    E_i = jnp.int32(E)
    n_win = jnp.min(jnp.where(~good, r.mrank, E_i))
    t_last = jnp.max(jnp.where(r.mrank < n_win, r.ent_t, 0))
    stop_code = jnp.where(
        n_win >= E_i,
        jnp.int32(STOP_CAP),
        jnp.sum(jnp.where(r.mrank == n_win, ent_code, 0)),
    ).astype(i32)
    adm = r.mrank < n_win
    win_flat = jnp.any(hit_all & adm[:W, None], axis=0)
    return _Admission(
        n_win=n_win,
        use=n_win >= 2,
        t_last=t_last,
        stop_code=stop_code,
        win_term=win_flat[:T],
        win_sub=win_flat[T : T + T * D].reshape(T, D),
        win_op=win_flat[T + T * D : M0].reshape(T, K),
        win_hb=win_flat[M0 + F :] if F else jnp.zeros((D,), bool),
        fu_win=adm[W : W + G * W].reshape(G, W).T,  # [W,G]
        pfu_win=adm[W + G * W :],
        n_chained=jnp.sum(adm[W:], dtype=i32),
    )
