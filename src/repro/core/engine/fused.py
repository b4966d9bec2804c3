"""Fused plan+omnibus windowed drain — the lockstep (vmap) hot path.

The pre-PR-5 `_omni_window` computed the window plan, materialized the whole
window, ran the branchless single-event `_omni_step` *as well*, and merged
the two full states with a per-leaf select — every heavy kernel traced
twice, every `SimState` leaf written twice and selected once, each
iteration. `_omni_step` cannot be cond-ed away under vmap (every branch of a
`lax.cond` executes per iteration anyway), so lockstep lanes paid plan+step
on every trip.

This module applies the PR-2 fusion trick to the plan itself: ONE
straight-line masked pass per iteration. The shared `window._window_plan`
already computes, per event slot, everything each drainable handler would —
lock decisions, chained statements, round-done transitions, per-fan-in DM
decisions — so the single-event case is just the rank-0 singleton of the
same masked write pass (`window._apply_window` with window-OR-single-event
masks). Only the *non-drainable* categories (txn start with admission +
hot-table claim, lock-wait timeout with abort fan-out, round advance /
chiller stage-2, txn-completing ack, release with queued waiters, noop)
need their own handlers; they are appended as identity-when-off row writes
on the scalar rank-0 event, exactly `_omni_step`'s masked-delta style, and
their release footprint is folded INTO the shared pass (`xcancel`/`xlel`/
`xcommit`) so the hotspot Eq.(4) kernel is traced exactly once per
iteration. Heavy kernels per iteration: one batched lock decision, one
chain resolution, one DM decision tensor, one hotspot release update, one
hot-table claim + admission lookup, one grant matrix, one stagger forecast,
one EWMA chain — each gated by window-OR-single-event masks.

Bitwise-identical to the other three step modes (asserted across presets,
jitters and abort-heavy workloads in tests/core/test_engine_batch.py), and
window formation — including the drained/windows/win_stops telemetry —
matches `_drain_step` exactly: both share `_window_plan` and the
`_drainable_due` pre-check.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hotspot as hs_mod
from repro.core import scheduler as sched
from repro.core.netmodel import INF_US, _hash_u32, ewma_update
from repro.core.workloads import Bank

from repro.core.engine.faults import _fault_event, _hb_event
from repro.core.engine.handlers import _grant_decision, _stagger
from repro.core.engine.state import (
    CAUSE_NONE,
    CAUSE_TIMEOUT,
    CAUSE_ADMISSION,
    CAUSE_CRASH,
    CAUSE_EXHAUSTED,
    N_STOP_REASONS,
    OP_NONE,
    OP_PENDING,
    OP_ENROUTE,
    OP_WAIT,
    OP_EXEC,
    OP_HOLD,
    SUB_NONE,
    SUB_SCHED,
    SUB_ROUND_REPLY,
    SUB_ROUND_AT_DM,
    SUB_WAIT_ROUND,
    SUB_CHILLER_WAIT,
    SUB_VOTE,
    SUB_VOTED,
    SUB_COMMIT_CMD,
    SUB_ACK,
    SUB_LOCAL_COMMIT,
    SUB_DONE,
    SUB_ABORT_PEER,
    SUB_ABORT_ACK,
    SUB_ABORTED,
    T_IDLE,
    T_ACTIVE,
    T_COMMIT_LOG,
    T_ABORT_WAIT,
    _SALT_MUL,
    SimConfig,
    SimState,
    _delay_salted,
    _ds_send,
    _exec_us,
    _hist_bin,
    _mw_link,
    _times_flat,
    _u01,
)
from repro.core.engine.apply import _apply_window, _drainable_due
from repro.core.engine.window import _window_plan

@jax.named_scope("repro/apply")
def _omni_window(cfg: SimConfig, bank: Bank, s: SimState) -> SimState:
    """Branchless fused windowed drain: plan + apply + single-event fallback
    in ONE straight-line masked pass (no `lax.switch`/`lax.cond`, no
    duplicate kernels, no full-state select).

    When the planned window holds >= 2 events (and the `_drainable_due`
    pre-check agrees with the map path), the shared masked pass applies the
    whole window; otherwise the same pass applies just the rank-0 event —
    the exact event `_step` would pick — with the non-drainable handlers
    appended as identity-when-off scalar-row writes. Bitwise-identical to
    every other step mode.
    """
    T, D, K = cfg.terminals, cfg.num_ds, cfg.max_ops
    i32 = jnp.int32
    w = jnp.where

    with jax.named_scope("repro/pick"):
        flat = _times_flat(s)
    v = _window_plan(cfg, bank, s)
    use = v.use & _drainable_due(s)

    # ---- rank-0 scalar event: the plan's first candidate IS the lex-min
    # event _step would pick (same masked-argmin tie-break) -----------------
    with jax.named_scope("repro/pick"):
        i0 = v.cand_i[0]
        t_now0 = flat[i0]
        is_term0 = i0 < T
        is_sub0 = ~is_term0 & (i0 < T + T * D)
        is_op0 = ~is_term0 & ~is_sub0
        j_sub = i0 - T
        j_op = i0 - T - T * D
        t = w(is_term0, i0, w(is_sub0, j_sub // D, j_op // K))
        idx = w(is_sub0, j_sub % D, w(is_term0, 0, j_op % K))
        F = cfg.max_faults
        M0 = T + T * D + T * K
        if F:
            # fault tail events: always pinned (use=False), handled by the masked
            # singleton handlers at the very end of this pass. Heartbeat probes
            # are conflict-free and drain inside windows; a rank-0 heartbeat only
            # takes the singleton handler when no window forms (`~use`).
            is_fault0 = (i0 >= M0) & (i0 < M0 + F)
            is_hb0 = i0 >= M0 + F
            is_tail0 = is_fault0 | is_hb0
            is_op0 = is_op0 & ~is_tail0
            f_ev0 = jnp.minimum(w(is_fault0, i0 - M0, 0), F - 1)
            d_hb0 = jnp.minimum(w(is_hb0, i0 - M0 - F, 0), D - 1)
            t = w(is_tail0, 0, t)
            idx = w(is_tail0, 0, idx)
        k_ev = jnp.minimum(idx, K - 1)
        d_ev = jnp.minimum(idx, D - 1)
    it0 = s.iters + 1
    salt0 = lambda a: it0 * _SALT_MUL + jnp.int32(a)
    tt_ids = jnp.arange(T, dtype=i32)
    dd = jnp.arange(D, dtype=i32)
    oh_t = tt_ids == t  # [T]

    # ---- single-event category flags (all False when a window applies) ----
    sub0 = s.sub_state[t, d_ev].astype(i32)
    op0 = s.op_state[t, k_ev].astype(i32)
    ph0 = s.phase[t].astype(i32)
    single = ~use
    is_start = single & is_term0 & (ph0 == T_IDLE)
    is_timeout = single & is_op0 & (op0 == OP_WAIT)
    # pinned sub events route to the scalar handlers below; drainable ones
    # (including a degenerate 1-event window) go through the shared pass
    pin0 = v.pinned_sub[t, d_ev]
    is_fanin_x = single & is_sub0 & v.dm_cat[t, d_ev] & pin0
    is_finish_x = single & is_sub0 & v.f_cat[t, d_ev] & pin0  # waiter release
    is_reply0 = sub0 == SUB_ROUND_REPLY
    is_round_in_x = is_fanin_x & ((sub0 == SUB_ROUND_REPLY) | (sub0 == SUB_VOTE))
    is_ack0 = sub0 == SUB_ACK
    is_fin_ack_x = is_fanin_x & (is_ack0 | (sub0 == SUB_ABORT_ACK))
    is_commit_fin0 = (sub0 == SUB_COMMIT_CMD) | (sub0 == SUB_LOCAL_COMMIT)
    is_noop = single & ~(
        (is_term0 & ((ph0 == T_IDLE) | (ph0 == T_COMMIT_LOG)))
        | (is_op0 & ((op0 == OP_ENROUTE) | (op0 == OP_WAIT) | (op0 == OP_EXEC)))
        | (
            is_sub0
            & (v.dm_cat | v.f_cat | v.cat_sched | v.cat_prep | v.cat_preparing)[
                t, d_ev
            ]
        )
    )
    if F:
        is_noop = is_noop & ~is_tail0

    # ---- shared masked pass: the window, or the rank-0 drainable event ----
    act_term = w(use, v.win_term, (v.pos_term == 0) & ~v.pinned_term)
    act_sub = w(use, v.win_sub, (v.pos_sub == 0) & ~v.pinned_sub)
    act_op = w(use, v.win_op, (v.pos_op == 0) & ~v.pinned_op)
    # fold the pinned single event's release footprint into the shared pass
    # so the hotspot kernel runs exactly once per iteration
    d_o = s.op_ds[t, k_ev].astype(i32)
    d_rel = w(is_finish_x, d_ev, d_o)
    rel_gate_x = is_finish_x | is_timeout
    d_of = s.op_ds.astype(i32)
    opn = s.op_state != OP_NONE
    xcancel = oh_t[:, None] & opn & (d_of == d_rel) & rel_gate_x  # [T,K]
    span_do = jnp.maximum(t_now0 - s.sub_arrive[t, d_o], 0)
    oh_t_do = oh_t[:, None] & (dd[None, :] == d_o)
    xlel = w(oh_t_do & is_timeout, span_do, 0)  # [T,D]
    oh_t_dev = oh_t[:, None] & (dd[None, :] == d_ev)
    xcommit = oh_t_dev & is_finish_x & is_commit_fin0
    sx = _apply_window(
        cfg,
        s,
        v,
        act_term,
        act_sub,
        act_op,
        w(use, v.t_last, t_now0),
        w(use, v.n_win, 1),
        w(use, v.n_win, 0),
        w(use, 1, 0),
        w(use, jax.nn.one_hot(v.stop_code, N_STOP_REASONS, dtype=i32), 0),
        fused_inc=jnp.int32(1),
        xcancel=xcancel,
        xlel=xlel,
        xcommit=xcommit,
        xrel=(rel_gate_x, t, d_rel),
        act_hb=w(use, v.win_hb, False),
        chained_inc=w(use, v.n_chained, 0),
        act_fu=v.fu_win & use,
        act_pfu=v.pfu_win & use,
    )

    # ======================================================================
    # Non-drainable single-event handlers — `_omni_step`'s masked-delta style
    # on the scalar rank-0 event; every write is identity-valued when `use`.
    # ======================================================================

    # ---- latency-monitor refresh for the pinned fan-in (drainable fan-ins
    # were counted by the shared pass's EWMA chain) -------------------------
    if F:
        # monitor freeze: a fan-in from a crashed or replica-served DS must
        # not feed the EWMA; a DEGRADE-inflated link IS observed, so the
        # sample is the effective RTT (see handlers._ewma_est)
        mon_freeze = s.ds_down[d_ev] | s.on_repl[t, d_ev]
        mon_sample = sx.tau_mw_eff[d_ev]
    else:
        mon_freeze = s.ds_down[d_ev]
        mon_sample = sx.tau_true[d_ev]
    tau_est = sx.tau_est.at[d_ev].set(
        w(
            is_fanin_x & ~mon_freeze,
            ewma_update(sx.tau_est[d_ev], mon_sample, i32(cfg.beta_milli)),
            sx.tau_est[d_ev],
        )
    )
    sx = sx._replace(tau_est=tau_est)

    # =================== txn start: bank load + admission ==================
    slot_b = s.cur[t] % cfg.bank_txns
    key_b = bank.key[t, slot_b]
    write_b = bank.write[t, slot_b]
    ds_b = bank.ds[t, slot_b]
    rnd_b = bank.round_id[t, slot_b]
    valid_b = bank.valid[t, slot_b]
    oh_b = jax.nn.one_hot(ds_b.astype(i32), D, dtype=bool)
    inv_new = jnp.any(oh_b & valid_b[:, None], axis=0)
    op_key = sx.op_key.at[t].set(w(is_start, w(valid_b, key_b, -1), sx.op_key[t]))
    op_write = sx.op_write.at[t].set(w(is_start, write_b, sx.op_write[t]))
    op_ds = sx.op_ds.at[t].set(w(is_start, ds_b, sx.op_ds[t]))
    op_round = sx.op_round.at[t].set(w(is_start, rnd_b, sx.op_round[t]))
    op_state = sx.op_state.at[t].set(
        w(is_start, w(valid_b, OP_PENDING, OP_NONE), sx.op_state[t].astype(i32)).astype(
            jnp.int8
        )
    )
    op_time = sx.op_time.at[t].set(w(is_start, INF_US, sx.op_time[t]))
    inv = sx.inv.at[t].set(w(is_start, inv_new, sx.inv[t]))
    is_dist = sx.is_dist.at[t].set(
        w(is_start, jnp.sum(inv_new.astype(i32)) > 1, sx.is_dist[t])
    )
    cur_round = sx.cur_round.at[t].set(
        w(is_start, 0, sx.cur_round[t].astype(i32)).astype(jnp.int8)
    )
    first_lock = sx.first_lock.at[t].set(w(is_start, INF_US, sx.first_lock[t]))
    txn_ctr = sx.txn_ctr.at[t].add(w(is_start, 1, 0))
    sx = sx._replace(
        op_key=op_key, op_write=op_write, op_ds=op_ds, op_round=op_round,
        op_state=op_state, op_time=op_time, inv=inv, is_dist=is_dist,
        cur_round=cur_round, first_lock=first_lock, txn_ctr=txn_ctr,
    )

    # O3 admission (Eq.9), read on the pre-claim table
    keym = w(valid_b, key_b, -1)
    slot_a, found_a = hs_mod.lookup_slots(sx.hs.slot_key, keym, valid_b)
    fa = found_a.astype(i32)
    p_abort = jnp.minimum(
        sched.abort_probability(
            sx.hs.c_cnt[slot_a] * fa,
            sx.hs.t_cnt[slot_a] * fa,
            sx.hs.a_cnt[slot_a] * fa,
            valid_b,
        ),
        s.dyn.block_prob_cap,
    )
    u = _u01(salt0(29) + t.astype(i32))
    block, force_abort = sched.admission_decision(
        p_abort, u, s.blocked[t], s.dyn.max_blocked
    )
    # fail fast on a footprint touching an unreachable DS — unless every hit
    # DS carries a read-only replica footprint, in which case the whole txn
    # fails over to the replicas (mirrors _h_start_txn)
    if F:
        hit_v = inv_new & (s.ds_down | (s.mw_heal > t_now0))
        writes_at_d = jnp.any(oh_b & (valid_b & write_b)[:, None], axis=0)
        can_fo = hit_v & (s.repl_tau < INF_US) & ~writes_at_d
        do_failover = jnp.any(hit_v) & jnp.all(~hit_v | can_fo)
        fo = hit_v & do_failover
        hit_down = is_start & jnp.any(hit_v) & ~do_failover
    else:
        hit_down = is_start & jnp.any(inv_new & s.ds_down)
    force_abort = (force_abort & s.dyn.admission & is_start) | hit_down
    block = block & s.dyn.admission & is_start & ~force_abort
    dispatching = is_start & ~block & ~force_abort

    # hot-table claim (dispatch only; identity-valued writes when off)
    with jax.named_scope("repro/hotspot"):
        hs = sx.hs
        claim_valid = valid_b & dispatching
        slot_c, evict = hs_mod.find_or_claim_slots(hs.slot_key, keym, claim_valid)
        ztgt = w(evict, slot_c, cfg.hot_capacity)
        zval = lambda f: w(dispatching, 0, f[ztgt])
        hs = hs._replace(
            w_lat=hs.w_lat.at[ztgt].set(zval(hs.w_lat)),
            t_cnt=hs.t_cnt.at[ztgt].set(zval(hs.t_cnt)),
            c_cnt=hs.c_cnt.at[ztgt].set(zval(hs.c_cnt)),
            a_cnt=hs.a_cnt.at[ztgt].set(zval(hs.a_cnt)),
        )
        hs = hs._replace(
            slot_key=hs_mod.claim_keys(hs.slot_key, slot_c, keym, claim_valid),
            a_cnt=hs.a_cnt.at[slot_c].add(claim_valid.astype(i32)),
            clock=hs.clock.at[slot_c].set(
                w(dispatching, 1, hs.clock[slot_c].astype(i32)).astype(jnp.int8)
            ),
        )
        sx = sx._replace(hs=hs)
    arrive = sx.arrive.at[t].set(w(dispatching | force_abort, t_now0, sx.arrive[t]))
    blocked = sx.blocked.at[t].add(w(block, 1, 0))
    abort_cause = sx.abort_cause.at[t].set(
        w(
            force_abort,
            w(hit_down, CAUSE_CRASH, CAUSE_ADMISSION),
            sx.abort_cause[t],
        )
    )
    sx = sx._replace(arrive=arrive, blocked=blocked, abort_cause=abort_cause)
    inv_t = sx.inv[t]

    # ===================== subtxn row (ordered masked writes) ==============
    sub_row = sx.sub_state[t].astype(i32)
    sub_tm = sx.sub_time[t]
    rd_done_row = sx.rd_done[t]
    sub_lel_row = sx.sub_lel[t]
    at_ev = dd == d_ev
    at_do = dd == d_o
    rd_done_row = w(is_start, False, rd_done_row)
    sub_lel_row = w(is_start, 0, sub_lel_row)
    # pinned fan-in self-update (drainable fan-ins took the shared pass)
    sub_row = w(
        is_round_in_x & at_ev, w(is_reply0, SUB_ROUND_AT_DM, SUB_VOTED), sub_row
    )
    sub_tm = w(is_round_in_x & at_ev, INF_US, sub_tm)
    rd_done_row = rd_done_row | (is_round_in_x & at_ev)
    sub_row = w(is_fin_ack_x & at_ev, w(is_ack0, SUB_DONE, SUB_ABORTED), sub_row)
    sub_tm = w(is_fin_ack_x & at_ev, INF_US, sub_tm)
    # waiter-release finish: ack back to the DM (release itself was folded
    # into the shared pass; the FIFO grants run below)
    lcs_gate_x = (
        is_finish_x
        & is_commit_fin0
        & (s.first_lock[t, d_ev] < INF_US)
        & (t_now0 >= jnp.int32(cfg.warmup_us))
    )
    lcs_span_x = w(lcs_gate_x, (t_now0 - s.first_lock[t, d_ev] + 500) // 1000, 0)
    ack_salt = salt0(47) + w(is_commit_fin0, 0, 6)  # 47 commit, 53 abort
    kb0, kr0 = _mw_link(s, s.on_repl[t, d_ev], d_ev, t_now0)
    ack_send_t = kb0 + _delay_salted(s.jitter_milli, kr0, ack_salt)
    sub_row = w(is_finish_x & at_ev, w(is_commit_fin0, SUB_ACK, SUB_ABORT_ACK), sub_row)
    sub_tm = w(is_finish_x & at_ev, ack_send_t, sub_tm)
    # timeout abort fan-out (peer notify + own ack); the partial round's LEL
    # was folded into the shared pass's Eq.(4) read, accounted here
    abort_family = (
        (sub_row == SUB_ABORT_PEER)
        | (sub_row == SUB_ABORT_ACK)
        | (sub_row == SUB_ABORTED)
    )
    peers = inv_t & (dd != d_o) & ~abort_family
    ab_salts = salt0(17) + dd
    if F:
        # abort notifications ride the effective links (see _initiate_abort)
        mesh_base, mesh_tau = _ds_send(s, d_o, dd, t_now0)
        notify_direct = mesh_base + _delay_salted(s.jitter_milli, mesh_tau, ab_salts)
        up_base, up_tau = _mw_link(s, s.on_repl[t, d_o], d_o, t_now0)
        to_dm = up_base + _delay_salted(s.jitter_milli, up_tau, salt0(19))
        dn_base, dn_tau = _mw_link(s, s.on_repl[t], dd, to_dm)
        notify_via_dm = dn_base + _delay_salted(s.jitter_milli, dn_tau, ab_salts)
        notify = w(s.dyn.early_abort, notify_direct, notify_via_dm)
        ok_base, ok_tau = _mw_link(s, s.on_repl[t, d_o], d_o, t_now0)
        own_ack_t = ok_base + _delay_salted(s.jitter_milli, ok_tau, salt0(23))
    else:
        notify_direct = _delay_salted(s.jitter_milli, s.tau_ds[d_o], ab_salts)
        to_dm = _delay_salted(s.jitter_milli, s.tau_true[d_o], salt0(19))
        notify_via_dm = to_dm + _delay_salted(s.jitter_milli, s.tau_true, ab_salts)
        notify = t_now0 + w(s.dyn.early_abort, notify_direct, notify_via_dm)
        own_ack_t = t_now0 + _delay_salted(s.jitter_milli, s.tau_true[d_o], salt0(23))
    sub_row = w(is_timeout & peers, SUB_ABORT_PEER, sub_row)
    sub_tm = w(is_timeout & peers, notify, sub_tm)
    sub_row = w(is_timeout & at_do, SUB_ABORT_ACK, sub_row)
    sub_tm = w(is_timeout & at_do, own_ack_t, sub_tm)
    sub_lel_row = sub_lel_row.at[w(is_timeout, d_o, 0)].add(w(is_timeout, span_do, 0))
    # first cause wins (mirrors _initiate_abort)
    abort_cause = sx.abort_cause.at[t].set(
        w(
            is_timeout & (sx.abort_cause[t] == CAUSE_NONE),
            CAUSE_TIMEOUT,
            sx.abort_cause[t],
        )
    )
    sx = sx._replace(abort_cause=abort_cause)

    # ============== pinned DM progress: chiller stage-2 / advance ==========
    ready_ch = is_round_in_x & v.ready_chiller_j[t, d_ev]
    waiting_c = inv_t & (sub_row == SUB_CHILLER_WAIT)
    sub_row = w(ready_ch & waiting_c, SUB_SCHED, sub_row)
    sub_tm = w(ready_ch & waiting_c, t_now0, sub_tm)
    advance = is_round_in_x & v.advance_j[t, d_ev]
    nxt_round = (s.cur_round[t] + 1).astype(i32)
    cur_round = sx.cur_round.at[t].set(
        w(advance, nxt_round, sx.cur_round[t].astype(i32)).astype(jnp.int8)
    )
    sx = sx._replace(cur_round=cur_round)
    rd_done_row = w(advance, False, rd_done_row)
    row_nn2 = s.op_state[t].astype(i32) != OP_NONE
    oh_row = jax.nn.one_hot(s.op_ds[t].astype(i32), D, dtype=bool)
    inv_next = jnp.any(
        oh_row & (row_nn2 & (s.op_round[t].astype(i32) == nxt_round))[:, None], axis=0
    )
    # one shared stagger forecast: txn-start round 0 OR round advance
    inv0 = jnp.any(oh_b & (valid_b & (rnd_b == 0))[:, None], axis=0)
    stag_mask = w(is_start, inv0, inv_next)
    off = _stagger(cfg, sx, t, stag_mask)
    # chiller first-round split (start only)
    tmin = jnp.min(w(inv0, sx.tau_est, INF_US))
    stage1 = inv0 & (sx.tau_est <= tmin)
    stage2 = inv0 & ~stage1
    chil_state = w(stage2, SUB_CHILLER_WAIT, w(stage1, SUB_SCHED, SUB_NONE))
    chil_time = w(stage1, t_now0, INF_US)
    later = inv_new & ~inv0
    norm_state = w(inv0, SUB_SCHED, w(later, SUB_WAIT_ROUND, SUB_NONE))
    norm_time = w(inv0, t_now0 + off, INF_US)
    start_state = w(s.dyn.chiller_two_stage, chil_state, norm_state)
    start_time = w(s.dyn.chiller_two_stage, chil_time, norm_time)
    sub_row = w(dispatching, start_state, sub_row)
    sub_tm = w(dispatching, start_time, sub_tm)
    sub_row = w(advance & inv_next, SUB_SCHED, sub_row)
    sub_tm = w(advance & inv_next, t_now0 + off, sub_tm)

    # ============== FIFO grants after the folded waiter release ============
    # (exact `_release_and_grant` semantics; the cancel/hotspot half already
    # ran inside the shared pass via xcancel — grants read the post-cancel
    # table, exactly as the sequential handler does)
    with jax.named_scope("repro/locks"):
        held = (
            row_nn2
            & (s.op_ds[t].astype(i32) == d_rel)
            & ((s.op_state[t].astype(i32) == OP_EXEC) | (s.op_state[t].astype(i32) == OP_HOLD))
            & rel_gate_x
        )
        rel_keys = w(held, s.op_key[t], -2)
        flat_state = sx.op_state.reshape(-1).astype(i32)
        flat_key = sx.op_key.reshape(-1)
        flat_write = sx.op_write.reshape(-1)
        flat_enq = sx.op_enq.reshape(-1)
        flat_ds = sx.op_ds.reshape(-1).astype(i32)
        holderf = (flat_state == OP_EXEC) | (flat_state == OP_HOLD)
        waitf = flat_state == OP_WAIT
        eq = flat_key[None, :] == rel_keys[:, None]  # [K, T*K]
        rem_x = jnp.any(eq & holderf[None, :] & flat_write[None, :], axis=1)
        rem_s = jnp.any(eq & holderf[None, :] & ~flat_write[None, :], axis=1)
        M = held[:, None] & eq & waitf[None, :]
        exq = w(M & flat_write[None, :], flat_enq[None, :], INF_US)
        ex_min = jnp.min(exq, axis=1)
        enq = w(M, flat_enq[None, :], INF_US)
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
        granted = jnp.any(grant_s | grant_x, axis=0)
        exec_tg = t_now0 + _exec_us(cfg, s, flat_ds)
        op_state = w(granted, OP_EXEC, flat_state).astype(jnp.int8).reshape(T, K)
        op_time = w(granted, exec_tg, sx.op_time.reshape(-1)).reshape(T, K)
        sx = sx._replace(op_state=op_state, op_time=op_time)
        # grant-time first_lock via an elementwise group-min (a scatter-min over
        # [T*K] indices serializes per index under vmap)
        oh_g = jax.nn.one_hot(sx.op_ds.astype(i32), D, dtype=bool)  # [T,K,D]
        g_min = jnp.min(
            jnp.where(granted.reshape(T, K)[:, :, None] & oh_g, t_now0, INF_US), axis=1
        )
        sx = sx._replace(first_lock=jnp.minimum(sx.first_lock, g_min))

    # =================== terminal finish (ack fan-in / O3 abort) ===========
    fin_done = is_fin_ack_x & (v.done_ack_j[t, d_ev] | v.done_abk_j[t, d_ev])
    gate_fin = fin_done | force_abort
    committed_fin = fin_done & is_ack0
    lat = t_now0 - sx.arrive[t]
    meas = t_now0 >= jnp.int32(cfg.warmup_us)
    hbin = _hist_bin(lat)
    slot_n = s.cur[t] % cfg.bank_txns
    one_c = w(gate_fin & meas & committed_fin, 1, 0)
    one_a = w(gate_fin & meas & ~committed_fin, 1, 0)
    dist = sx.is_dist[t]
    lat_ms = (lat + 500) // 1000
    # abort-cause tally + fault-window goodput (mirrors _finish_txn)
    will_retry_fin = ~committed_fin & (sx.retries[t] < s.dyn.max_retries)
    cause_fin = w(
        ~will_retry_fin & (sx.retries[t] > 0), CAUSE_EXHAUSTED, sx.abort_cause[t]
    )
    if F:
        any_down_f = jnp.any(s.ds_down | (s.mw_heal > t_now0))
    else:
        any_down_f = jnp.any(s.ds_down)
    sx = sx._replace(
        ab_cause=sx.ab_cause.at[cause_fin].add(one_a),
        commits_fault=sx.commits_fault + w(any_down_f, one_c, 0),
    )
    sx = sx._replace(
        commits=sx.commits + one_c,
        aborts=sx.aborts + one_a,
        commits_dist=sx.commits_dist + w(dist, one_c, 0),
        aborts_dist=sx.aborts_dist + w(dist, one_a, 0),
        lat_sum=sx.lat_sum + one_c * lat_ms,
        lat_sum_dist=sx.lat_sum_dist + w(dist, one_c, 0) * lat_ms,
        hist_all=sx.hist_all.at[hbin].add(one_c),
        hist_cen=sx.hist_cen.at[hbin].add(w(dist, 0, one_c)),
        hist_dist=sx.hist_dist.at[hbin].add(w(dist, one_c, 0)),
        slot_commits=sx.slot_commits.at[t, slot_n].add(one_c, mode="drop"),
        slot_aborts=sx.slot_aborts.at[t, slot_n].add(one_a, mode="drop"),
        slot_lat=sx.slot_lat.at[t, slot_n].add(one_c * lat_ms, mode="drop"),
    )
    # per-txn row resets
    op_state = sx.op_state.at[t].set(
        w(gate_fin, OP_NONE, sx.op_state[t].astype(i32)).astype(jnp.int8)
    )
    op_time = sx.op_time.at[t].set(w(gate_fin, INF_US, sx.op_time[t]))
    inv = sx.inv.at[t].set(w(gate_fin, False, sx.inv[t]))
    sub_row = w(gate_fin, SUB_NONE, sub_row)
    sub_tm = w(gate_fin, INF_US, sub_tm)
    sub_lel_row = w(gate_fin, 0, sub_lel_row)
    first_lock = sx.first_lock.at[t].set(w(gate_fin, INF_US, sx.first_lock[t]))
    rd_done_row = w(gate_fin, False, rd_done_row)
    cur_round = sx.cur_round.at[t].set(
        w(gate_fin, 0, sx.cur_round[t].astype(i32)).astype(jnp.int8)
    )
    retry = gate_fin & ~committed_fin & (sx.retries[t] < s.dyn.max_retries)
    base = s.dyn.retry_backoff_us
    jit_b = (
        _hash_u32(sx.txn_ctr[t] * 977 + t.astype(i32) * 131 + sx.retries[t])
        % jnp.maximum(base, 1).astype(jnp.uint32)
    ).astype(i32)
    # floor at 1 us so a zero-backoff retry against a still-down DS cannot
    # livelock the event loop (mirrors _finish_txn)
    backoff = jnp.maximum(base * (1 + jnp.minimum(sx.retries[t], 7)) + jit_b, 1)
    retries = sx.retries.at[t].set(
        w(gate_fin, w(retry, sx.retries[t] + 1, 0), sx.retries[t])
    )
    retry_same = sx.retry_same.at[t].set(w(gate_fin, retry, sx.retry_same[t]))
    blocked = sx.blocked.at[t].set(w(gate_fin, 0, sx.blocked[t]))
    cur = sx.cur.at[t].add(w(gate_fin & ~retry, 1, 0))
    abort_cause = sx.abort_cause.at[t].set(
        w(gate_fin, CAUSE_NONE, sx.abort_cause[t])
    )
    sx = sx._replace(
        op_state=op_state, op_time=op_time, inv=inv, first_lock=first_lock,
        cur_round=cur_round, retries=retries, retry_same=retry_same,
        blocked=blocked, cur=cur, abort_cause=abort_cause,
    )

    # ======================= phase / terminal timer ========================
    # (the drainable gates — log flush, send-commit, log decision — were
    # written by the shared pass; only the pinned single-event gates remain)
    phase = sx.phase[t].astype(i32)
    phase = w(dispatching, T_ACTIVE, phase)
    phase = w(is_timeout, T_ABORT_WAIT, phase)
    phase = w(gate_fin, T_IDLE, phase)
    tt = sx.term_time[t]
    tt = w(block, t_now0 + s.dyn.admission_backoff_us, tt)
    tt = w(dispatching | is_timeout, INF_US, tt)
    tt = w(gate_fin, w(committed_fin, t_now0, t_now0 + backoff), tt)
    sx = sx._replace(
        phase=sx.phase.at[t].set(phase.astype(jnp.int8)),
        term_time=sx.term_time.at[t].set(tt),
    )

    # ======================= scatter the event rows ========================
    # WAN-leg charges for the pinned singleton routes (drainable events were
    # charged inside the shared pass): a pinned fan-in (round advance,
    # chiller stage-2, txn-completing ack) is still a WAN receive, and a
    # waiter-release finish charges by its PRE-state exactly like
    # `_h_ds_finish` — COMMIT_CMD +1, LOCAL_COMMIT +0, ABORT_PEER only via
    # the DM route (~early_abort). Timeouts, starts, faults, heartbeats
    # charge nothing.
    wan_x = (
        w(is_fanin_x, 1, 0)
        + w(is_finish_x & (sub0 == SUB_COMMIT_CMD), 1, 0)
        + w(is_finish_x & (sub0 == SUB_ABORT_PEER) & ~s.dyn.early_abort, 1, 0)
    )
    sx = sx._replace(
        sub_state=sx.sub_state.at[t].set(sub_row.astype(jnp.int8)),
        sub_time=sx.sub_time.at[t].set(sub_tm),
        sub_lel=sx.sub_lel.at[t].set(sub_lel_row),
        rd_done=sx.rd_done.at[t].set(rd_done_row),
        lcs_sum=sx.lcs_sum + lcs_span_x,
        lcs_cnt=sx.lcs_cnt + lcs_gate_x.astype(i32),
        wan_legs=sx.wan_legs + wan_x,
    )

    # ============== replica failover bookkeeping (start / finish) ==========
    # one combined on_repl write: a dispatching start routes the hit subtxns
    # to their replicas (stale reads + staleness window recorded), a finish
    # releases the routing — the two gates are mutually exclusive. Written
    # after the scatter so every send above read the pre-update routing.
    if F:
        stale_w = w(fo, t_now0 - s.down_since + s.repl_lag_us, 0)
        on_repl_row = w(dispatching, fo, w(gate_fin, False, sx.on_repl[t]))
        sx = sx._replace(
            on_repl=sx.on_repl.at[t].set(on_repl_row),
            failovers=sx.failovers + w(dispatching, jnp.sum(fo.astype(i32)), 0),
            stale_reads=sx.stale_reads
            + w(
                dispatching,
                jnp.sum((valid_b & ~write_b & fo[ds_b.astype(i32)]).astype(i32)),
                0,
            ),
            max_stale_us=jnp.maximum(
                sx.max_stale_us, w(dispatching, jnp.max(stale_w), 0)
            ),
        )

    # ============================== noop ===================================
    upd = dict(
        op_time=w(is_noop & (sx.op_time == t_now0), INF_US, sx.op_time),
        sub_time=w(is_noop & (sx.sub_time == t_now0), INF_US, sx.sub_time),
        term_time=w(is_noop & (sx.term_time == t_now0), INF_US, sx.term_time),
        noops=sx.noops + w(is_noop, 1, 0),
    )
    if F:
        upd.update(
            fault_time=w(is_noop & (sx.fault_time == t_now0), INF_US, sx.fault_time),
            hb_time=w(is_noop & (sx.hb_time == t_now0), INF_US, sx.hb_time),
        )
    sx = sx._replace(**upd)

    # ===================== fault / heartbeat tail events ===================
    # Run dead last: the sub_row/sub_tm scatter above rewrites row `t` (a
    # stale row-0 copy for tail events) and would clobber the crash
    # cascade's sub-state writes if these ran any earlier. A fault at rank 0
    # is always pinned, so `use` is False and the rest of the pass was a
    # masked identity; a rank-0 heartbeat may instead have drained inside
    # the window (`use`), in which case `_apply_window` already counted and
    # re-armed it and the singleton handler must stay off.
    if F:
        sx = _fault_event(cfg, sx, f_ev0, is_fault0)
        sx = _hb_event(cfg, sx, d_hb0, is_hb0 & ~use)
    return sx
