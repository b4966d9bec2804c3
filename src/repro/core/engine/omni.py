"""Branchless omnibus step: the lockstep (vmap) single-event hot path.

One straight-line masked pass with no `lax.switch`/`lax.cond` — every
handler of `handlers.py` re-expressed as an identity-when-off masked delta,
the heavy kernels traced exactly once per step. Bitwise-identical to
`step._step` (asserted in tests/core/test_engine_batch.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import hotspot as hs_mod
from repro.core import scheduler as sched
from repro.core.netmodel import INF_US, _hash_u32, ewma_update
from repro.core.protocols import (
    PREPARE_COORD,
    PREPARE_DECENTRAL,
    PREPARE_NONE,
)
from repro.core.workloads import Bank

from repro.core.engine.state import (
    OP_NONE,
    OP_PENDING,
    OP_ENROUTE,
    OP_QUEUED,
    OP_WAIT,
    OP_EXEC,
    OP_HOLD,
    OP_DONE,
    SUB_NONE,
    SUB_SCHED,
    SUB_RUN,
    SUB_ROUND_REPLY,
    SUB_ROUND_AT_DM,
    SUB_WAIT_ROUND,
    SUB_CHILLER_WAIT,
    SUB_PREP_CMD,
    SUB_PREPARING,
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
    T_COMMIT_WAIT,
    T_ABORT_WAIT,
    CAUSE_NONE,
    CAUSE_TIMEOUT,
    CAUSE_ADMISSION,
    CAUSE_CRASH,
    CAUSE_EXHAUSTED,
    SimConfig,
    SimState,
    _delay,
    _delay_salted,
    _ds_send,
    _exec_us,
    _hist_bin,
    _lock_wait_deadline,
    _measuring,
    _mw_link,
    _round_done_transition,
    _salt,
    _tiga_arrival,
    _tiga_fast,
    _times_flat,
    _u01,
)
from repro.core.engine.faults import _fault_event, _hb_event
from repro.core.engine.handlers import _grant_decision, _stagger

@jax.named_scope("repro/apply")
def _omni_step(cfg: SimConfig, bank: Bank, s: SimState) -> SimState:
    """Branchless all-category dispatch: process the single earliest event as
    ONE straight-line masked pass — no `lax.switch`, no `lax.cond`. Selected
    by ``SimConfig(lockstep=True, drain=False)`` — the lockstep (vmap)
    reference path; lockstep lanes with draining run `fused._omni_window`.

    Under lockstep (vmap) lanes the switch executes every branch per
    iteration anyway and pays a full-state `select_n` merge per branch;
    here every handler is a masked delta gated by its category flag, and the
    heavy kernels each trace/execute exactly once per step with gated
    inputs — one lock attempt (arrival OR chained statement), one
    release/grant (DS finish OR timeout abort), one hotspot Eq.(4) update,
    one DM-progress decision, one stagger forecast (txn start OR round
    advance), one terminal finish (last ack OR admission abort), one EWMA
    monitor update (any DM fan-in).

    Bitwise-identical to `_step` (asserted across presets in tests): same
    event pick and tie-break, same salts, same update formulas — only the
    dispatch mechanism differs. A step costs the same whatever the event
    category, so diverged lanes batch as well as lockstepped ones.
    """
    T, D, K = cfg.terminals, cfg.num_ds, cfg.max_ops
    i32 = jnp.int32
    w = jnp.where

    # ---- event pick (identical to _step) ----------------------------------
    F = cfg.max_faults
    M0 = T + T * D + T * K
    with jax.named_scope("repro/pick"):
        flat = _times_flat(s)
        i = jnp.argmin(flat).astype(i32)
        t_now = flat[i]
        is_term = i < T
        is_sub = ~is_term & (i < T + T * D)
        is_op = ~is_term & ~is_sub
        j_sub = i - T
        j_op = i - T - T * D
        t = w(is_term, i, w(is_sub, j_sub // D, j_op // K))
        idx = w(is_sub, j_sub % D, w(is_term, 0, j_op % K))
        if F:
            # fault/heartbeat tail sections (masked handlers run at the very end
            # of the pass — everything in between is identity for a tail event)
            is_fault_ev = (i >= M0) & (i < M0 + F)
            is_hb_ev = i >= M0 + F
            is_tail = is_fault_ev | is_hb_ev
            is_op = is_op & ~is_tail
            f_ev = jnp.minimum(w(is_fault_ev, i - M0, 0), F - 1)
            d_hb = jnp.minimum(w(is_hb_ev, i - M0 - F, 0), D - 1)
            t = w(is_tail, 0, t)
            idx = w(is_tail, 0, idx)
        k_ev = jnp.minimum(idx, K - 1)
        d_ev = jnp.minimum(idx, D - 1)
    s = s._replace(now=t_now, iters=s.iters + 1)

    # ---- category flags (mirror the handler-id tables) --------------------
    sub0 = s.sub_state[t, d_ev].astype(i32)
    op0 = s.op_state[t, k_ev].astype(i32)
    ph0 = s.phase[t].astype(i32)
    is_start = is_term & (ph0 == T_IDLE)
    is_logflush = is_term & (ph0 == T_COMMIT_LOG)
    is_arrive = is_op & (op0 == OP_ENROUTE)
    is_timeout = is_op & (op0 == OP_WAIT)
    is_exec = is_op & (op0 == OP_EXEC)
    is_sched = is_sub & (sub0 == SUB_SCHED)
    is_reply = is_sub & (sub0 == SUB_ROUND_REPLY)
    is_vote = is_sub & (sub0 == SUB_VOTE)
    is_round_in = is_reply | is_vote
    is_prep_cmd = is_sub & (sub0 == SUB_PREP_CMD)
    is_prepared = is_sub & (sub0 == SUB_PREPARING)
    is_commit_fin = is_sub & ((sub0 == SUB_COMMIT_CMD) | (sub0 == SUB_LOCAL_COMMIT))
    is_abort_fin = is_sub & (sub0 == SUB_ABORT_PEER)
    is_finish = is_commit_fin | is_abort_fin
    is_ack = is_sub & (sub0 == SUB_ACK)
    is_abort_ack = is_sub & (sub0 == SUB_ABORT_ACK)
    is_fin_ack = is_ack | is_abort_ack
    is_noop = ~(
        is_start | is_logflush | is_arrive | is_timeout | is_exec | is_sched
        | is_round_in | is_prep_cmd | is_prepared | is_finish | is_fin_ack
    )
    if F:
        is_noop = is_noop & ~is_tail
    d_o = s.op_ds[t, k_ev].astype(i32)  # the op event's data source
    kk = jnp.arange(K, dtype=i32)
    dd = jnp.arange(D, dtype=i32)

    # =================== txn start: bank load + admission ==================
    slot_b = s.cur[t] % cfg.bank_txns
    key_b = bank.key[t, slot_b]
    write_b = bank.write[t, slot_b]
    ds_b = bank.ds[t, slot_b]
    rnd_b = bank.round_id[t, slot_b]
    valid_b = bank.valid[t, slot_b]
    oh_b = jax.nn.one_hot(ds_b.astype(i32), D, dtype=bool)
    inv_new = jnp.any(oh_b & valid_b[:, None], axis=0)

    op_key = s.op_key.at[t].set(
        w(is_start, w(valid_b, key_b, -1), s.op_key[t])
    )
    op_write = s.op_write.at[t].set(w(is_start, write_b, s.op_write[t]))
    op_ds = s.op_ds.at[t].set(w(is_start, ds_b, s.op_ds[t]))
    op_round = s.op_round.at[t].set(w(is_start, rnd_b, s.op_round[t]))
    op_state = s.op_state.at[t].set(
        w(is_start, w(valid_b, OP_PENDING, OP_NONE), s.op_state[t].astype(i32)).astype(jnp.int8)
    )
    op_time = s.op_time.at[t].set(w(is_start, INF_US, s.op_time[t]))
    inv = s.inv.at[t].set(w(is_start, inv_new, s.inv[t]))
    is_dist = s.is_dist.at[t].set(
        w(is_start, jnp.sum(inv_new.astype(i32)) > 1, s.is_dist[t])
    )
    cur_round = s.cur_round.at[t].set(
        w(is_start, 0, s.cur_round[t].astype(i32)).astype(jnp.int8)
    )
    rd_done_row = w(is_start, False, s.rd_done[t])
    sub_lel_row = w(is_start, 0, s.sub_lel[t])
    first_lock = s.first_lock.at[t].set(w(is_start, INF_US, s.first_lock[t]))
    txn_ctr = s.txn_ctr.at[t].add(w(is_start, 1, 0))
    s = s._replace(
        op_key=op_key, op_write=op_write, op_ds=op_ds, op_round=op_round,
        op_state=op_state, op_time=op_time, inv=inv, is_dist=is_dist,
        cur_round=cur_round, first_lock=first_lock, txn_ctr=txn_ctr,
    )
    inv_t = s.inv[t]

    # O3 admission (Eq.9), read on the pre-claim table
    keym = w(valid_b, key_b, -1)
    slot_a, found_a = hs_mod.lookup_slots(s.hs.slot_key, keym, valid_b)
    fa = found_a.astype(i32)
    p_abort = jnp.minimum(
        sched.abort_probability(
            s.hs.c_cnt[slot_a] * fa, s.hs.t_cnt[slot_a] * fa, s.hs.a_cnt[slot_a] * fa,
            valid_b,
        ),
        s.dyn.block_prob_cap,
    )
    u = _u01(_salt(s, 29) + t.astype(i32))
    block, force_abort = sched.admission_decision(
        p_abort, u, s.blocked[t], s.dyn.max_blocked
    )
    # fail fast on a footprint touching an unreachable DS — unless every hit
    # DS carries a read-only replica footprint, in which case the whole txn
    # fails over to the replicas (mirrors _h_start_txn)
    if F:
        hit_v = inv_new & (s.ds_down | (s.mw_heal > s.now))
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

    # hot-table claim (dispatch only; every write is identity-valued when the
    # gate is off so non-start events leave the table — scratch row included —
    # bitwise-untouched)
    hs = s.hs
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
    s = s._replace(hs=hs)
    arrive = s.arrive.at[t].set(
        w(dispatching | force_abort, s.now, s.arrive[t])
    )
    blocked = s.blocked.at[t].add(w(block, 1, 0))
    abort_cause = s.abort_cause.at[t].set(
        w(
            force_abort,
            w(hit_down, CAUSE_CRASH, CAUSE_ADMISSION),
            s.abort_cause[t],
        )
    )
    s = s._replace(arrive=arrive, blocked=blocked, abort_cause=abort_cause)

    # ============ op events: exec completion, chained lock attempt =========
    op_state = s.op_state.at[t, k_ev].set(
        w(is_exec, OP_HOLD, s.op_state[t, k_ev].astype(i32)).astype(jnp.int8)
    )
    op_time = s.op_time.at[t, k_ev].set(
        w(is_exec, INF_US, s.op_time[t, k_ev])
    )
    s = s._replace(op_state=op_state, op_time=op_time)
    row_st = s.op_state[t].astype(i32)
    nxt_mask = (
        (row_st == OP_QUEUED)
        & (s.op_ds[t].astype(i32) == d_o)
        & (s.op_round[t] == s.cur_round[t])
    )
    has_next = jnp.any(nxt_mask)
    nxt = jnp.argmax(nxt_mask).astype(i32)
    do_lock = is_arrive | (is_exec & has_next)
    k_lock = w(is_arrive, k_ev, nxt)

    # one shared lock attempt (FIFO-fair, exact _attempt_lock semantics)
    r_l = s.op_key[t, k_lock]
    w_l = s.op_write[t, k_lock]
    d_l = s.op_ds[t, k_lock].astype(i32)
    stf = s.op_state.astype(i32)
    on_r = s.op_key == r_l
    holder = (stf == OP_EXEC) | (stf == OP_HOLD)
    x_held = jnp.any(holder & on_r & s.op_write)
    s_held = jnp.any(holder & on_r & ~s.op_write)
    waiter = jnp.any((stf == OP_WAIT) & on_r)
    lock_ok = w(w_l, ~x_held & ~s_held, ~x_held) & ~waiter
    exec_t = s.now + _exec_us(cfg, s, d_l)
    op_state = s.op_state.at[t, k_lock].set(
        w(do_lock, w(lock_ok, OP_EXEC, OP_WAIT), s.op_state[t, k_lock].astype(i32)).astype(jnp.int8)
    )
    op_time = s.op_time.at[t, k_lock].set(
        w(do_lock, w(lock_ok, exec_t, _lock_wait_deadline(s.dyn, s.now)), s.op_time[t, k_lock])
    )
    op_enq = s.op_enq.at[t, k_lock].set(
        w(do_lock, s.now, s.op_enq[t, k_lock])
    )
    first_lock = s.first_lock.at[t, d_l].min(
        w(do_lock & lock_ok, s.now, INF_US)
    )
    s = s._replace(
        op_state=op_state, op_time=op_time, op_enq=op_enq, first_lock=first_lock
    )

    # round completion at (t, d_o) — exec with no next statement; a lock-wait
    # timeout accounts the partial round the same way before aborting
    rd = is_exec & ~has_next
    g_lel = rd | is_timeout
    span_do = jnp.maximum(s.now - s.sub_arrive[t, d_o], 0)
    sub_lel_row = sub_lel_row.at[w(g_lel, d_o, 0)].add(w(g_lel, span_do, 0))
    row_nn = s.op_state[t].astype(i32) != OP_NONE
    d_final = jnp.max(
        w(row_nn & (s.op_ds[t].astype(i32) == d_o), s.op_round[t].astype(i32), -1)
    )
    rd_is_final = s.cur_round[t].astype(i32) >= d_final
    centralized = jnp.sum(inv_t.astype(i32)) == 1
    rd_aborting = s.sub_state[t, d_o].astype(i32) == SUB_ABORT_PEER
    rbase_rd, rtau_rd = _mw_link(s, s.on_repl[t, d_o], d_o, s.now)
    reply_t_rd = rbase_rd + _delay(s, rtau_rd, _salt(s, 37))
    prep_t_rd = s.now + s.dyn.lan_rtt_us + s.dyn.log_flush_us
    local_t_rd = s.now + s.dyn.log_flush_us
    single_rd = jnp.max(w(row_nn, s.op_round[t], 0)) == 0
    fast_rd = _tiga_fast(s.dyn, single_rd, inv_t, s.sub_fast[t])
    rd_state, rd_time = _round_done_transition(
        s.dyn, rd_is_final, centralized, reply_t_rd, prep_t_rd, local_t_rd, fast_rd
    )

    # ===================== subtxn row (ordered masked writes) ==============
    sub_row = s.sub_state[t].astype(i32)
    sub_tm = s.sub_time[t]
    at_ev = dd == d_ev
    at_do = dd == d_o
    # exec round-done reply/prepare transition
    g_rd = rd & ~rd_aborting
    sub_row = w(g_rd & at_do, rd_state, sub_row)
    sub_tm = w(g_rd & at_do, rd_time, sub_tm)
    s = s._replace(
        fast_commits=s.fast_commits + w(g_rd & (rd_state == SUB_LOCAL_COMMIT), 1, 0)
    )
    # dispatch command reaches DS d_ev
    abase_ev, atau_ev = _mw_link(s, s.on_repl[t, d_ev], d_ev, s.now)
    arrival = abase_ev + _delay(s, atau_ev, _salt(s, 41))
    first_t_ev, fast_ev = _tiga_arrival(s.dyn, s.clock_skew_us, s.now, arrival)
    disp_mask = (
        (s.op_state[t].astype(i32) == OP_PENDING)
        & (s.op_ds[t].astype(i32) == d_ev)
        & (s.op_round[t] == s.cur_round[t])
    )
    disp_first = jnp.argmax(disp_mask).astype(i32)
    disp_has = jnp.any(disp_mask)
    op_state = s.op_state.at[t].set(
        w(
            is_sched & disp_mask,
            w(kk == disp_first, OP_ENROUTE, OP_QUEUED),
            s.op_state[t].astype(i32),
        ).astype(jnp.int8)
    )
    op_time = s.op_time.at[t, disp_first].set(
        w(is_sched & disp_has, first_t_ev, s.op_time[t, disp_first])
    )
    s = s._replace(op_state=op_state, op_time=op_time)
    sub_row = w(is_sched & at_ev, SUB_RUN, sub_row)
    sub_tm = w(is_sched & at_ev, INF_US, sub_tm)
    sub_arrive = s.sub_arrive.at[t, d_ev].set(
        w(is_sched, arrival, s.sub_arrive[t, d_ev])
    )
    sub_fast = s.sub_fast.at[t, d_ev].set(
        w(is_sched, fast_ev, s.sub_fast[t, d_ev])
    )
    s = s._replace(sub_arrive=sub_arrive, sub_fast=sub_fast)
    # DS-side 2PC legs
    sub_row = w(is_prep_cmd & at_ev, SUB_PREPARING, sub_row)
    sub_tm = w(is_prep_cmd & at_ev, s.now + s.dyn.log_flush_us, sub_tm)
    vbase_ev, vtau_ev = _mw_link(s, s.on_repl[t, d_ev], d_ev, s.now)
    vote_send_t = vbase_ev + _delay(s, vtau_ev, _salt(s, 43))
    sub_row = w(is_prepared & at_ev, SUB_VOTE, sub_row)
    sub_tm = w(is_prepared & at_ev, vote_send_t, sub_tm)
    # DM fan-ins: self-update + shared EWMA monitor refresh
    if F:
        # the monitor samples the *effective* link RTT (DEGRADE is observed,
        # the scheduler re-plans); freeze on crashed-DS fan-ins and on
        # replica-link fan-ins, which say nothing about the primary link
        mon_sample = s.tau_mw_eff[d_ev]
        mon_freeze = s.ds_down[d_ev] | s.on_repl[t, d_ev]
    else:
        # monitor freeze: a fan-in from a crashed DS (message already in
        # flight when it died) must not feed the EWMA (see _ewma_est)
        mon_sample = s.tau_true[d_ev]
        mon_freeze = s.ds_down[d_ev]
    tau_est = s.tau_est.at[d_ev].set(
        w(
            (is_round_in | is_fin_ack) & ~mon_freeze,
            ewma_update(s.tau_est[d_ev], mon_sample, i32(cfg.beta_milli)),
            s.tau_est[d_ev],
        )
    )
    s = s._replace(tau_est=tau_est)
    sub_row = w(is_round_in & at_ev, w(is_reply, SUB_ROUND_AT_DM, SUB_VOTED), sub_row)
    sub_tm = w(is_round_in & at_ev, INF_US, sub_tm)
    rd_done_row = rd_done_row | (is_round_in & at_ev)
    ack_committed = is_ack
    sub_row = w(is_fin_ack & at_ev, w(ack_committed, SUB_DONE, SUB_ABORTED), sub_row)
    sub_tm = w(is_fin_ack & at_ev, INF_US, sub_tm)
    # DS finish: ack back to the DM (release/grant + hotspot below)
    lcs_gate = (
        is_commit_fin & (s.first_lock[t, d_ev] < INF_US) & _measuring(cfg, s)
    )
    lcs_span = w(lcs_gate, (s.now - s.first_lock[t, d_ev] + 500) // 1000, 0)
    ack_salt = _salt(s, 47) + w(is_commit_fin, 0, 6)  # 47 commit, 53 abort
    kbase_ev, ktau_ev = _mw_link(s, s.on_repl[t, d_ev], d_ev, s.now)
    ack_send_t = kbase_ev + _delay(s, ktau_ev, ack_salt)
    sub_row = w(is_finish & at_ev, w(is_commit_fin, SUB_ACK, SUB_ABORT_ACK), sub_row)
    sub_tm = w(is_finish & at_ev, ack_send_t, sub_tm)
    # timeout abort fan-out (peer notify + own ack)
    abort_family = (
        (sub_row == SUB_ABORT_PEER) | (sub_row == SUB_ABORT_ACK) | (sub_row == SUB_ABORTED)
    )
    peers = inv_t & (dd != d_o) & ~abort_family
    ab_salts = _salt(s, 17) + dd
    if F:
        # abort notifications ride the effective links (see _initiate_abort)
        mesh_base, mesh_tau = _ds_send(s, d_o, dd, s.now)
        notify_direct = mesh_base + _delay_salted(s.jitter_milli, mesh_tau, ab_salts)
        up_base, up_tau = _mw_link(s, s.on_repl[t, d_o], d_o, s.now)
        to_dm = up_base + _delay(s, up_tau, _salt(s, 19))
        dn_base, dn_tau = _mw_link(s, s.on_repl[t], dd, to_dm)
        notify_via_dm = dn_base + _delay_salted(s.jitter_milli, dn_tau, ab_salts)
        notify = w(s.dyn.early_abort, notify_direct, notify_via_dm)
        ok_base, ok_tau = _mw_link(s, s.on_repl[t, d_o], d_o, s.now)
        own_ack_t = ok_base + _delay(s, ok_tau, _salt(s, 23))
    else:
        notify_direct = _delay_salted(s.jitter_milli, s.tau_ds[d_o], ab_salts)
        to_dm = _delay(s, s.tau_true[d_o], _salt(s, 19))
        notify_via_dm = to_dm + _delay_salted(s.jitter_milli, s.tau_true, ab_salts)
        notify = s.now + w(s.dyn.early_abort, notify_direct, notify_via_dm)
        own_ack_t = s.now + _delay(s, s.tau_true[d_o], _salt(s, 23))
    sub_row = w(is_timeout & peers, SUB_ABORT_PEER, sub_row)
    sub_tm = w(is_timeout & peers, notify, sub_tm)
    sub_row = w(is_timeout & at_do, SUB_ABORT_ACK, sub_row)
    sub_tm = w(is_timeout & at_do, own_ack_t, sub_tm)
    # first cause wins (mirrors _initiate_abort)
    abort_cause = s.abort_cause.at[t].set(
        w(
            is_timeout & (s.abort_cause[t] == CAUSE_NONE),
            CAUSE_TIMEOUT,
            s.abort_cause[t],
        )
    )
    s = s._replace(abort_cause=abort_cause)

    # ================== DM progress (round fan-in only) ====================
    # chiller stage-2: every dispatched sub voted -> release the held stage
    waiting_c = inv_t & (sub_row == SUB_CHILLER_WAIT)
    active_c = inv_t & ~waiting_c
    ready_chiller = (
        is_round_in
        & jnp.all(~active_c | (sub_row == SUB_VOTED))
        & jnp.any(waiting_c)
        & s.dyn.chiller_two_stage
    )
    sub_row = w(ready_chiller & waiting_c, SUB_SCHED, sub_row)
    sub_tm = w(ready_chiller & waiting_c, s.now, sub_tm)
    row_nn2 = s.op_state[t].astype(i32) != OP_NONE
    oh_row = jax.nn.one_hot(s.op_ds[t].astype(i32), D, dtype=bool)
    inv_rd = jnp.any(
        oh_row & (row_nn2 & (s.op_round[t] == s.cur_round[t]))[:, None], axis=0
    )
    all_rd = jnp.all(~inv_rd | rd_done_row)
    max_round = jnp.max(w(row_nn2, s.op_round[t].astype(i32), -1))
    final_t = s.cur_round[t].astype(i32) >= max_round
    aborting_t = ph0 == T_ABORT_WAIT
    act = is_round_in & all_rd & ~aborting_t
    advance = act & ~final_t
    # round advance: next round's subs dispatch at now + stagger
    nxt_round = (s.cur_round[t] + 1).astype(i32)
    cur_round = s.cur_round.at[t].set(
        w(advance, nxt_round, s.cur_round[t].astype(i32)).astype(jnp.int8)
    )
    s = s._replace(cur_round=cur_round)
    rd_done_row = w(advance, False, rd_done_row)
    inv_next = jnp.any(
        oh_row & (row_nn2 & (s.op_round[t].astype(i32) == nxt_round))[:, None], axis=0
    )
    # one shared stagger forecast: txn-start round 0 OR round advance
    inv0 = jnp.any(oh_b & (valid_b & (rnd_b == 0))[:, None], axis=0)
    stag_mask = w(is_start, inv0, inv_next)
    off = _stagger(cfg, s, t, stag_mask)
    # chiller first-round split (start only)
    tmin = jnp.min(w(inv0, s.tau_est, INF_US))
    stage1 = inv0 & (s.tau_est <= tmin)
    stage2 = inv0 & ~stage1
    chil_state = w(stage2, SUB_CHILLER_WAIT, w(stage1, SUB_SCHED, SUB_NONE))
    chil_time = w(stage1, s.now, INF_US)
    later = inv_new & ~inv0
    norm_state = w(inv0, SUB_SCHED, w(later, SUB_WAIT_ROUND, SUB_NONE))
    norm_time = w(inv0, s.now + off, INF_US)
    start_state = w(s.dyn.chiller_two_stage, chil_state, norm_state)
    start_time = w(s.dyn.chiller_two_stage, chil_time, norm_time)
    sub_row = w(dispatching, start_state, sub_row)
    sub_tm = w(dispatching, start_time, sub_tm)
    sub_row = w(advance & inv_next, SUB_SCHED, sub_row)
    sub_tm = w(advance & inv_next, s.now + off, sub_tm)
    # commit decision (commit > prepare > log-flush priority)
    all_at_dm = jnp.all(~inv_t | (sub_row == SUB_ROUND_AT_DM))
    all_voted = jnp.all(~inv_t | (sub_row == SUB_VOTED))
    dec_c, dec_p, dec_l = sched.commit_decision(
        s.dyn.prepare, all_at_dm, all_voted, centralized,
        PREPARE_NONE, PREPARE_COORD, PREPARE_DECENTRAL,
    )
    gate_dec = act & final_t
    send_c = gate_dec & dec_c
    send_p = gate_dec & dec_p & ~dec_c
    log_f = gate_dec & dec_l & ~dec_c & ~dec_p
    dm_base, dm_tau = _mw_link(s, s.on_repl[t], dd, s.now)
    c_salts = _salt(s, 11) + dd
    dt_commit = dm_base + _delay_salted(s.jitter_milli, dm_tau, c_salts)
    p_salts = _salt(s, 13) + dd
    dt_prepare = dm_base + _delay_salted(s.jitter_milli, dm_tau, p_salts)
    sub_row = w(send_c & inv_t, SUB_COMMIT_CMD, sub_row)
    sub_tm = w(send_c & inv_t, dt_commit, sub_tm)
    sub_row = w(send_p & inv_t, SUB_PREP_CMD, sub_row)
    sub_tm = w(send_p & inv_t, dt_prepare, sub_tm)
    # terminal commit-log flush fires: broadcast commit to every DS
    e_salts = _salt(s, 31) + dd
    dt_log = dm_base + _delay_salted(s.jitter_milli, dm_tau, e_salts)
    sub_row = w(is_logflush & inv_t, SUB_COMMIT_CMD, sub_row)
    sub_tm = w(is_logflush & inv_t, dt_log, sub_tm)

    # ============== shared release/grant + hotspot completion ==============
    rel_gate = is_finish | is_timeout
    d_rel = w(is_finish, d_ev, d_o)
    # hotspot Eq.(4) before/after release is equivalent (release preserves
    # op_key/op_ds and maps states to OP_DONE != OP_NONE)
    hs_mask = row_nn2 & (s.op_ds[t].astype(i32) == d_rel) & rel_gate
    hs_keys = s.op_key[t]
    hs = s.hs
    slot_f, found_f = hs_mod.lookup_slots(hs.slot_key, hs_keys, hs_mask)
    # the timeout handler accounts the partial round into sub_lel BEFORE the
    # Eq.(4) update; that add lives in sub_lel_row (scattered later), so fold
    # it into the value read here
    lel_f = (s.sub_lel[t, d_rel] + w(is_timeout, span_do, 0)).astype(jnp.float32)
    new_w = hs_mod.eq4_masked_w(hs.w_lat, slot_f, found_f, lel_f, cfg.alpha_milli)
    upd_f = found_f.astype(i32)
    hs = hs._replace(
        w_lat=hs.w_lat.at[slot_f].set(w(found_f, new_w, hs.w_lat[slot_f])),
        a_cnt=jnp.maximum(hs.a_cnt.at[slot_f].add(-upd_f), 0),
        t_cnt=hs.t_cnt.at[slot_f].add(upd_f),
        c_cnt=hs.c_cnt.at[slot_f].add(upd_f * is_commit_fin.astype(i32)),
    )
    s = s._replace(hs=hs)
    # release every lock txn t holds at d_rel + FIFO grants (exact
    # _release_and_grant semantics, output-gated)
    row_state2 = s.op_state[t].astype(i32)
    mine = row_nn2 & (s.op_ds[t].astype(i32) == d_rel)
    held = mine & ((row_state2 == OP_EXEC) | (row_state2 == OP_HOLD)) & rel_gate
    rel_keys = w(held, s.op_key[t], -2)
    cancel_mask = mine & rel_gate
    op_state = s.op_state.at[t].set(
        w(cancel_mask, OP_DONE, s.op_state[t].astype(i32)).astype(jnp.int8)
    )
    op_time = s.op_time.at[t].set(w(cancel_mask, INF_US, s.op_time[t]))
    s = s._replace(op_state=op_state, op_time=op_time)
    flat_state = s.op_state.reshape(-1).astype(i32)
    flat_key = s.op_key.reshape(-1)
    flat_write = s.op_write.reshape(-1)
    flat_enq = s.op_enq.reshape(-1)
    flat_ds = s.op_ds.reshape(-1).astype(i32)
    granted = _grant_decision(
        held, rel_keys, flat_state, flat_key, flat_write, flat_enq
    )
    exec_tg = s.now + _exec_us(cfg, s, flat_ds)
    op_state = w(granted, OP_EXEC, flat_state).astype(jnp.int8).reshape(T, K)
    op_time = w(granted, exec_tg, s.op_time.reshape(-1)).reshape(T, K)
    s = s._replace(op_state=op_state, op_time=op_time)
    gt = jnp.arange(T * K, dtype=i32) // K
    fl = s.first_lock.reshape(-1)
    g_idx = w(granted, gt * D + flat_ds, T * D)
    fl_pad = jnp.concatenate([fl, jnp.full((1,), INF_US, jnp.int32)])
    fl_pad = fl_pad.at[g_idx].min(w(granted, s.now, INF_US))
    s = s._replace(first_lock=fl_pad[: T * D].reshape(T, D))

    # =================== terminal finish (ack fan-in / O3 abort) ===========
    want = w(ack_committed, SUB_DONE, SUB_ABORTED)
    fin_done = is_fin_ack & jnp.all(~inv_t | (sub_row == want))
    gate_fin = fin_done | force_abort
    committed_fin = fin_done & ack_committed
    lat = s.now - s.arrive[t]
    meas = _measuring(cfg, s)
    hbin = _hist_bin(lat)
    slot_n = s.cur[t] % cfg.bank_txns
    one_c = w(gate_fin & meas & committed_fin, 1, 0)
    one_a = w(gate_fin & meas & ~committed_fin, 1, 0)
    dist = s.is_dist[t]
    lat_ms = (lat + 500) // 1000
    # abort-cause tally + fault-window goodput (mirrors _finish_txn)
    will_retry_fin = ~committed_fin & (s.retries[t] < s.dyn.max_retries)
    cause_fin = w(
        ~will_retry_fin & (s.retries[t] > 0), CAUSE_EXHAUSTED, s.abort_cause[t]
    )
    if F:
        # "during fault" means some DS is unreachable — crashed or
        # partitioned from the middleware (mirrors _finish_txn)
        any_down_f = jnp.any(s.ds_down | (s.mw_heal > s.now))
    else:
        any_down_f = jnp.any(s.ds_down)
    s = s._replace(
        ab_cause=s.ab_cause.at[cause_fin].add(one_a),
        commits_fault=s.commits_fault + w(any_down_f, one_c, 0),
    )
    s = s._replace(
        commits=s.commits + one_c,
        aborts=s.aborts + one_a,
        commits_dist=s.commits_dist + w(dist, one_c, 0),
        aborts_dist=s.aborts_dist + w(dist, one_a, 0),
        lat_sum=s.lat_sum + one_c * lat_ms,
        lat_sum_dist=s.lat_sum_dist + w(dist, one_c, 0) * lat_ms,
        hist_all=s.hist_all.at[hbin].add(one_c),
        hist_cen=s.hist_cen.at[hbin].add(w(dist, 0, one_c)),
        hist_dist=s.hist_dist.at[hbin].add(w(dist, one_c, 0)),
        slot_commits=s.slot_commits.at[t, slot_n].add(one_c, mode="drop"),
        slot_aborts=s.slot_aborts.at[t, slot_n].add(one_a, mode="drop"),
        slot_lat=s.slot_lat.at[t, slot_n].add(one_c * lat_ms, mode="drop"),
    )
    # per-txn row resets
    op_state = s.op_state.at[t].set(
        w(gate_fin, OP_NONE, s.op_state[t].astype(i32)).astype(jnp.int8)
    )
    op_time = s.op_time.at[t].set(w(gate_fin, INF_US, s.op_time[t]))
    inv = s.inv.at[t].set(w(gate_fin, False, s.inv[t]))
    sub_row = w(gate_fin, SUB_NONE, sub_row)
    sub_tm = w(gate_fin, INF_US, sub_tm)
    sub_lel_row = w(gate_fin, 0, sub_lel_row)
    first_lock = s.first_lock.at[t].set(
        w(gate_fin, INF_US, s.first_lock[t])
    )
    rd_done_row = w(gate_fin, False, rd_done_row)
    cur_round = s.cur_round.at[t].set(
        w(gate_fin, 0, s.cur_round[t].astype(i32)).astype(jnp.int8)
    )
    retry = gate_fin & ~committed_fin & (s.retries[t] < s.dyn.max_retries)
    base = s.dyn.retry_backoff_us
    jit_b = (
        _hash_u32(s.txn_ctr[t] * 977 + t.astype(i32) * 131 + s.retries[t])
        % jnp.maximum(base, 1).astype(jnp.uint32)
    ).astype(i32)
    # floor at 1 us so a zero-backoff retry against a still-down DS cannot
    # livelock the event loop (mirrors _finish_txn)
    backoff = jnp.maximum(base * (1 + jnp.minimum(s.retries[t], 7)) + jit_b, 1)
    retries = s.retries.at[t].set(
        w(gate_fin, w(retry, s.retries[t] + 1, 0), s.retries[t])
    )
    retry_same = s.retry_same.at[t].set(w(gate_fin, retry, s.retry_same[t]))
    blocked = s.blocked.at[t].set(w(gate_fin, 0, s.blocked[t]))
    cur = s.cur.at[t].add(w(gate_fin & ~retry, 1, 0))
    abort_cause = s.abort_cause.at[t].set(
        w(gate_fin, CAUSE_NONE, s.abort_cause[t])
    )
    s = s._replace(
        op_state=op_state, op_time=op_time, inv=inv, first_lock=first_lock,
        cur_round=cur_round, retries=retries, retry_same=retry_same,
        blocked=blocked, cur=cur, abort_cause=abort_cause,
    )

    # ======================= phase / terminal timer ========================
    phase = ph0
    phase = w(dispatching, T_ACTIVE, phase)
    phase = w(is_logflush | send_c, T_COMMIT_WAIT, phase)
    phase = w(log_f, T_COMMIT_LOG, phase)
    phase = w(is_timeout, T_ABORT_WAIT, phase)
    phase = w(gate_fin, T_IDLE, phase)
    tt0 = s.term_time[t]
    tt = tt0
    tt = w(block, s.now + s.dyn.admission_backoff_us, tt)
    tt = w(dispatching | is_logflush | send_c | is_timeout, INF_US, tt)
    tt = w(log_f, s.now + s.dyn.log_flush_us, tt)
    tt = w(gate_fin, w(committed_fin, s.now, s.now + backoff), tt)
    s = s._replace(
        phase=s.phase.at[t].set(phase.astype(jnp.int8)),
        term_time=s.term_time.at[t].set(tt),
    )

    # ======================= scatter the event rows ========================
    # WAN-leg charging (receive-side; mirrors the sequential handlers): op
    # arrival, DM round fan-in, prepare-cmd arrival, finish by PRE-state
    # (COMMIT_CMD yes, LOCAL_COMMIT no, ABORT_PEER only via the DM route),
    # and commit/abort ack fan-in each count one one-way WAN leg.
    wan_inc = (
        w(is_arrive, 1, 0)
        + w(is_round_in, 1, 0)
        + w(is_prep_cmd, 1, 0)
        + w(is_fin_ack, 1, 0)
        + w(is_sub & (sub0 == SUB_COMMIT_CMD), 1, 0)
        + w(is_abort_fin & ~s.dyn.early_abort, 1, 0)
    )
    s = s._replace(
        sub_state=s.sub_state.at[t].set(sub_row.astype(jnp.int8)),
        sub_time=s.sub_time.at[t].set(sub_tm),
        sub_lel=s.sub_lel.at[t].set(sub_lel_row),
        rd_done=s.rd_done.at[t].set(rd_done_row),
        lcs_sum=s.lcs_sum + lcs_span,
        lcs_cnt=s.lcs_cnt + lcs_gate.astype(i32),
        wan_legs=s.wan_legs + wan_inc,
    )

    # ============== replica failover bookkeeping (start / finish) ==========
    # one combined on_repl write: a dispatching start routes the hit subtxns
    # to their replicas (stale reads + staleness window recorded), a finish
    # releases the routing — the two gates are mutually exclusive. Written
    # after the scatter so every send above read the pre-update routing.
    if F:
        stale_w = w(fo, s.now - s.down_since + s.repl_lag_us, 0)
        on_repl_row = w(dispatching, fo, w(gate_fin, False, s.on_repl[t]))
        s = s._replace(
            on_repl=s.on_repl.at[t].set(on_repl_row),
            failovers=s.failovers + w(dispatching, jnp.sum(fo.astype(i32)), 0),
            stale_reads=s.stale_reads
            + w(
                dispatching,
                jnp.sum((valid_b & ~write_b & fo[ds_b.astype(i32)]).astype(i32)),
                0,
            ),
            max_stale_us=jnp.maximum(
                s.max_stale_us, w(dispatching, jnp.max(stale_w), 0)
            ),
        )

    # ============================== noop ===================================
    upd = dict(
        op_time=w(is_noop & (s.op_time == s.now), INF_US, s.op_time),
        sub_time=w(is_noop & (s.sub_time == s.now), INF_US, s.sub_time),
        term_time=w(is_noop & (s.term_time == s.now), INF_US, s.term_time),
        noops=s.noops + w(is_noop, 1, 0),
    )
    if cfg.max_faults:
        upd.update(
            fault_time=w(is_noop & (s.fault_time == s.now), INF_US, s.fault_time),
            hb_time=w(is_noop & (s.hb_time == s.now), INF_US, s.hb_time),
        )
    s = s._replace(**upd)

    # ===================== fault / heartbeat tail events ===================
    # Run dead last: the sub_row/sub_tm scatter above rewrites row `t` (a
    # stale row-0 copy for tail events) and would clobber the crash
    # cascade's sub-state writes if these ran any earlier.
    if cfg.max_faults:
        s = _fault_event(cfg, s, f_ev, is_fault_ev)
        s = _hb_event(cfg, s, d_hb, is_hb_ev)
    return s
