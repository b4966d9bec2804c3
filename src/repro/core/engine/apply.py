"""Masked window application + the map-lane drain step.

`_apply_window` materializes a planned window (see `window._window_plan`) in
ONE masked pass, bitwise-identical to stepping its events sequentially;
`_drain_step` is the scalar (map-lane) drain entry, cond-gated behind the
cheap `_drainable_due` pre-check. The lockstep (vmap) lanes reuse both
through `fused._omni_window`, so window formation — and the drain telemetry
— is identical across strategies.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from repro.core import hotspot as hs_mod
from repro.core.netmodel import INF_US, ewma_update
from repro.core.workloads import Bank

from repro.core.engine.state import (
    N_STOP_REASONS,
    OP_NONE,
    OP_PENDING,
    OP_ENROUTE,
    OP_QUEUED,
    OP_EXEC,
    OP_HOLD,
    OP_DONE,
    SUB_SCHED,
    SUB_RUN,
    SUB_ROUND_REPLY,
    SUB_PREP_CMD,
    SUB_PREPARING,
    SUB_VOTE,
    SUB_COMMIT_CMD,
    SUB_ACK,
    SUB_LOCAL_COMMIT,
    SUB_ABORT_PEER,
    SUB_ABORT_ACK,
    T_COMMIT_LOG,
    T_COMMIT_WAIT,
    SimConfig,
    SimState,
    _at_ds,
    _times_flat,
)
from repro.core.engine.step import _step
from repro.core.engine.window import K_EWMA, _window_plan

if TYPE_CHECKING:
    from repro.core.engine.window import _PlanVals

@jax.named_scope("repro/apply")
def _apply_window(
    cfg: SimConfig,
    s_: SimState,
    v: _PlanVals,
    act_term,
    act_sub,
    act_op,
    t_now,
    iters_inc,
    drained_inc,
    windows_inc,
    stops_inc,
    fused_inc=0,
    xcancel=None,
    xlel=None,
    xcommit=None,
    xrel=None,
    act_hb=None,
    chained_inc=0,
    act_fu=None,
    act_pfu=None,
) -> SimState:
    """Materialize a planned window (the events under the act_* masks) in one
    masked pass, bitwise-identical to stepping them sequentially.

    `act_*` is usually the window membership (`v.win_*`); the fused lockstep
    pass instead selects window-OR-single-event masks and folds the
    non-drainable single event's release footprint in via `xcancel` /
    `xlel` / `xcommit` / `xrel` so the heavy hotspot kernel is traced
    exactly once.
    """
    T, D, K = cfg.terminals, cfg.num_ds, cfg.max_ops
    i32 = jnp.int32
    st = s_.op_state
    sst = s_.sub_state
    inv = s_.inv
    evt_sub = s_.sub_time
    evt_op = s_.op_time
    d_of = s_.op_ds.astype(i32)
    oh_d = jax.nn.one_hot(d_of, D, dtype=bool)
    opn = st != OP_NONE
    same_round = s_.op_round == s_.cur_round[:, None]
    kk = jnp.arange(K, dtype=i32)

    # ---- windowed masks ---------------------------------------------------
    due_log = act_term & v.cat_log
    due_sched = act_sub & v.cat_sched
    due_prep = act_sub & v.cat_prep
    due_preparing = act_sub & v.cat_preparing
    dm_mask = act_sub & v.dm_cat  # every one's row view is exact by plan
    due_commit = act_sub & v.cat_commit
    f_mask = act_sub & v.f_cat
    due_arr = act_op & v.cat_arr
    due_exec = act_op & v.cat_exec
    do_chain = due_exec & v.has_next
    rd = due_exec & ~v.has_next
    rd_td = jnp.any(oh_d & rd[:, :, None], axis=1)
    sub_upd = rd_td & ~v.aborting_td
    # triggering fan-ins in the window (at most one per terminal, always the
    # last in-window event of its terminal — plan rule b)
    send_c_wj = dm_mask & v.send_c_j
    send_p_wj = dm_mask & v.send_p_j
    log_wj = dm_mask & v.log_t_j
    send_c_w = jnp.any(send_c_wj, axis=1)
    send_p_w = jnp.any(send_p_wj, axis=1)
    log_w = jnp.any(log_wj, axis=1)
    dt_commit_w = jnp.max(
        jnp.where(send_c_wj[:, :, None], v.dt_commit3, 0), axis=1
    )
    dt_prepare_w = jnp.max(
        jnp.where(send_p_wj[:, :, None], v.dt_prepare3, 0), axis=1
    )
    log_term_w = jnp.max(jnp.where(log_wj, v.log_term_j, 0), axis=1)
    cancel = opn & _at_ds(f_mask, d_of)
    if xcancel is not None:
        cancel = cancel | xcancel

    # ---- op arrays: arrivals/execs, chained statements, dispatch marks,
    # commit/abort cancellations (masks pairwise disjoint) ------------------
    op_state = jnp.where(
        due_arr, v.arr_state, jnp.where(due_exec, OP_HOLD, st.astype(i32))
    )
    op_time = jnp.where(due_arr, v.arr_time, jnp.where(due_exec, INF_US, s_.op_time))
    op_enq = jnp.where(due_arr, evt_op, s_.op_enq)
    tgt3_w = v.tgt3 & do_chain[:, :, None]
    chain_tgt = jnp.any(tgt3_w, axis=1)  # [T,K] chain-target slots
    pick = lambda x: jnp.max(jnp.where(tgt3_w, x[:, :, None], 0), axis=1)
    op_state = jnp.where(chain_tgt, pick(v.chain_state), op_state)
    op_time = jnp.where(chain_tgt, pick(v.chain_time), op_time)
    op_enq = jnp.where(chain_tgt, pick(evt_op), op_enq)
    sched_w = _at_ds(due_sched, d_of)
    c_ops_w = sched_w & (st == OP_PENDING) & same_round
    is_first_w = (
        c_ops_w
        & (_at_ds(v.first_c, d_of) == kk[None, :])
        & _at_ds(v.has_c, d_of)
    )
    arr_at_op = _at_ds(v.eff_arrival_td, d_of)
    op_state = jnp.where(
        c_ops_w, jnp.where(is_first_w, OP_ENROUTE, OP_QUEUED), op_state
    )
    op_time = jnp.where(is_first_w, arr_at_op, op_time)
    # chained follow-up entities (two-pass plan): entity (r, g) completes
    # comp_k (-> HOLD) at u_g and attempts att_k (-> EXEC/WAIT). Attempts
    # land first: an entity's completion slot IS its parent's attempt target,
    # and sequentially the completion overwrites the grant. Per-slot writers
    # are unique by the plan's dup rule + the argmax-and-clear queue walk.
    ids_tk = jnp.arange(T * K, dtype=i32)
    if act_fu is not None:
        att_m = act_fu & v.fu_att_has
        att_idx = jnp.where(att_m, v.fu_term[:, None] * K + v.fu_att_k, T * K)
        hit_att = att_idx.T.reshape(-1)[:, None] == ids_tk[None, :]
        pick_att = lambda x: jnp.max(
            jnp.where(hit_att, x.T.reshape(-1)[:, None], 0), axis=0
        ).reshape(T, K)
        att_any = jnp.any(hit_att, axis=0).reshape(T, K)
        op_state = jnp.where(att_any, pick_att(v.fu_att_state), op_state)
        op_time = jnp.where(att_any, pick_att(v.fu_att_time), op_time)
        op_enq = jnp.where(att_any, pick_att(v.fu_u), op_enq)
        comp_idx = jnp.where(act_fu, v.fu_term[:, None] * K + v.fu_comp_k, T * K)
        hit_comp = comp_idx.T.reshape(-1)[:, None] == ids_tk[None, :]
        comp_any = jnp.any(hit_comp, axis=0).reshape(T, K)
        op_state = jnp.where(comp_any, OP_HOLD, op_state)
        op_time = jnp.where(comp_any, INF_US, op_time)
    op_state = jnp.where(cancel, OP_DONE, op_state).astype(jnp.int8)
    op_time = jnp.where(cancel, INF_US, op_time)

    got = (due_arr & v.ok) | (do_chain & v.ok_chain)
    got_t = jnp.min(
        jnp.where(oh_d & got[:, :, None], evt_op[:, :, None], INF_US), axis=1
    )
    first_lock = jnp.minimum(s_.first_lock, got_t)
    if act_fu is not None:
        # granted follow-up attempts feed first-lock at their own u_g
        ids_td = jnp.arange(T * D, dtype=i32)
        hit_ftd = (v.fu_term * D + v.fu_d)[:, None] == ids_td[None, :]
        att_got = att_m & v.fu_att_ok
        got_r = jnp.min(jnp.where(att_got, v.fu_u, INF_US), axis=1)
        got_t2 = jnp.min(
            jnp.where(hit_ftd, got_r[:, None], INF_US), axis=0
        ).reshape(T, D)
        first_lock = jnp.minimum(first_lock, got_t2)

    # ---- sub arrays: self-updates first, then whole-row broadcasts --------
    sub_state = jnp.where(sub_upd, v.new_sub_state, sst.astype(i32))
    sub_time = jnp.where(sub_upd, v.new_sub_time, s_.sub_time)
    sub_state = jnp.where(due_prep, SUB_PREPARING, sub_state)
    sub_time = jnp.where(due_prep, v.prep_time, sub_time)
    sub_state = jnp.where(due_preparing, SUB_VOTE, sub_state)
    sub_time = jnp.where(due_preparing, v.vote_t, sub_time)
    sub_state = jnp.where(due_sched, SUB_RUN, sub_state)
    sub_time = jnp.where(due_sched, INF_US, sub_time)
    sub_arrive = jnp.where(due_sched, v.arrival_td, s_.sub_arrive)
    sub_fast = jnp.where(due_sched, v.fast_disp_td, s_.sub_fast)
    sub_state = jnp.where(dm_mask, v.dm_self, sub_state)
    sub_time = jnp.where(dm_mask, INF_US, sub_time)
    row_c = send_c_w[:, None] & inv
    sub_state = jnp.where(row_c, SUB_COMMIT_CMD, sub_state)
    sub_time = jnp.where(row_c, dt_commit_w, sub_time)
    row_p = send_p_w[:, None] & inv
    sub_state = jnp.where(row_p, SUB_PREP_CMD, sub_state)
    sub_time = jnp.where(row_p, dt_prepare_w, sub_time)
    row_e = due_log[:, None] & inv
    sub_state = jnp.where(row_e, SUB_COMMIT_CMD, sub_state)
    sub_time = jnp.where(row_e, v.dt_log, sub_time)
    sub_state = jnp.where(due_commit, SUB_ACK, sub_state)
    sub_state = jnp.where(f_mask & ~due_commit, SUB_ABORT_ACK, sub_state)
    sub_time = jnp.where(f_mask, v.ack_t, sub_time)
    sub_lel = s_.sub_lel + jnp.where(
        rd_td, jnp.maximum(v.time_rd - s_.sub_arrive, 0), 0
    )
    # chained round completions / prepare-flush votes. Their (t, d) slots are
    # disjoint from every pass-1 sub write above (one in-flight round per
    # (t, d); a same-slot dispatch or release cannot share the window), so
    # these are pure additional writers — except the prepare flush, which
    # deliberately overwrites its own parent's PREP_CMD -> PREPARING write.
    fu_fast = jnp.int32(0)
    if act_fu is not None:
        rd_g = act_fu & v.fu_rd  # [W,G]; at most one g per row
        rd_w_g = rd_g & v.fu_rd_wr
        rd_any_r = jnp.any(rd_g, axis=1)
        rd_w_r = jnp.any(rd_w_g, axis=1)
        rd_u_r = jnp.max(jnp.where(rd_g, v.fu_u, 0), axis=1)
        rd_state_r = jnp.max(jnp.where(rd_w_g, v.fu_rd_state, 0), axis=1)
        rd_time_r = jnp.max(jnp.where(rd_w_g, v.fu_rd_time, 0), axis=1)
        sc_td = lambda val, m: jnp.max(
            jnp.where(hit_ftd & m[:, None], val[:, None], 0), axis=0
        ).reshape(T, D)
        rd2_w = jnp.any(hit_ftd & rd_w_r[:, None], axis=0).reshape(T, D)
        sub_state = jnp.where(rd2_w, sc_td(rd_state_r, rd_w_r), sub_state)
        sub_time = jnp.where(rd2_w, sc_td(rd_time_r, rd_w_r), sub_time)
        rd2_any = jnp.any(hit_ftd & rd_any_r[:, None], axis=0).reshape(T, D)
        sub_lel = sub_lel + jnp.where(
            rd2_any,
            jnp.maximum(sc_td(rd_u_r, rd_any_r) - s_.sub_arrive, 0),
            0,
        )
        fu_fast = jnp.sum(rd_w_g & (v.fu_rd_state == SUB_LOCAL_COMMIT), dtype=i32)
    if act_pfu is not None:
        ids_td2 = jnp.arange(T * D, dtype=i32)
        pfu_idx = jnp.where(act_pfu, v.cand_t_sub * D + v.cand_d_sub, T * D)
        hit_pfu = pfu_idx[:, None] == ids_td2[None, :]
        pfu_m = jnp.any(hit_pfu, axis=0).reshape(T, D)
        pfu_t = jnp.max(
            jnp.where(hit_pfu, v.pfu_vote_t[:, None], 0), axis=0
        ).reshape(T, D)
        sub_state = jnp.where(pfu_m, SUB_VOTE, sub_state)
        sub_time = jnp.where(pfu_m, pfu_t, sub_time)
    rd_done = s_.rd_done | (dm_mask & v.cat_prog)

    # ---- latency monitor: one exact EWMA application per in-window fan-in
    # (the plan caps a DS column at K_EWMA fan-ins, so the unrolled chain
    # composes them exactly; tau_est is never read inside a window — the only
    # readers, txn starts and round advances, are non-drainable) ------------
    if s_.fault_time.shape[0]:
        # monitor freeze mirrors the sequential `_ewma_est` gate — crashed-DS
        # fan-ins and replica-link fan-ins don't feed the EWMA — and the
        # sample is the *effective* RTT so degrades are observed. Neither
        # ds_down, link state nor replica routing can change inside a window
        # (fault events are pinned, starts/finishes are non-drainable).
        cnt_d = jnp.sum(
            dm_mask & ~(s_.ds_down[None, :] | s_.on_repl), axis=0, dtype=i32
        )
        mon_sample = s_.tau_mw_eff
    else:
        cnt_d = jnp.sum(dm_mask, axis=0, dtype=i32)  # [D]
        mon_sample = s_.tau_true
    tau_est = s_.tau_est
    for i in range(K_EWMA):
        tau_est = jnp.where(
            cnt_d > i,
            ewma_update(tau_est, mon_sample, jnp.int32(cfg.beta_milli)),
            tau_est,
        )

    # ---- terminal phase/timer (window events own their terminals) ---------
    phase = jnp.where(send_c_w, T_COMMIT_WAIT, s_.phase.astype(i32))
    phase = jnp.where(log_w, T_COMMIT_LOG, phase)
    phase = jnp.where(due_log, T_COMMIT_WAIT, phase).astype(jnp.int8)
    term_time = jnp.where(send_c_w | due_log, INF_US, s_.term_time)
    term_time = jnp.where(log_w, log_term_w, term_time)

    # ---- hotspot table: one slot write per released footprint key ---------
    # Releases live at sub candidates (plus the fused pass's folded rank-0
    # release, `xrel`), so the footprint lookup + Eq.(4) run on compact
    # [W, K] rows and the table update is ONE packed scatter-add over [W*K]
    # indices — vmapped scatters serialize per index on CPU, and the four
    # [T,D,K]-wide scatters this block used to issue dominated the whole
    # lockstep iteration.
    with jax.named_scope("repro/hotspot"):
        Wc = v.cand_i.shape[0]
        wr = jnp.arange(Wc, dtype=i32)
        t_rel = v.cand_t_sub
        d_rel = v.cand_d_sub
        rel_act = v.cand_is_sub & f_mask[t_rel, d_rel]
        if xrel is not None:
            r0, rt0, rd0 = xrel
            at0 = (wr == 0) & r0
            rel_act = rel_act | at0
            t_rel = jnp.where(at0, rt0, t_rel)
            d_rel = jnp.where(at0, rd0, d_rel)
        key_rel = s_.op_key[t_rel]  # [W,K]
        st_rel = s_.op_state[t_rel].astype(i32)
        ds_rel = s_.op_ds[t_rel].astype(i32)
        cancel_rel = rel_act[:, None] & (st_rel != OP_NONE) & (ds_rel == d_rel[:, None])
        slot_c, found_c = hs_mod.lookup_slots(
            s_.hs.slot_key,
            jnp.where(cancel_rel, key_rel, -1).reshape(-1),
            cancel_rel.reshape(-1),
        )
        slot_rel = slot_c.reshape(Wc, K)
        found_rel = found_c.reshape(Wc, K)
        lel_td = s_.sub_lel if xlel is None else s_.sub_lel + xlel
        lel_rel = lel_td[t_rel, d_rel].astype(jnp.float32)[:, None]  # [W,1]
        new_w = hs_mod.eq4_masked_w(
            s_.hs.w_lat, slot_rel, found_rel, lel_rel, cfg.alpha_milli
        )
        committed_td = due_commit if xcommit is None else due_commit | xcommit
        committed_rel = committed_td[t_rel, d_rel][:, None] & found_rel
        # w_lat keeps scatter-SET semantics (duplicated keys inside one footprint
        # write one identical Eq.(4) value — expressing the set as a packed add
        # changes XLA's float-fusion context and costs a 1-ulp divergence); the
        # three counters pack into one scatter-add.
        upd = found_rel.astype(i32)
        tbl = jnp.stack([s_.hs.a_cnt, s_.hs.t_cnt, s_.hs.c_cnt], axis=1)  # [C+1, 3]
        tbl = tbl.at[slot_c].add(
            jnp.stack([-upd, upd, committed_rel.astype(i32)], axis=2).reshape(-1, 3)
        )
        found_fl = found_rel.reshape(-1)
        hs = s_.hs._replace(
            w_lat=s_.hs.w_lat.at[slot_c].set(
                jnp.where(found_fl, new_w.reshape(-1), s_.hs.w_lat[slot_c])
            ),
            a_cnt=jnp.maximum(tbl[:, 0], 0),
            t_cnt=tbl[:, 1],
            c_cnt=tbl[:, 2],
        )

    # lock-contention-span metric (commit events, per-event warmup gate)
    lcs_have = due_commit & (s_.first_lock < INF_US) & (
        evt_sub >= jnp.int32(cfg.warmup_us)
    )
    lcs_span = jnp.where(lcs_have, (evt_sub - s_.first_lock + 500) // 1000, 0)

    # WAN-leg charging (receive-side, mirrors the sequential handlers): op
    # arrivals, DM fan-ins (round replies/votes and commit/abort acks),
    # prepare-cmd arrivals, and finishes by PRE-state — COMMIT_CMD arrived
    # over the WAN, LOCAL_COMMIT was decided on-site, ABORT_PEER only rode
    # the WAN when routed via the DM (~early_abort). fast_commits counts
    # round completions landing directly in SUB_LOCAL_COMMIT (YUGA
    # centralized, FASTC co-commit, TIGA in-slack single-round).
    wan_inc = (
        jnp.sum(due_arr, dtype=i32)
        + jnp.sum(dm_mask, dtype=i32)
        + jnp.sum(due_prep, dtype=i32)
        + jnp.sum(f_mask & (sst == SUB_COMMIT_CMD), dtype=i32)
        + jnp.sum(f_mask & (sst == SUB_ABORT_PEER) & ~s_.dyn.early_abort, dtype=i32)
    )
    fast_inc = (
        jnp.sum(sub_upd & (v.new_sub_state == SUB_LOCAL_COMMIT), dtype=i32)
        + fu_fast
    )

    # ---- in-window heartbeat probes (satellite of the typed fault model):
    # mirrors `_hb_event` with now = the slot's scheduled time — count and
    # re-arm a firing probe, disarm a non-firing one. Reachability cannot
    # change inside a window, so the plan's fire predicate is exact.
    extra = {}
    if s_.fault_time.shape[0] and act_hb is not None:
        hb_fired = act_hb & v.hb_fire
        extra["hb_count"] = s_.hb_count + hb_fired.astype(i32)
        extra["hb_time"] = jnp.where(
            hb_fired,
            s_.hb_time + s_.dyn.hb_interval_us,
            jnp.where(act_hb, INF_US, s_.hb_time),
        )

    return s_._replace(
        **extra,
        now=t_now,
        iters=s_.iters + iters_inc,
        drained=s_.drained + drained_inc,
        windows=s_.windows + windows_inc,
        win_stops=s_.win_stops + stops_inc,
        fused=s_.fused + fused_inc,
        chained=s_.chained + chained_inc,
        op_state=op_state,
        op_time=op_time,
        op_enq=op_enq,
        first_lock=first_lock,
        sub_state=sub_state.astype(jnp.int8),
        sub_time=sub_time,
        sub_arrive=sub_arrive,
        sub_fast=sub_fast,
        sub_lel=sub_lel,
        rd_done=rd_done,
        tau_est=tau_est,
        phase=phase,
        term_time=term_time,
        hs=hs,
        lcs_sum=s_.lcs_sum + jnp.sum(lcs_span),
        lcs_cnt=s_.lcs_cnt + jnp.sum(lcs_have.astype(i32)),
        wan_legs=s_.wan_legs + wan_inc,
        fast_commits=s_.fast_commits + fast_inc,
    )


@jax.named_scope("repro/plan")
def _drainable_due(s: SimState) -> jax.Array:
    """Cheap drainability pre-check shared by the map and lockstep drain
    paths: True iff every event due at the minimum timestamp belongs to a
    statically drainable category. Sharing the formula keeps window formation
    — and therefore the drain telemetry — identical across strategies."""
    t_now = jnp.min(_times_flat(s))
    due_term = s.term_time == t_now
    due_sub = s.sub_time == t_now
    due_op = s.op_time == t_now
    sst = s.sub_state
    sub_drainable = (
        (sst == SUB_SCHED)
        | (sst == SUB_ROUND_REPLY)
        | (sst == SUB_PREP_CMD)
        | (sst == SUB_PREPARING)
        | (sst == SUB_VOTE)
        | (sst == SUB_COMMIT_CMD)
        | (sst == SUB_LOCAL_COMMIT)
        | (sst == SUB_ACK)
        | (sst == SUB_ABORT_PEER)
        | (sst == SUB_ABORT_ACK)
    )
    op_drainable = (s.op_state == OP_ENROUTE) | (s.op_state == OP_EXEC)
    clean = (
        ~jnp.any(due_term & (s.phase != T_COMMIT_LOG))
        & ~jnp.any(due_sub & ~sub_drainable)
        & ~jnp.any(due_op & ~op_drainable)
    )
    if s.fault_time.shape[0]:
        # a due fault event (crash/recovery/partition/degrade transition)
        # always takes the sequential step; heartbeat probes are conflict-free
        # within a window (reachability cannot change mid-window) and drain.
        clean = clean & ~jnp.any(s.fault_time == t_now)
    return clean


@jax.named_scope("repro/apply")
def _drain_step(cfg: SimConfig, bank: Bank, s: SimState) -> SimState:
    """One drain iteration of the scalar (map-lane) hot path: apply the
    maximal conflict-free window of events in one masked pass.

    Cheap pre-checks route to the windowed masked pass only when every event
    due at the minimum timestamp belongs to a drainable category; txn starts
    (admission + hot-table claims), lock-wait timeouts (abort fan-out through
    the grant machinery), fault-injection events (crash/recovery cascades,
    heartbeat probes) and unexpected states always take the sequential
    single-event step, as does any window the prefix scan cuts below two
    events. Bitwise-identical to `_step` (`drain=False`); the windowed-drain
    telemetry (`SimState.drained/windows/win_stops`) is the only divergence.
    """
    clean = _drainable_due(s)

    def windowed(s_: SimState) -> SimState:
        v = _window_plan(cfg, bank, s_)

        def apply_fn(s2: SimState) -> SimState:
            return _apply_window(
                cfg,
                s2,
                v,
                v.win_term,
                v.win_sub,
                v.win_op,
                v.t_last,
                v.n_win,
                v.n_win,
                jnp.int32(1),
                jax.nn.one_hot(v.stop_code, N_STOP_REASONS, dtype=jnp.int32),
                act_hb=v.win_hb,
                chained_inc=v.n_chained,
                act_fu=v.fu_win,
                act_pfu=v.pfu_win,
            )

        return jax.lax.cond(v.use, apply_fn, lambda s2: _step(cfg, bank, s2), s_)

    return jax.lax.cond(clean, windowed, lambda s_: _step(cfg, bank, s_), s)
