"""Windowed conflict-free drain: batch the maximal prefix of the event order.

`_window_plan` ranks the concatenated event-time view into the exact
sequential processing order and finds the longest conflict-free prefix;
`_apply_window` materializes the whole window in ONE masked pass,
bitwise-identical to single-event stepping. `_drain_step` is the map-lane
entry (cond-gated behind a cheap drainability pre-check); the lockstep
(vmap) lanes run the fused plan+omnibus pass in `fused._omni_window`, which
shares `_window_plan`/`_apply_window` so both strategies form — and count —
exactly the same windows.

Window stoppers (slot-accurate read/write sets — see docs/architecture.md):

* non-drainable categories (txn start, lock-wait timeout, round advance,
  chiller stage-2 re-dispatch, txn-completing ack, release with a queued
  waiter) pin their earliest-scheduled-time to 0; a round advance that ends
  a window is counted as `round`, every other pin as `nondrainable`;
* an event scheduling work at/before the window's timestamps (running-min
  rule over earliest-scheduled-times);
* the second touch of one lock key (arrival / chain target / released
  footprint), via per-key first-touch ranks on the eq_key matrix;
* the slot-accurate DM rules: a *triggering* fan-in (one that fires a
  commit/prepare/log broadcast, a round advance, a chiller re-dispatch or a
  terminal finish) writes its whole row and stays forward-exclusive, and a
  fan-in's row read is only exact when every earlier in-window event of its
  terminal is itself a non-triggering fan-in — but *non-triggering* fan-ins
  write only their own (terminal, DS) slot, so any number of them coexist
  per terminal and per window (the pre-PR-5 rules stopped at the second
  fan-in per terminal and per DS);
* at most `K_EWMA` fan-ins per data source (the latency monitor composes
  that many exact EWMA applications per window);
* a release sharing its (terminal, DS) with an earlier op event;
* fault-schedule events (typed crash/partition/degrade starts and ends,
  present only when ``SimConfig.max_faults > 0``) are always pinned: a due
  one stops the window at itself (stop reason `fault`) and runs through the
  sequential fault handler. Heartbeat probes, by contrast, are conflict-free
  (they write only their own counter/timer and read link state no window
  event can change) and drain inside windows like any other event — their
  re-arm time enters the running-min "scheduled" rule.

Two-pass chain admission (PR 10): the running-min "scheduled" rule used to
stop the window whenever an in-window event scheduled work inside the
window's time range — which is exactly what every zero-RTT dispatch/exec
chain does (a granted lock arrival schedules its own exec completion
`exec_us` later; an exec completion chains the next queued statement; a
prepare command schedules its WAL flush). The plan's second pass therefore
*admits* those follow-ups as first-class window entities: for each op
candidate it walks the statement queue up to `CHAIN_DEPTH` generations of
virtual exec completions (each with the lock grant, timestamps and salted
delays it would have had sequentially), and for each prepare-command
candidate the PREPARING->VOTE flush. Candidates and follow-ups merge into
one (time, flat-index, is-follow-up) rank order; every salted value is
computed from the merged rank, so admitted windows stay bitwise-identical
to sequential stepping. A follow-up whose own follow-up cannot be admitted
stops the window with the `sched_chain` reason (the fence the pre-chaining
plan would have hit earlier is still `scheduled`), and `SimState.chained`
counts admitted follow-ups.

Every windowed event keeps the iteration number (hash salt) and timestamp it
would have had sequentially, so drained runs stay bitwise-identical to
`drain=False` (asserted across presets, jitters, zero-RTT tie storms and
abort-heavy workloads for all four step modes).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import scheduler as sched
from repro.core.netmodel import INF_US
from repro.core.protocols import (
    PREPARE_COORD,
    PREPARE_DECENTRAL,
    PREPARE_NONE,
)
from repro.core.workloads import Bank

# the two-pass chain admitter (follow-up entities, merged ranks, effect
# values, the shared entity-space prefix scan) and the plan output type live
# in chain.py; `_PlanVals` and the STOP_* codes are re-exported here for the
# applier / fused passes and tests.
from repro.core.engine.chain import (
    CHAIN_DEPTH,
    STOP_CAP,
    STOP_DM_COL,
    STOP_DM_ROW,
    STOP_FAULT,
    STOP_HORIZON,
    STOP_LOCK_KEY,
    STOP_NONDRAINABLE,
    STOP_REL_OP,
    STOP_ROUND,
    STOP_SCHED_CHAIN,
    STOP_SCHEDULED,
    _PlanVals,
    chain_effects,
    chain_entities,
    entity_admission,
    merged_ranks,
)
from repro.core.engine.state import (
    OP_NONE,
    OP_PENDING,
    OP_ENROUTE,
    OP_QUEUED,
    OP_WAIT,
    OP_EXEC,
    OP_HOLD,
    SUB_SCHED,
    SUB_ROUND_REPLY,
    SUB_ROUND_AT_DM,
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
    T_ABORT_WAIT,
    T_COMMIT_LOG,
    _SALT_MUL,
    SimConfig,
    SimState,
    _at_ds,
    _delay_salted,
    _exec_us,
    _lock_wait_deadline,
    _mw_send,
    _round_done_transition,
    _tiga_arrival,
    _tiga_fast,
    _times_flat,
)

# Max DM fan-ins per data source per window: the latency monitor applies one
# EWMA update per fan-in, composed exactly by unrolling this many masked
# applications in `_apply_window`; the (K_EWMA+1)-th same-column fan-in stops
# the window (stop reason `dm_col`).
K_EWMA = 4

# Window candidate budget: only the PLAN_CAP lex-smallest events can join one
# window (longer windows split bitwise-identically across iterations). Keeping
# the candidate set small is what makes the lockstep plan cheap: ranks and
# the running-min prefix cost O(PLAN_CAP * M) / O(PLAN_CAP^2) elementwise
# work instead of the O(M^2) comparison matrices the pre-PR-5 plan paid per
# iteration. Both rank routes cap identically so the drain telemetry stays
# strategy-independent. Raised 8 -> 16 with the two-pass chain admitter:
# once follow-ups stop tripping the scheduling fence, windows actually reach
# the old cap (cap stops only matter once the fence falls, per ROADMAP).
PLAN_CAP = 16


@jax.named_scope("repro/plan")
def _window_plan(cfg: SimConfig, bank: Bank, s: SimState) -> _PlanVals:
    """Plan the maximal conflict-free *prefix* (window) of the global event
    order — the generalization of the tie-only drain to events at distinct
    timestamps.

    Per-event timestamps are the event queues themselves; ranking the
    concatenated [T + T*D + T*K] time view with one stable sort reproduces
    the sequential processing order exactly (time, then flat-index
    tie-break). A prefix scan then finds the longest prefix such that every
    event is drainable, nothing is scheduled into the window's time range,
    and no two window events interact under the slot-accurate read/write-set
    rules listed in the module docstring. Order-aware pairwise conflicts mark
    the *later* event of each conflicting pair, so the window stops exactly
    at the first conflicting event — whose stop reason is recorded.

    Two bitwise-identical rank/prefix routes: the scalar (map) path uses one
    stable argsort + cummin; the lockstep path counts with M x M comparison
    matrices, because batched sorts/scans under vmap lower to pathologically
    slow per-lane loops on CPU while the matrices are pure elementwise work
    shared across lanes.
    """
    T, D, K, F = cfg.terminals, cfg.num_ds, cfg.max_ops, cfg.max_faults
    M0 = T + T * D + T * K
    # fault/heartbeat tail slots exist only on fault-carrying configs; they
    # are always pinned (never drained), so a due fault stops the window at
    # itself and routes through the sequential fault handler.
    M = M0 + (F + D if F else 0)
    i32 = jnp.int32
    BIG = jnp.int32(M)
    st = s.op_state
    sst = s.sub_state
    inv = s.inv
    evt_term = s.term_time
    evt_sub = s.sub_time
    evt_op = s.op_time
    flat = _times_flat(s)

    # ---- sequential ranks of the flat time view ----------------------------
    # pos[e] = #events lexicographically before e by (time, flat index) — the
    # exact sequential processing order. Only the W = PLAN_CAP lex-smallest
    # events (the window candidates) need exact ranks; everything else
    # saturates at W, which no window comparison can reach. The lockstep
    # route extracts the candidates with W masked argmins ([M] reductions —
    # batched sorts/scatters under vmap lower to per-lane loops on CPU) and
    # ranks every slot against them with one [W, M] comparison; the scalar
    # (map) route keeps the stable argsort. Ranks below W agree bitwise
    # between the two routes, and every window decision only consults those.
    W = min(PLAN_CAP, M)
    maxi = jnp.int32(2**31 - 1)
    ids_m = jnp.arange(M, dtype=i32)
    if cfg.lockstep:
        mflat = flat
        cand_is, cand_ts = [], []
        for _ in range(W):
            j = jnp.argmin(mflat).astype(i32)
            cand_is.append(j)
            cand_ts.append(flat[j])
            mflat = jnp.where(ids_m == j, maxi, mflat)
        cand_i = jnp.stack(cand_is)  # [W] flat indices, rank order
        cand_t = jnp.stack(cand_ts)
        # time of the first NON-candidate slot: the chain admitter only
        # trusts follow-up times strictly below it (nothing outside the
        # candidate set can interleave an admitted follow-up)
        t_w1 = jnp.min(mflat)
        lex_before = (cand_t[:, None] < flat[None, :]) | (
            (cand_t[:, None] == flat[None, :]) & (cand_i[:, None] < ids_m[None, :])
        )  # [W, M]: candidate i processed before slot e
        pos = jnp.sum(lex_before, axis=0, dtype=i32)
    else:
        order = jnp.argsort(flat, stable=True)
        pos = jnp.zeros((M,), i32).at[order].set(jnp.arange(M, dtype=i32))
        cand_i = order[:W].astype(i32)
        cand_t = flat[cand_i]
        t_w1 = flat[order[W]] if M > W else maxi
    # candidate coordinates (rank order). Every window decision — masks,
    # conflicts, n(e) consultation, the fused singleton — only ever reads
    # candidate slots, so per-slot tensors below may be garbage elsewhere.
    w_rank = jnp.arange(W, dtype=i32)
    hit_all = cand_i[:, None] == ids_m[None, :]  # [W, M]
    is_sub_c = (cand_i >= T) & (cand_i < T + T * D)
    is_op_c = (cand_i >= T + T * D) & (cand_i < M0)
    sub_flat_c = jnp.clip(cand_i - T, 0, T * D - 1)
    t_sub_c = jnp.where(is_sub_c, sub_flat_c // D, 0)
    d_sub_c = jnp.where(is_sub_c, sub_flat_c % D, 0)
    op_flat_c = jnp.clip(cand_i - T - T * D, 0, T * K - 1)
    pos_term = pos[:T]
    pos_sub = pos[T : T + T * D].reshape(T, D)
    pos_op = pos[T + T * D : M0].reshape(T, K)
    # NOTE: per-event iteration numbers (hash salts) are assigned AFTER the
    # chain pass below — admitted follow-ups occupy merged ranks, shifting
    # the sequential iteration number of every later candidate.

    # ---- per-slot event categories (what each slot would fire as) ---------
    cat_log = s.phase == T_COMMIT_LOG
    cat_sched = sst == SUB_SCHED
    cat_reply = sst == SUB_ROUND_REPLY
    cat_vote = sst == SUB_VOTE
    cat_prog = cat_reply | cat_vote
    cat_prep = sst == SUB_PREP_CMD
    cat_preparing = sst == SUB_PREPARING
    cat_commit = (sst == SUB_COMMIT_CMD) | (sst == SUB_LOCAL_COMMIT)
    cat_abort_peer = sst == SUB_ABORT_PEER
    cat_ack = sst == SUB_ACK
    cat_abort_ack = sst == SUB_ABORT_ACK
    dm_cat = cat_prog | cat_ack | cat_abort_ack
    f_cat = cat_commit | cat_abort_peer
    cat_arr = st == OP_ENROUTE
    cat_exec = st == OP_EXEC

    d_of = s.op_ds.astype(i32)
    oh_d = jax.nn.one_hot(d_of, D, dtype=bool)  # [T,K,D]
    opn = st != OP_NONE
    tau_row = s.tau_true[None, :]  # [1,D]
    d_ids = jnp.arange(D, dtype=i32)
    kk = jnp.arange(K, dtype=i32)
    # middleware<->DS link per (t, d): heal-deferred send base + effective
    # (replica / degraded) RTT. Link state — mw_heal/tau_mw_eff/repl routing —
    # cannot change inside a window (fault events are pinned, txn starts and
    # finishes are non-drainable), so the per-slot precomputation matches the
    # sequential `_mw_link` call each handler would make at its own `now`.
    if F:
        link_td = lambda t0: _mw_send(s, s.on_repl, d_ids[None, :], t0)
    else:
        link_td = lambda t0: (t0, tau_row)

    # ---- op events: candidate-query lock decisions ------------------------
    # (pre-state views are exact: the window never batches two events
    # touching one key, and an EXEC->HOLD transition keeps holder status).
    # Lock checks are only ever consulted at candidate arrivals and at the
    # chain targets of candidate exec completions, so they run as [2W, T*K]
    # key queries instead of the [T*K, T*K] comparison matrix the pre-PR-5
    # plan built per iteration.
    with jax.named_scope("repro/locks"):
        fk = s.op_key.reshape(-1)
        fw = s.op_write.reshape(-1)
        fst = st.reshape(-1)
        holder = (fst == OP_EXEC) | (fst == OP_HOLD)
        waiting = fst == OP_WAIT

    # chain targets of exec completions (first QUEUED op, same DS/round); the
    # chained lock attempt happens at the *source* completion time
    row_q = st == OP_QUEUED
    same_round = s.op_round == s.cur_round[:, None]
    eq_ds = s.op_ds[:, :, None] == s.op_ds[:, None, :]
    chain_mask = (
        cat_exec[:, :, None] & row_q[:, None, :] & eq_ds & same_round[:, None, :]
    )
    has_next = jnp.any(chain_mask, axis=2)
    nxt = jnp.argmax(chain_mask, axis=2).astype(i32)  # [T,K]
    do_chain_cat = cat_exec & has_next
    rd_cat = cat_exec & ~has_next  # round completes at (t, d_of)

    TK = T * K
    NT = CHAIN_DEPTH + 1  # targets the chain walk may touch per candidate
    ids_tk = jnp.arange(TK, dtype=i32)
    t_op_c = op_flat_c // K
    k_op_c = op_flat_c % K
    d_op_c = d_of.reshape(-1)[op_flat_c]
    # queue walk: the first NT queued same-DS same-round statements of each
    # op candidate, in the exact argmax order the sequential chain handler
    # consumes them (each virtual completion un-queues its target)
    qrow = (
        (row_q & same_round)[t_op_c]
        & (d_of[t_op_c] == d_op_c[:, None])
        & is_op_c[:, None]
    )  # [W, K]
    tgt_ks, tgt_exs = [], []
    for _ in range(NT):
        tgt_exs.append(jnp.any(qrow, axis=1))
        tk_j = jnp.argmax(qrow, axis=1).astype(i32)
        tgt_ks.append(tk_j)
        qrow = qrow & (kk[None, :] != tk_j[:, None])
    tgt_k = jnp.stack(tgt_ks, axis=1)  # [W, NT]
    tgt_ex = jnp.stack(tgt_exs, axis=1)
    with jax.named_scope("repro/locks"):
        q_self = jnp.where(is_op_c, op_flat_c, TK)  # sentinel -> padded row
        q_tgts = jnp.where(
            is_op_c[:, None] & tgt_ex, t_op_c[:, None] * K + tgt_k, TK
        )  # [W, NT]
        fk_pad = jnp.concatenate([fk, jnp.full((1,), -3, fk.dtype)])
        fw_pad = jnp.concatenate([fw, jnp.zeros((1,), bool)])
        qs = jnp.concatenate([q_self, q_tgts.T.reshape(-1)])  # [(1+NT)W]
        keys_q = fk_pad[qs]
        m_q = keys_q[:, None] == fk[None, :]  # [(1+NT)W, T*K]
        x_held_q = jnp.any(m_q & (holder & fw)[None, :], axis=1)
        s_held_q = jnp.any(m_q & (holder & ~fw)[None, :], axis=1)
        wait_q = jnp.any(m_q & waiting[None, :], axis=1)
        ok_q = jnp.where(fw_pad[qs], ~x_held_q & ~s_held_q, ~x_held_q) & ~wait_q
        ok_self_c = ok_q[:W]
        ok_tgt = ok_q[W:].reshape(NT, W).T  # [W, NT] per-target grants
        # broadcast the candidate-correct grants back to slot shape (False
        # elsewhere — nothing beyond the candidates ever reads them)
        hit_op = q_self[:, None] == ids_tk[None, :]  # [W, T*K]
        ok = jnp.any(hit_op & ok_self_c[:, None], axis=0).reshape(T, K)
        ok_chain = jnp.any(hit_op & ok_tgt[:, 0][:, None], axis=0).reshape(T, K)

    exec_t = evt_op + _exec_us(cfg, s, d_of)  # [T,K] per-event time basis
    to_t = _lock_wait_deadline(s.dyn, evt_op)
    arr_state = jnp.where(ok, OP_EXEC, OP_WAIT)
    arr_time = jnp.where(ok, exec_t, to_t)
    chain_state = jnp.where(ok_chain, OP_EXEC, OP_WAIT)  # at source slots
    chain_time = jnp.where(ok_chain, exec_t, to_t)  # source time + same-DS exec

    # ---- second pass: chain entities across the scheduling fence (the
    # follow-up queue walk, order guard and prepare-flush entities — see
    # chain.chain_entities) --------------------------------------------------
    G = CHAIN_DEPTH
    c = chain_entities(
        s.dyn, sst, exec_t, evt_op, cand_t, cand_i, t_w1,
        is_op_c, is_sub_c, op_flat_c, sub_flat_c, t_op_c, k_op_c,
        cat_arr, do_chain_cat, ok_self_c, ok_tgt, tgt_k, tgt_ex,
        T, D, K,
    )
    # locals consulted by the dup-touch rules below
    arr_c, chn_c, seed_ca, ca_m = c.arr_c, c.chn_c, c.seed_ca, c.ca_m
    att_has, fu_valid = c.att_has, c.fu_valid

    # ---- merged entity ranks: candidates + follow-ups in one (time, flat
    # index, is-follow-up) order (chain.merged_ranks) ------------------------
    r = merged_ranks(cand_t, cand_i, c, BIG, maxi)
    mrank_pre, mrank_fu = r.mrank_pre, r.mrank_fu
    # per-slot iteration numbers, shifted by the follow-ups sorted before
    # each candidate (exact for every admitted candidate; rank 0 never
    # shifts — a valid follow-up's ancestor candidate precedes it)
    shift_c = mrank_pre - w_rank
    shift_flat = jnp.sum(jnp.where(hit_all, shift_c[:, None], 0), axis=0)
    iters_term = s.iters + 1 + pos_term + shift_flat[:T]
    iters_sub = s.iters + 1 + pos_sub + shift_flat[T : T + T * D].reshape(T, D)
    iters_op = s.iters + 1 + pos_op + shift_flat[T + T * D : M0].reshape(T, K)
    iters_fu = s.iters + 1 + mrank_fu
    iters_pfu = s.iters + 1 + r.mrank_pfu

    # round completions, per (t, d) — at most one in-flight op per (t, d)
    rd3 = oh_d & rd_cat[:, :, None]  # [T,K,D]
    time_rd = jnp.max(jnp.where(rd3, evt_op[:, :, None], 0), axis=1)
    iters_rd = jnp.max(jnp.where(rd3, iters_op[:, :, None], 0), axis=1)
    salt_td = iters_rd * _SALT_MUL + jnp.int32(37)
    rbase, rtau = link_td(time_rd)
    reply_t = rbase + _delay_salted(s.jitter_milli, rtau, salt_td)
    rmax_td = jnp.max(
        jnp.where(opn[:, :, None] & oh_d, s.op_round[:, :, None].astype(i32), -1),
        axis=1,
    )
    is_final_td = s.cur_round[:, None].astype(i32) >= rmax_td
    n_inv = jnp.sum(inv.astype(i32), axis=1)
    centr_t = n_inv == 1
    aborting_td = sst == SUB_ABORT_PEER
    prep_round_t = time_rd + s.dyn.lan_rtt_us + s.dyn.log_flush_us
    local_round_t = time_rd + s.dyn.log_flush_us
    # TIGA fast-path eligibility is per-txn and window-stable: op_round /
    # inv / sub_fast can only change under pinned events (txn start, round
    # advance) or same-txn dispatches, which the rank order keeps ahead of
    # any same-txn round completion (all round-0 dispatches share one
    # timestamp under the STAGGER_NONE gate TIGA requires).
    single_t = jnp.max(jnp.where(opn, s.op_round.astype(i32), 0), axis=1) == 0
    fast_t = _tiga_fast(s.dyn, single_t, inv, s.sub_fast)
    new_sub_state, new_sub_time = _round_done_transition(
        s.dyn, is_final_td, centr_t[:, None], reply_t, prep_round_t, local_round_t,
        fast_t[:, None],
    )

    # ---- sub dispatch (DM -> DS statements) -------------------------------
    arr_salt = iters_sub * _SALT_MUL + jnp.int32(41)
    abase, atau = link_td(evt_sub)
    arrival_td = abase + _delay_salted(s.jitter_milli, atau, arr_salt)
    # TIGA execute-at-arrival: the first statement fires at the synchronized
    # deadline when the (skew-shifted) arrival lands inside the slack window;
    # `sub_arrive` keeps the true arrival for the LEL accounting.
    eff_arrival_td, fast_disp_td = _tiga_arrival(
        s.dyn, s.clock_skew_us, evt_sub, arrival_td
    )
    sched_at_op = _at_ds(cat_sched, d_of)  # [T,K]
    c_ops = sched_at_op & (st == OP_PENDING) & same_round
    cand3 = c_ops[:, :, None] & oh_d
    has_c = jnp.any(cand3, axis=1)  # [T,D]
    first_c = jnp.argmax(cand3, axis=1).astype(i32)

    # ---- DS-side prepare command / WAL-flushed vote -----------------------
    prep_time = evt_sub + s.dyn.log_flush_us
    vote_salt = iters_sub * _SALT_MUL + jnp.int32(43)
    vbase, vtau = link_td(evt_sub)
    vote_t = vbase + _delay_salted(s.jitter_milli, vtau, vote_salt)

    # ---- chain-entity effect values (what each admitted follow-up writes,
    # with the salt/timestamp it would have had sequentially) ----------------
    eff = chain_effects(
        s, F, c, t_op_c, d_op_c, t_sub_c, d_sub_c, iters_fu, iters_pfu,
        is_final_td, aborting_td, centr_t, fast_t,
    )

    # ---- DM-side fan-ins: slot-accurate read/write sets -------------------
    # A fan-in at (t, j) writes only its own slot (+ rd_done[t, j] and the
    # DS-j EWMA) unless it *triggers* a row action. Its row read is exact iff
    # every earlier in-window event of terminal t is itself a non-triggering
    # fan-in — whose self-update the cumulative [T, j, d] view applies, via
    # the same first-touch-rank machinery the lock keys use: slot (t, d)'s
    # update is visible to fan-in (t, j) iff rank(t,d) <= rank(t,j).
    dm_self = jnp.where(
        cat_reply,
        SUB_ROUND_AT_DM,
        jnp.where(cat_vote, SUB_VOTED, jnp.where(cat_ack, SUB_DONE, SUB_ABORTED)),
    )
    le3 = dm_cat[:, None, :] & (pos_sub[:, None, :] <= pos_sub[:, :, None])
    sta3 = jnp.where(le3, dm_self[:, None, :], sst[:, None, :].astype(i32))
    rd_done3 = s.rd_done[:, None, :] | (le3 & cat_prog[:, None, :])
    inv3 = inv[:, None, :]
    waiting_c3 = inv3 & (sta3 == SUB_CHILLER_WAIT)
    active_c3 = inv3 & ~waiting_c3
    ready_chiller_j = (
        cat_prog
        & jnp.all(~active_c3 | (sta3 == SUB_VOTED), axis=2)
        & jnp.any(waiting_c3, axis=2)
        & s.dyn.chiller_two_stage
    )
    inv_rd = jnp.any(oh_d & (opn & same_round)[:, :, None], axis=1)
    all_rd_j = jnp.all(~inv_rd[:, None, :] | rd_done3, axis=2)
    rmax_t = jnp.max(jnp.where(opn, s.op_round.astype(i32), -1), axis=1)
    final_t = s.cur_round.astype(i32) >= rmax_t
    aborting_t = s.phase == T_ABORT_WAIT
    act_j = cat_prog & all_rd_j & ~aborting_t[:, None]
    advance_j = act_j & ~final_t[:, None]  # round advance: non-drainable
    all_at_dm_j = jnp.all(~inv3 | (sta3 == SUB_ROUND_AT_DM), axis=2)
    all_voted_j = jnp.all(~inv3 | (sta3 == SUB_VOTED), axis=2)
    dec_c_j, dec_p_j, dec_l_j = sched.commit_decision(
        s.dyn.prepare,
        all_at_dm_j,
        all_voted_j,
        centr_t[:, None],
        PREPARE_NONE,
        PREPARE_COORD,
        PREPARE_DECENTRAL,
    )
    gate_j = act_j & final_t[:, None]
    send_c_j = gate_j & dec_c_j
    send_p_j = gate_j & dec_p_j & ~dec_c_j
    log_t_j = gate_j & dec_l_j & ~dec_c_j & ~dec_p_j
    done_ack_j = cat_ack & jnp.all(~inv3 | (sta3 == SUB_DONE), axis=2)
    done_abk_j = cat_abort_ack & jnp.all(~inv3 | (sta3 == SUB_ABORTED), axis=2)
    if F:
        b3, r3 = _mw_send(
            s, s.on_repl[:, None, :], d_ids[None, None, :], evt_sub[:, :, None]
        )
    else:
        b3, r3 = evt_sub[:, :, None], tau_row[None]
    salt_dmc3 = iters_sub[:, :, None] * _SALT_MUL + jnp.int32(11) + d_ids[None, None, :]
    dt_commit3 = b3 + _delay_salted(s.jitter_milli, r3, salt_dmc3)
    salt_dmp3 = iters_sub[:, :, None] * _SALT_MUL + jnp.int32(13) + d_ids[None, None, :]
    dt_prepare3 = b3 + _delay_salted(s.jitter_milli, r3, salt_dmp3)
    log_term_j = evt_sub + s.dyn.log_flush_us

    # ---- terminal commit-log flush (broadcast) ----------------------------
    salt_e = iters_term[:, None] * _SALT_MUL + jnp.int32(31) + d_ids[None, :]
    lbase, ltau = link_td(evt_term[:, None])
    dt_log = lbase + _delay_salted(s.jitter_milli, ltau, salt_e)

    # ---- DS-side commit apply / peer-abort release ------------------------
    f_at_op = _at_ds(f_cat, d_of)  # [T,K]
    cancel_cat = opn & f_at_op  # ops cancelled (this IS the release)
    ack_salt = iters_sub * _SALT_MUL + jnp.where(cat_commit, 47, 53)
    kbase, ktau = link_td(evt_sub)
    ack_t = kbase + _delay_salted(s.jitter_milli, ktau, ack_salt)
    # FIFO grant order matters only if someone queues on a released key —
    # such a release is not drainable (the grants would need exact ordering).
    # Releases live at sub candidates, so the waiter probe runs on compact
    # [W, K] footprint rows gathered per candidate.
    t_rel = jnp.where(is_sub_c, t_sub_c, 0)
    rel_c = is_sub_c & f_cat[t_rel, d_sub_c]
    key_rel = s.op_key[t_rel]  # [W,K]
    st_rel = s.op_state[t_rel].astype(i32)
    ds_rel_row = s.op_ds[t_rel].astype(i32)
    cancel_rel = (
        rel_c[:, None] & (st_rel != OP_NONE) & (ds_rel_row == d_sub_c[:, None])
    )
    held_rel = cancel_rel & ((st_rel == OP_EXEC) | (st_rel == OP_HOLD))
    m_rel = (
        jnp.where(held_rel, key_rel, -3)[:, :, None] == fk[None, None, :]
    )  # [W,K,T*K]
    waiter_rel = jnp.any(
        jnp.any(m_rel & waiting[None, None, :], axis=2), axis=1
    )  # [W]
    sub_ids = jnp.arange(T * D, dtype=i32)
    hit_sub_rel = (
        jnp.where(rel_c, sub_flat_c, T * D)[:, None] == sub_ids[None, :]
    )  # [W, T*D]
    rel_waiter_td = jnp.any(hit_sub_rel & waiter_rel[:, None], axis=0).reshape(T, D)

    # ---- earliest-scheduled-time n(e) per event slot (INF_US = schedules
    # nothing) and the non-drainable pins ------------------------------------
    n_fan = jnp.where(
        send_c_j,
        jnp.min(jnp.where(inv3, dt_commit3, INF_US), axis=2),
        jnp.where(
            send_p_j,
            jnp.min(jnp.where(inv3, dt_prepare3, INF_US), axis=2),
            jnp.where(log_t_j, log_term_j, INF_US),
        ),
    )
    pinned_term = ~cat_log  # txn starts (and unexpected terminal states)
    n_term = jnp.where(
        cat_log, jnp.min(jnp.where(inv, dt_log, INF_US), axis=1), 0
    )
    sub_drain_cat = cat_sched | cat_prep | cat_preparing | f_cat | dm_cat
    pinned_sub = (
        ~sub_drain_cat
        | (f_cat & rel_waiter_td)
        | (dm_cat & (ready_chiller_j | advance_j | done_ack_j | done_abk_j))
    )
    n_sub = jnp.full((T, D), INF_US, i32)
    n_sub = jnp.where(cat_sched, jnp.where(has_c, eff_arrival_td, INF_US), n_sub)
    n_sub = jnp.where(cat_prep, prep_time, n_sub)
    n_sub = jnp.where(cat_preparing, vote_t, n_sub)
    n_sub = jnp.where(f_cat, ack_t, n_sub)
    n_sub = jnp.where(dm_cat, n_fan, n_sub)
    n_sub = jnp.where(pinned_sub, 0, n_sub)
    rd_sched_t = jnp.where(
        _at_ds(aborting_td, d_of),
        INF_US,
        _at_ds(new_sub_time, d_of),
    )
    pinned_op = ~(cat_arr | cat_exec)  # lock-wait timeouts / unexpected
    n_op = jnp.where(
        cat_arr,
        arr_time,
        jnp.where(do_chain_cat, chain_time, jnp.where(rd_cat, rd_sched_t, INF_US)),
    )
    n_op = jnp.where(pinned_op, 0, n_op)

    # ---- order-aware pairwise conflicts: mark the LATER event of each pair
    # so the prefix stops exactly at the first conflicting event, keeping the
    # conflict families separate for stop-reason attribution ----------------
    # (a) duplicate lock keys among arrivals, chain targets, released
    #     footprints. Every touch belongs to a candidate event (a chain touch
    #     at its target key, stamped with the source candidate's rank; a
    #     footprint touch per cancelled op of a release candidate), and a
    #     non-candidate touch can never out-rank a candidate — so the
    #     first-touch comparison runs on the compact candidate touch list
    #     instead of the [T*K, T*K] eq_key matrix. A single event touching
    #     one key twice (a release footprint with a duplicated record) shares
    #     one rank and stays drainable — one event batches with itself
    #     trivially.
    pos_f_at_op = _at_ds(jnp.where(f_cat, pos_sub, BIG), d_of)
    # reverse chain map: tgt3[t,k,j] <=> source op k chains to target op j
    # (gather-based — a scatter here would lower to a per-lane loop under vmap)
    tgt3 = do_chain_cat[:, :, None] & (kk[None, None, :] == nxt[:, :, None])
    # touch list: W arrival self-keys + W*NT chain-walk target touches (each
    # stamped with the merged rank of the entity attempting it) + W*K release
    # footprints. CA seeds attempt target j via chain entity j+1; CX seeds
    # attempt target 0 at the candidate itself and target j>=1 via entity j.
    # A touch is listed whenever its entity exists and the target is real —
    # denied attempts still create waiters later queries must see, so the
    # toucher gate excludes the attempt's own grant bit.
    tv = jnp.where(
        ca_m,
        jnp.concatenate([fu_valid & att_has, jnp.zeros((W, 1), bool)], axis=1),
        jnp.concatenate([chn_c[:, None], fu_valid & att_has], axis=1),
    )  # [W, NT] target-column touch validity
    tr = jnp.where(
        ca_m,
        jnp.concatenate([mrank_fu, jnp.zeros((W, 1), i32)], axis=1),
        jnp.concatenate([mrank_pre[:, None], mrank_fu], axis=1),
    )  # [W, NT] merged rank of the toucher
    with jax.named_scope("repro/locks"):
        tkeys = jnp.concatenate(
            [fk_pad[q_self], fk_pad[q_tgts].T.reshape(-1), key_rel.reshape(-1)]
        )  # [(1+NT)W + W*K]
        tvalid = jnp.concatenate([arr_c, tv.T.reshape(-1), cancel_rel.reshape(-1)])
        tw = jnp.concatenate(
            [
                mrank_pre,
                tr.T.reshape(-1),
                jnp.broadcast_to(mrank_pre[:, None], (W, K)).reshape(-1),
            ]
        )
        eq_t = (tkeys[:, None] == tkeys[None, :]) & tvalid[:, None] & tvalid[None, :]
        dup_t = jnp.any(eq_t & (tw[None, :] < tw[:, None]), axis=1)
        dup_arr_c = dup_t[:W] & arr_c
        tg_dup = dup_t[W : W + NT * W].reshape(NT, W).T & tv  # [W, NT]
        dup_chn_c = tg_dup[:, 0] & ~seed_ca  # pass-1 chain attempt (CX candidate)
        fu_dup = jnp.where(ca_m, tg_dup[:, :G], tg_dup[:, 1:])  # [W, G] per entity
        dup_rel_c = jnp.any(dup_t[W + NT * W :].reshape(W, K) & cancel_rel, axis=1)
        dup_arr = jnp.any(hit_op & dup_arr_c[:, None], axis=0).reshape(T, K)
        dup_chain = jnp.any(hit_op & dup_chn_c[:, None], axis=0).reshape(T, K)
        conf_key_sub = jnp.any(hit_sub_rel & dup_rel_c[:, None], axis=0).reshape(T, D)
        conf_key_op = dup_arr | dup_chain

    # (b) slot-accurate DM row rules. Row-writers (commit-log flushes and
    #     *triggering* fan-ins) stay forward-exclusive; a fan-in additionally
    #     conflicts when any non-fan-in event of its terminal precedes it
    #     (its cumulative row view would miss that event's writes).
    trig_j = dm_cat & (
        ready_chiller_j
        | advance_j
        | send_c_j
        | send_p_j
        | log_t_j
        | done_ack_j
        | done_abk_j
    )
    pos_excl = jnp.minimum(
        jnp.where(cat_log, pos_term, BIG),
        jnp.min(jnp.where(trig_j, pos_sub, BIG), axis=1),
    )
    pos_nonfan = jnp.minimum(
        pos_term,
        jnp.minimum(
            jnp.min(jnp.where(~dm_cat, pos_sub, BIG), axis=1),
            jnp.min(pos_op, axis=1),
        ),
    )
    conf_row_term = pos_excl < pos_term
    conf_row_sub = (pos_excl[:, None] < pos_sub) | (
        dm_cat & (pos_nonfan[:, None] < pos_sub)
    )
    conf_row_op = pos_excl[:, None] < pos_op

    # (c) at most K_EWMA fan-ins per data source per window (the monitor
    #     composes one exact EWMA application per fan-in, unrolled K_EWMA
    #     deep) — per-(DS-column) first-touch counts, any terminal
    col_lt = dm_cat[None, :, :] & (pos_sub[None, :, :] < pos_sub[:, None, :])
    col_before = jnp.sum(col_lt, axis=1, dtype=i32)  # [T,D]
    conf_col_sub = dm_cat & (col_before >= K_EWMA)

    # (d) a release and an earlier op event at the same (terminal, DS)
    pos_op_td = jnp.min(jnp.where(oh_d, pos_op[:, :, None], BIG), axis=1)
    conf_rel_sub = f_cat & (pos_op_td < pos_sub)
    conf_rel_op = pos_f_at_op < pos_op

    # ---- maximal prefix over the sorted event order -----------------------
    # The window ends at the first (by rank) "stopper": a conflicted event,
    # an event at/after the horizon, a pinned (non-drainable) event, or the
    # first event whose time some earlier-or-equal-rank event schedules at or
    # before (running min of n(e) in rank order must stay strictly above the
    # event times — pinned events carry n=0, stopping the window at
    # themselves).
    zt = jnp.zeros((T,), bool)
    conf_key = jnp.concatenate([zt, conf_key_sub.reshape(-1), conf_key_op.reshape(-1)])
    conf_row = jnp.concatenate(
        [conf_row_term, conf_row_sub.reshape(-1), conf_row_op.reshape(-1)]
    )
    conf_col = jnp.concatenate(
        [zt, conf_col_sub.reshape(-1), jnp.zeros((T * K,), bool)]
    )
    conf_rel = jnp.concatenate(
        [zt, conf_rel_sub.reshape(-1), conf_rel_op.reshape(-1)]
    )
    pinned_flat = jnp.concatenate(
        [pinned_term, pinned_sub.reshape(-1), pinned_op.reshape(-1)]
    )
    # round advances are pinned sub slots, counted apart from the other pins
    round_flat = jnp.concatenate(
        [zt, advance_j.reshape(-1), jnp.zeros((T * K,), bool)]
    )
    n_flat = jnp.concatenate([n_term, n_sub.reshape(-1), n_op.reshape(-1)])
    if F:
        # fault-schedule tails: pinned, schedule nothing, conflict with
        # nothing — a due one simply stops the window at itself. Heartbeat
        # tails are conflict-free and DRAIN: a probe writes only its own
        # counter/timer and reads reachability state no window event can
        # change, so its only window interaction is the re-arm time entering
        # the running-min "scheduled" rule.
        zfd = jnp.zeros((F + D,), bool)
        conf_key = jnp.concatenate([conf_key, zfd])
        conf_row = jnp.concatenate([conf_row, zfd])
        conf_col = jnp.concatenate([conf_col, zfd])
        conf_rel = jnp.concatenate([conf_rel, zfd])
        pinned_flat = jnp.concatenate(
            [pinned_flat, jnp.ones((F,), bool), jnp.zeros((D,), bool)]
        )
        round_flat = jnp.concatenate([round_flat, zfd])
        # a firing probe re-arms at its slot time + interval; a non-firing
        # (or disarmed) one schedules nothing
        hb_fire = s.ds_down | (s.mw_heal > s.hb_time)
        n_hb = jnp.where(
            hb_fire & (s.hb_time < INF_US),
            s.hb_time + s.dyn.hb_interval_us,
            INF_US,
        )
        n_flat = jnp.concatenate([n_flat, jnp.zeros((F,), i32), n_hb])
    else:
        hb_fire = jnp.zeros((D,), bool)
    conflict = conf_key | conf_row | conf_col | conf_rel
    horizon_i = jnp.int32(cfg.horizon_us)
    code = jnp.where(
        flat >= horizon_i,
        STOP_HORIZON,
        jnp.where(
            pinned_flat,
            jnp.where(round_flat, STOP_ROUND, STOP_NONDRAINABLE),
            jnp.where(
                conf_key,
                STOP_LOCK_KEY,
                jnp.where(
                    conf_row,
                    STOP_DM_ROW,
                    jnp.where(
                        conf_col,
                        STOP_DM_COL,
                        jnp.where(conf_rel, STOP_REL_OP, STOP_SCHEDULED),
                    ),
                ),
            ),
        ),
    ).astype(i32)
    if F:
        # distinguish fault-schedule stoppers from ordinary non-drainable
        # events (horizon stays dominant). Heartbeat slots are unpinned and
        # keep the generic codes — a probe that ends a window does so via the
        # ordinary running-min/`scheduled` machinery, and the per-stopper
        # telemetry proves the drain (mean-window ratchet guard).
        idx_flat = jnp.arange(M, dtype=i32)
        fault_flat = (idx_flat >= M0) & (idx_flat < M0 + F)
        code = jnp.where((flat < horizon_i) & fault_flat, STOP_FAULT, code)
    # ---- shared entity-space prefix scan (both routes): admission over the
    # merged [E, E] strict order (chain.entity_admission) --------------------
    adm = entity_admission(
        s.dyn, c, r, eff, conflict[cand_i], code[cand_i], n_flat[cand_i],
        fu_dup, hit_all, horizon_i, maxi, T, D, K, M0, F,
    )

    return _PlanVals(
        cand_i=cand_i,
        cand_is_sub=is_sub_c,
        cand_t_sub=t_sub_c,
        cand_d_sub=d_sub_c,
        pos_term=pos_term,
        pos_sub=pos_sub,
        pos_op=pos_op,
        iters_term=iters_term,
        iters_sub=iters_sub,
        iters_op=iters_op,
        cat_log=cat_log,
        cat_sched=cat_sched,
        cat_prep=cat_prep,
        cat_preparing=cat_preparing,
        cat_commit=cat_commit,
        cat_ack=cat_ack,
        cat_prog=cat_prog,
        dm_cat=dm_cat,
        f_cat=f_cat,
        cat_arr=cat_arr,
        cat_exec=cat_exec,
        ok=ok,
        arr_state=arr_state,
        arr_time=arr_time,
        has_next=has_next,
        tgt3=tgt3,
        ok_chain=ok_chain,
        chain_state=chain_state,
        chain_time=chain_time,
        time_rd=time_rd,
        new_sub_state=new_sub_state,
        new_sub_time=new_sub_time,
        aborting_td=aborting_td,
        arrival_td=arrival_td,
        eff_arrival_td=eff_arrival_td,
        fast_disp_td=fast_disp_td,
        has_c=has_c,
        first_c=first_c,
        prep_time=prep_time,
        vote_t=vote_t,
        dm_self=dm_self,
        ready_chiller_j=ready_chiller_j,
        advance_j=advance_j,
        send_c_j=send_c_j,
        send_p_j=send_p_j,
        log_t_j=log_t_j,
        done_ack_j=done_ack_j,
        done_abk_j=done_abk_j,
        dt_commit3=dt_commit3,
        dt_prepare3=dt_prepare3,
        log_term_j=log_term_j,
        dt_log=dt_log,
        ack_t=ack_t,
        rel_waiter_td=rel_waiter_td,
        fu_win=adm.fu_win,
        fu_term=t_op_c,
        fu_d=d_op_c,
        fu_u=c.u,
        fu_comp_k=c.comp_k,
        fu_att_has=att_has,
        fu_att_k=c.att_k,
        fu_att_ok=c.att_ok_t,
        fu_att_state=eff.att_state_fu,
        fu_att_time=eff.att_time_fu,
        fu_rd=eff.rd_fu,
        fu_rd_wr=eff.rd_wr_fu,
        fu_rd_state=eff.rd_state_fu,
        fu_rd_time=eff.rd_time_fu,
        pfu_win=adm.pfu_win,
        pfu_vote_t=eff.vote2,
        n_chained=adm.n_chained,
        pinned_term=pinned_term,
        pinned_sub=pinned_sub,
        pinned_op=pinned_op,
        win_term=adm.win_term,
        win_sub=adm.win_sub,
        win_op=adm.win_op,
        win_hb=adm.win_hb,
        hb_fire=hb_fire,
        n_win=adm.n_win,
        use=adm.use,
        t_last=adm.t_last,
        stop_code=adm.stop_code,
    )
