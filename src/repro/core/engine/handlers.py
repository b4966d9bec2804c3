"""Event handlers: the sequential (per-event) semantics of the engine.

Hotspot/metric bookkeeping, DM-side protocol progress, the abort path and
the twelve fused event handlers the dispatch switch routes to, plus the
state->handler-id tables (the lock-table primitives live in
`engine.locks`). These define the seed semantics every other step mode
(`omni`, `window`) must reproduce bitwise.
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
    STAGGER_NET_LEL,
    STAGGER_NONE,
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
    DynProto,
    SimConfig,
    SimState,
    _delay,
    _delay_salted,
    _ds_send,
    _exec_us,
    _hist_bin,
    _measuring,
    _mw_link,
    _round_done_transition,
    _salt,
    _tiga_arrival,
    _tiga_fast,
    _u01,
)

# ---------------------------------------------------------------------------
# lock table primitives live in engine.locks (re-exported here for the
# dispatch tables and the engine package facade)
# ---------------------------------------------------------------------------

from repro.core.engine.locks import (  # noqa: E402
    _attempt_lock,
    _grant_decision,
    _release_and_grant,
)


# ---------------------------------------------------------------------------
# hotspot + metric helpers
# ---------------------------------------------------------------------------


@jax.named_scope("repro/hotspot")
def _hs_dispatch(cfg, s: SimState, keys, valid) -> SimState:
    """Claim hot-table slots for the txn's records and bump a_cnt."""
    hs = s.hs
    slot, evict = hs_mod.find_or_claim_slots(hs.slot_key, keys, valid)
    zero_if = lambda f: f.at[jnp.where(evict, slot, cfg.hot_capacity)].set(0)
    hs = hs._replace(
        w_lat=zero_if(hs.w_lat),
        t_cnt=zero_if(hs.t_cnt),
        c_cnt=zero_if(hs.c_cnt),
        a_cnt=zero_if(hs.a_cnt),
    )
    hs = hs._replace(
        slot_key=hs_mod.claim_keys(hs.slot_key, slot, keys, valid),
        a_cnt=hs.a_cnt.at[slot].add(valid.astype(jnp.int32)),
        clock=hs.clock.at[slot].set(1),
    )
    return s._replace(hs=hs)


@jax.named_scope("repro/hotspot")
def _hs_complete_ds(cfg, s: SimState, t, d, committed) -> SimState:
    """Hotspot Eq.(4) update + a_cnt/t_cnt/c_cnt bookkeeping for subtxn (t,d)."""
    mask = (s.op_state[t] != OP_NONE) & (s.op_ds[t] == d.astype(s.op_ds.dtype))
    keys = s.op_key[t]
    hs = s.hs
    slot, found = hs_mod.lookup_slots(hs.slot_key, keys, mask)
    lel = s.sub_lel[t, d].astype(jnp.float32)
    new_w = hs_mod.eq4_masked_w(hs.w_lat, slot, found, lel, cfg.alpha_milli)
    upd = found.astype(jnp.int32)
    hs = hs._replace(
        w_lat=hs.w_lat.at[slot].set(jnp.where(found, new_w, hs.w_lat[slot])),
        a_cnt=jnp.maximum(hs.a_cnt.at[slot].add(-upd), 0),
        t_cnt=hs.t_cnt.at[slot].add(upd),
        c_cnt=hs.c_cnt.at[slot].add(upd * committed.astype(jnp.int32)),
    )
    return s._replace(hs=hs)


def _lcs_metric(cfg, s: SimState, t, d, gate=None) -> SimState:
    fl = s.first_lock[t, d]
    have = (fl < INF_US) & _measuring(cfg, s)
    if gate is not None:
        have = have & gate
    span_ms = jnp.where(have, (s.now - fl + 500) // 1000, 0)
    return s._replace(
        lcs_sum=s.lcs_sum + span_ms,
        lcs_cnt=s.lcs_cnt + have.astype(jnp.int32),
    )


def _finish_txn(cfg: SimConfig, s: SimState, t, committed) -> SimState:
    """Terminal-side completion: metrics, reset, schedule next/retry."""
    N = cfg.bank_txns
    lat = s.now - s.arrive[t]
    dist = s.is_dist[t]
    meas = _measuring(cfg, s)
    b = _hist_bin(lat)
    slot = s.cur[t] % N

    # abort-cause tally (first cause wins; a final abort that burned retries
    # is recorded as "exhausted" — the distinct give-up code) + fault-window
    # goodput. Tallied before the reset below clears the pending cause.
    will_retry = ~committed & (s.retries[t] < s.dyn.max_retries)
    cause = jnp.where(
        ~will_retry & (s.retries[t] > 0), CAUSE_EXHAUSTED, s.abort_cause[t]
    )
    # goodput gate: "during fault" means some DS is unreachable — crashed or
    # partitioned from the middleware (fault-free configs: ds_down only)
    if s.fault_time.shape[0]:
        any_down = jnp.any(s.ds_down | (s.mw_heal > s.now))
    else:
        any_down = jnp.any(s.ds_down)
    s = s._replace(
        ab_cause=s.ab_cause.at[cause].add(jnp.where(meas & ~committed, 1, 0)),
        commits_fault=s.commits_fault + jnp.where(meas & committed & any_down, 1, 0),
    )

    s = s._replace(
        commits=s.commits + jnp.where(meas & committed, 1, 0),
        aborts=s.aborts + jnp.where(meas & ~committed, 1, 0),
        commits_dist=s.commits_dist + jnp.where(meas & committed & dist, 1, 0),
        aborts_dist=s.aborts_dist + jnp.where(meas & ~committed & dist, 1, 0),
        lat_sum=s.lat_sum + jnp.where(meas & committed, (lat + 500) // 1000, 0),
        lat_sum_dist=s.lat_sum_dist
        + jnp.where(meas & committed & dist, (lat + 500) // 1000, 0),
        hist_all=s.hist_all.at[b].add(jnp.where(meas & committed, 1, 0)),
        hist_cen=s.hist_cen.at[b].add(jnp.where(meas & committed & ~dist, 1, 0)),
        hist_dist=s.hist_dist.at[b].add(jnp.where(meas & committed & dist, 1, 0)),
        slot_commits=s.slot_commits.at[t, slot].add(
            jnp.where(meas & committed, 1, 0), mode="drop"
        ),
        slot_aborts=s.slot_aborts.at[t, slot].add(
            jnp.where(meas & ~committed, 1, 0), mode="drop"
        ),
        slot_lat=s.slot_lat.at[t, slot].add(
            jnp.where(meas & committed, (lat + 500) // 1000, 0), mode="drop"
        ),
    )
    # reset per-txn rows
    K, D = cfg.max_ops, cfg.num_ds
    s = s._replace(
        op_state=s.op_state.at[t].set(jnp.zeros((K,), jnp.int8)),
        op_time=s.op_time.at[t].set(jnp.full((K,), INF_US, jnp.int32)),
        inv=s.inv.at[t].set(jnp.zeros((D,), bool)),
        sub_state=s.sub_state.at[t].set(jnp.zeros((D,), jnp.int8)),
        sub_time=s.sub_time.at[t].set(jnp.full((D,), INF_US, jnp.int32)),
        sub_lel=s.sub_lel.at[t].set(jnp.zeros((D,), jnp.int32)),
        first_lock=s.first_lock.at[t].set(jnp.full((D,), INF_US, jnp.int32)),
        rd_done=s.rd_done.at[t].set(jnp.zeros((D,), bool)),
        cur_round=s.cur_round.at[t].set(0),
        abort_cause=s.abort_cause.at[t].set(CAUSE_NONE),
    )
    if s.fault_time.shape[0]:  # a failed-over txn releases its replica routing
        s = s._replace(on_repl=s.on_repl.at[t].set(jnp.zeros((D,), bool)))
    # next / retry
    retry = ~committed & (s.retries[t] < s.dyn.max_retries)
    base = s.dyn.retry_backoff_us
    # randomized exponential backoff: breaks deadlock lockstep between
    # terminals that would otherwise retry in phase and re-deadlock forever
    jit = (
        _hash_u32(s.txn_ctr[t] * 977 + t.astype(jnp.int32) * 131 + s.retries[t])
        % jnp.maximum(base, 1).astype(jnp.uint32)
    ).astype(jnp.int32)
    # floor 1 µs: a zero-backoff preset would respin a crash-fail-fasted
    # terminal at a constant `now` until max_events (livelock)
    backoff = jnp.maximum(base * (1 + jnp.minimum(s.retries[t], 7)) + jit, 1)
    s = s._replace(
        retries=s.retries.at[t].set(jnp.where(retry, s.retries[t] + 1, 0)),
        retry_same=s.retry_same.at[t].set(retry),
        blocked=s.blocked.at[t].set(0),
        cur=s.cur.at[t].add(jnp.where(retry, 0, 1)),
        phase=s.phase.at[t].set(T_IDLE),
        term_time=s.term_time.at[t].set(jnp.where(committed, s.now, s.now + backoff)),
    )
    return s


# ---------------------------------------------------------------------------
# DM-side protocol progress
# ---------------------------------------------------------------------------


def _round_inv(s: SimState, t) -> jax.Array:
    """[D] which data sources have ops in the current round."""
    row = s.op_state[t] != OP_NONE
    rd = s.op_round[t] == s.cur_round[t]
    D = s.inv.shape[1]
    oh = jax.nn.one_hot(s.op_ds[t].astype(jnp.int32), D, dtype=bool)
    return jnp.any(oh & (row & rd)[:, None], axis=0)


@jax.named_scope("repro/hotspot")
def _lel_forecast(cfg, s: SimState, t) -> jax.Array:
    """Eq.(5) per data source for txn t: [D] int32 µs (hot-table lookup)."""
    row = s.op_state[t] != OP_NONE
    slot, found = hs_mod.lookup_slots(s.hs.slot_key, s.op_key[t], row)
    w = s.hs.w_lat[slot] * found.astype(jnp.int32)
    D = s.inv.shape[1]
    oh = jax.nn.one_hot(s.op_ds[t].astype(jnp.int32), D, dtype=jnp.int32)
    return jnp.sum(w[:, None] * oh, axis=0).astype(jnp.int32)


def _stagger(cfg: SimConfig, s: SimState, t, inv_mask) -> jax.Array:
    """Dispatch offsets per DS (Eq.3 / Eq.8 / none / chiller), selected by the
    dynamic stagger knob: a zero LEL vector turns Eq.(8) into Eq.(3)."""
    lel = (
        _lel_forecast(cfg, s, t).astype(jnp.float32)
        * s.lel_scale_milli.astype(jnp.float32)
        / 1000.0
    ).astype(jnp.int32)
    lel = jnp.where(s.dyn.stagger == STAGGER_NET_LEL, lel, 0)
    off = sched.stagger_offsets(s.tau_est, inv_mask, lel)
    return jnp.where(s.dyn.stagger == STAGGER_NONE, jnp.zeros_like(off), off)


def _dispatch_subs(cfg, s: SimState, t, mask, times) -> SimState:
    s = s._replace(
        sub_state=s.sub_state.at[t].set(
            jnp.where(mask, SUB_SCHED, s.sub_state[t]).astype(jnp.int8)
        ),
        sub_time=s.sub_time.at[t].set(jnp.where(mask, times, s.sub_time[t])),
    )
    return s


def _dm_progress(cfg: SimConfig, s: SimState, t) -> SimState:
    """Called whenever the DM hears from a data source: handles chiller stage-2
    dispatch, interactive-round advancement, prepare broadcast (2PC) and the
    commit decision."""
    inv = s.inv[t]
    st = s.sub_state[t]
    n_inv = jnp.sum(inv.astype(jnp.int32))
    centralized = n_inv == 1

    # chiller stage-2: when every dispatched (stage-1) sub has voted
    waiting = inv & (st == SUB_CHILLER_WAIT)
    active = inv & ~waiting
    ready = (
        jnp.all(~active | (st == SUB_VOTED))
        & jnp.any(waiting)
        & s.dyn.chiller_two_stage
    )
    s = jax.lax.cond(
        ready,
        lambda s_: _dispatch_subs(
            cfg, s_, t, waiting, jnp.full_like(s_.sub_time[t], s_.now)
        ),
        lambda s_: s_,
        s,
    )
    st = s.sub_state[t]

    inv_rd = _round_inv(s, t)
    all_rd = jnp.all(~inv_rd | s.rd_done[t])
    max_round = jnp.max(
        jnp.where(s.op_state[t] != OP_NONE, s.op_round[t], -1)
    ).astype(jnp.int8)
    final = s.cur_round[t] >= max_round

    def advance(s_: SimState) -> SimState:
        nxt = (s_.cur_round[t] + 1).astype(jnp.int8)
        s_ = s_._replace(
            cur_round=s_.cur_round.at[t].set(nxt),
            rd_done=s_.rd_done.at[t].set(jnp.zeros_like(s_.rd_done[t])),
        )
        row = s_.op_state[t] != OP_NONE
        oh = jax.nn.one_hot(s_.op_ds[t].astype(jnp.int32), cfg.num_ds, dtype=bool)
        inv_next = jnp.any(oh & (row & (s_.op_round[t] == nxt))[:, None], axis=0)
        off = _stagger(cfg, s_, t, inv_next)
        return _dispatch_subs(cfg, s_, t, inv_next, s_.now + off)

    def decide(s_: SimState) -> SimState:
        st_ = s_.sub_state[t]
        all_at_dm = jnp.all(~inv | (st_ == SUB_ROUND_AT_DM))
        all_voted = jnp.all(~inv | (st_ == SUB_VOTED))
        # one-phase commit for centralized transactions (all protocols); the
        # no-prepare preset broadcasts commit as soon as every sub reported
        do_commit, do_prepare, do_log = sched.commit_decision(
            s_.dyn.prepare,
            all_at_dm,
            all_voted,
            centralized,
            PREPARE_NONE,
            PREPARE_COORD,
            PREPARE_DECENTRAL,
        )

        def send_commit(s2: SimState) -> SimState:
            ids = jnp.arange(cfg.num_ds, dtype=jnp.int32)
            salts = _salt(s2, 11) + ids
            base, tau = _mw_link(s2, s2.on_repl[t], ids, s2.now)
            dtimes = base + jax.vmap(lambda r, sa: _delay(s2, r, sa))(tau, salts)
            return s2._replace(
                sub_state=s2.sub_state.at[t].set(
                    jnp.where(inv, SUB_COMMIT_CMD, st_).astype(jnp.int8)
                ),
                sub_time=s2.sub_time.at[t].set(
                    jnp.where(inv, dtimes, s2.sub_time[t])
                ),
                phase=s2.phase.at[t].set(T_COMMIT_WAIT),
                term_time=s2.term_time.at[t].set(INF_US),
            )

        def send_prepare(s2: SimState) -> SimState:
            ids = jnp.arange(cfg.num_ds, dtype=jnp.int32)
            salts = _salt(s2, 13) + ids
            base, tau = _mw_link(s2, s2.on_repl[t], ids, s2.now)
            dtimes = base + jax.vmap(lambda r, sa: _delay(s2, r, sa))(tau, salts)
            return s2._replace(
                sub_state=s2.sub_state.at[t].set(
                    jnp.where(inv, SUB_PREP_CMD, st_).astype(jnp.int8)
                ),
                sub_time=s2.sub_time.at[t].set(
                    jnp.where(inv, dtimes, s2.sub_time[t])
                ),
            )

        def commit_log(s2: SimState) -> SimState:
            return s2._replace(
                phase=s2.phase.at[t].set(T_COMMIT_LOG),
                term_time=s2.term_time.at[t].set(
                    s2.now + s2.dyn.log_flush_us
                ),
            )

        return jax.lax.cond(
            do_commit,
            send_commit,
            lambda s2: jax.lax.cond(
                do_prepare,
                send_prepare,
                lambda s3: jax.lax.cond(do_log, commit_log, lambda s4: s4, s3),
                s2,
            ),
            s_,
        )

    aborting = s.phase[t] == T_ABORT_WAIT
    return jax.lax.cond(
        all_rd & ~aborting,
        lambda s_: jax.lax.cond(final, decide, advance, s_),
        lambda s_: s_,
        s,
    )


# ---------------------------------------------------------------------------
# abort path
# ---------------------------------------------------------------------------


def _initiate_abort(cfg: SimConfig, s: SimState, t, d) -> SimState:
    """Lock-wait timeout at (t, d): abort the whole distributed transaction.
    With early_abort the geo-agent notifies peers directly (DS<->DS);
    otherwise the notification is routed through the DM (1.5 WAN rounds)."""
    s = _release_and_grant(cfg, s, t, d)
    s = _hs_complete_ds(cfg, s, t, d, jnp.asarray(False))

    inv = s.inv[t]
    st = s.sub_state[t]
    D = cfg.num_ds
    ids = jnp.arange(D, dtype=jnp.int32)
    abort_family = (st == SUB_ABORT_PEER) | (st == SUB_ABORT_ACK) | (st == SUB_ABORTED)
    peers = inv & (ids != d) & ~abort_family

    salts = _salt(s, 17) + ids
    if s.fault_time.shape[0]:
        # abort notifications ride the effective links: degraded/partitioned
        # mesh links slow/hold the direct route, the via-DM route crosses the
        # timed-out sub's own middleware (or replica) link both ways
        on_d = s.on_repl[t, d]
        mesh_base, mesh_tau = _ds_send(s, d, ids, s.now)
        notify_direct = mesh_base + jax.vmap(lambda r, sa: _delay(s, r, sa))(
            mesh_tau, salts
        )
        up_base, up_tau = _mw_link(s, on_d, d, s.now)
        to_dm = up_base + _delay(s, up_tau, _salt(s, 19))
        dn_base, dn_tau = _mw_link(s, s.on_repl[t], ids, to_dm)
        notify_via_dm = dn_base + jax.vmap(lambda r, sa: _delay(s, r, sa))(
            dn_tau, salts
        )
        notify = jnp.where(s.dyn.early_abort, notify_direct, notify_via_dm)
        ack_base, ack_tau = _mw_link(s, on_d, d, s.now)
        own_ack = ack_base + _delay(s, ack_tau, _salt(s, 23))
    else:
        notify_direct = jax.vmap(lambda r, sa: _delay(s, r, sa))(s.tau_ds[d], salts)
        to_dm = _delay(s, s.tau_true[d], _salt(s, 19))
        notify_via_dm = to_dm + jax.vmap(lambda r, sa: _delay(s, r, sa))(
            s.tau_true, salts
        )
        notify = s.now + jnp.where(s.dyn.early_abort, notify_direct, notify_via_dm)
        own_ack = s.now + _delay(s, s.tau_true[d], _salt(s, 23))
    new_st = jnp.where(peers, SUB_ABORT_PEER, st)
    new_tm = jnp.where(peers, notify, s.sub_time[t])
    new_st = new_st.at[d].set(SUB_ABORT_ACK)
    new_tm = new_tm.at[d].set(own_ack)
    return s._replace(
        sub_state=s.sub_state.at[t].set(new_st.astype(jnp.int8)),
        sub_time=s.sub_time.at[t].set(new_tm),
        phase=s.phase.at[t].set(T_ABORT_WAIT),
        term_time=s.term_time.at[t].set(INF_US),
        # first cause wins (a second timeout during an in-flight abort must
        # not relabel it)
        abort_cause=s.abort_cause.at[t].set(
            jnp.where(s.abort_cause[t] == CAUSE_NONE, CAUSE_TIMEOUT, s.abort_cause[t])
        ),
    )


# ---------------------------------------------------------------------------
# event handlers  (each: (cfg, bank, s, t, idx) -> s)
# ---------------------------------------------------------------------------


def _h_start_txn(cfg: SimConfig, bank: Bank, s: SimState, t, idx) -> SimState:
    """T_IDLE fires: load the txn from the bank, run O3 admission, compute the
    stagger (Eq.3/Eq.8) and dispatch round-0 subtransactions."""
    N = cfg.bank_txns
    slot = s.cur[t] % N
    key = bank.key[t, slot]
    write = bank.write[t, slot]
    ds = bank.ds[t, slot]
    rnd = bank.round_id[t, slot]
    valid = bank.valid[t, slot]
    D = cfg.num_ds

    oh = jax.nn.one_hot(ds.astype(jnp.int32), D, dtype=bool)
    inv = jnp.any(oh & valid[:, None], axis=0)

    s = s._replace(
        op_key=s.op_key.at[t].set(jnp.where(valid, key, -1)),
        op_write=s.op_write.at[t].set(write),
        op_ds=s.op_ds.at[t].set(ds),
        op_round=s.op_round.at[t].set(rnd),
        op_state=s.op_state.at[t].set(
            jnp.where(valid, OP_PENDING, OP_NONE).astype(jnp.int8)
        ),
        op_time=s.op_time.at[t].set(jnp.full((cfg.max_ops,), INF_US, jnp.int32)),
        inv=s.inv.at[t].set(inv),
        is_dist=s.is_dist.at[t].set(jnp.sum(inv.astype(jnp.int32)) > 1),
        cur_round=s.cur_round.at[t].set(0),
        rd_done=s.rd_done.at[t].set(jnp.zeros((D,), bool)),
        sub_lel=s.sub_lel.at[t].set(jnp.zeros((D,), jnp.int32)),
        first_lock=s.first_lock.at[t].set(jnp.full((D,), INF_US, jnp.int32)),
        txn_ctr=s.txn_ctr.at[t].add(1),
    )

    def do_dispatch(s_: SimState) -> SimState:
        s_ = _hs_dispatch(cfg, s_, jnp.where(valid, key, -1), valid)
        s_ = s_._replace(arrive=s_.arrive.at[t].set(s_.now))
        if s_.fault_time.shape[0]:
            # replica failover bookkeeping: route the hit subtxns to their
            # replicas, count the failovers and the stale read statements,
            # and record the staleness window (outage age + replication lag)
            stale_w = jnp.where(
                fo, s_.now - s_.down_since + s_.repl_lag_us, 0
            )
            s_ = s_._replace(
                on_repl=s_.on_repl.at[t].set(fo),
                failovers=s_.failovers + jnp.sum(fo.astype(jnp.int32)),
                stale_reads=s_.stale_reads
                + jnp.sum(
                    (valid & ~write & fo[ds.astype(jnp.int32)]).astype(jnp.int32)
                ),
                max_stale_us=jnp.maximum(s_.max_stale_us, jnp.max(stale_w)),
            )
        row = s_.op_state[t] != OP_NONE
        inv0 = jnp.any(oh & (row & (rnd == 0))[:, None], axis=0)
        off = _stagger(cfg, s_, t, inv0)
        # chiller: intra-region (min-RTT) subs first; cross-region wait
        # (§VII-A-1). Selected dynamically against the standard dispatch.
        tmin = jnp.min(jnp.where(inv0, s_.tau_est, INF_US))
        stage1 = inv0 & (s_.tau_est <= tmin)
        stage2 = inv0 & ~stage1
        chil_state = jnp.where(
            stage2, SUB_CHILLER_WAIT, jnp.where(stage1, SUB_SCHED, SUB_NONE)
        )
        chil_time = jnp.where(stage1, s_.now, INF_US)
        later = inv & ~inv0
        norm_state = jnp.where(
            inv0, SUB_SCHED, jnp.where(later, SUB_WAIT_ROUND, SUB_NONE)
        )
        norm_time = jnp.where(inv0, s_.now + off, INF_US)
        chiller = s_.dyn.chiller_two_stage
        s_ = s_._replace(
            sub_state=s_.sub_state.at[t].set(
                jnp.where(chiller, chil_state, norm_state).astype(jnp.int8)
            ),
            sub_time=s_.sub_time.at[t].set(
                jnp.where(chiller, chil_time, norm_time)
            ),
        )
        s_ = s_._replace(
            phase=s_.phase.at[t].set(T_ACTIVE),
            term_time=s_.term_time.at[t].set(INF_US),
        )
        return s_

    # ---- O3 late transaction scheduling (Eq.9) ----------------------------
    slot, found = hs_mod.lookup_slots(s.hs.slot_key, jnp.where(valid, key, -1), valid)
    c = s.hs.c_cnt[slot] * found.astype(jnp.int32)
    tc = s.hs.t_cnt[slot] * found.astype(jnp.int32)
    a = s.hs.a_cnt[slot] * found.astype(jnp.int32)
    p_abort = jnp.minimum(
        sched.abort_probability(c, tc, a, valid), s.dyn.block_prob_cap
    )
    u = _u01(_salt(s, 29) + t.astype(jnp.int32))
    block, force_abort = sched.admission_decision(
        p_abort, u, s.blocked[t], s.dyn.max_blocked
    )
    block = block & s.dyn.admission
    # fail fast when the footprint touches an unreachable data source: abort
    # immediately (the retry/backoff loop re-attempts it — by then the DS may
    # have recovered) instead of dispatching into a black hole. Exception:
    # when EVERY unreachable DS in the footprint has a replica and the txn
    # only reads there, the whole txn fails over — those subtxns ride the
    # replica links and their reads are stale by the outage age + repl lag.
    if s.fault_time.shape[0]:
        hit = inv & (s.ds_down | (s.mw_heal > s.now))
        writes_at_d = jnp.any(oh & (valid & write)[:, None], axis=0)  # [D]
        can_fo = hit & (s.repl_tau < INF_US) & ~writes_at_d
        do_failover = jnp.any(hit) & jnp.all(~hit | can_fo)
        fo = hit & do_failover
        hit_down = jnp.any(hit) & ~do_failover
    else:
        fo = jnp.zeros_like(inv)
        hit_down = jnp.any(inv & s.ds_down)
    force_abort = (force_abort & s.dyn.admission) | hit_down

    def do_block(s_: SimState) -> SimState:
        return s_._replace(
            blocked=s_.blocked.at[t].add(1),
            term_time=s_.term_time.at[t].set(s_.now + s_.dyn.admission_backoff_us),
        )

    def do_abort(s_: SimState) -> SimState:
        # admission / fail-fast abort: nothing dispatched; count + retry
        s_ = s_._replace(
            arrive=s_.arrive.at[t].set(s_.now),
            abort_cause=s_.abort_cause.at[t].set(
                jnp.where(hit_down, CAUSE_CRASH, CAUSE_ADMISSION)
            ),
        )
        return _finish_txn(cfg, s_, t, jnp.asarray(False))

    return jax.lax.cond(
        force_abort, do_abort, lambda s_: jax.lax.cond(block, do_block, do_dispatch, s_), s
    )


def _h_send_commits(cfg: SimConfig, bank, s: SimState, t, idx) -> SimState:
    """T_COMMIT_LOG fires: the DM flushed the commit log — broadcast commit."""
    inv = s.inv[t]
    st = s.sub_state[t]
    ids = jnp.arange(cfg.num_ds, dtype=jnp.int32)
    salts = _salt(s, 31) + ids
    base, tau = _mw_link(s, s.on_repl[t], ids, s.now)
    dtimes = base + jax.vmap(lambda r, sa: _delay(s, r, sa))(tau, salts)
    return s._replace(
        sub_state=s.sub_state.at[t].set(
            jnp.where(inv, SUB_COMMIT_CMD, st).astype(jnp.int8)
        ),
        sub_time=s.sub_time.at[t].set(jnp.where(inv, dtimes, s.sub_time[t])),
        phase=s.phase.at[t].set(T_COMMIT_WAIT),
        term_time=s.term_time.at[t].set(INF_US),
    )


def _h_op_arrive(cfg: SimConfig, bank, s: SimState, t, k) -> SimState:
    """OP_ENROUTE fires: the round's first statement reaches the DS."""
    s = s._replace(wan_legs=s.wan_legs + 1)  # DM -> DS statement leg lands
    return _attempt_lock(cfg, s, t, k)


def _h_op_timeout(cfg: SimConfig, bank, s: SimState, t, k) -> SimState:
    """OP_WAIT fires: lock-wait timeout — abort the transaction."""
    d = s.op_ds[t, k].astype(jnp.int32)
    # account the partial round into LEL before aborting
    s = s._replace(
        sub_lel=s.sub_lel.at[t, d].add(
            jnp.maximum(s.now - s.sub_arrive[t, d], 0)
        )
    )
    return _initiate_abort(cfg, s, t, d)


def _h_op_exec_done(cfg: SimConfig, bank, s: SimState, t, k) -> SimState:
    """OP_EXEC fires: statement finished; chain the next statement of this
    subtransaction or complete the round."""
    d = s.op_ds[t, k].astype(jnp.int32)
    s = s._replace(
        op_state=s.op_state.at[t, k].set(OP_HOLD),
        op_time=s.op_time.at[t, k].set(INF_US),
    )
    row = s.op_state[t]
    nxt_mask = (
        (row == OP_QUEUED)
        & (s.op_ds[t] == d.astype(s.op_ds.dtype))
        & (s.op_round[t] == s.cur_round[t])
    )
    has_next = jnp.any(nxt_mask)
    nxt = jnp.argmax(nxt_mask)

    def chain(s_: SimState) -> SimState:
        return _attempt_lock(cfg, s_, t, nxt)

    def round_done(s_: SimState) -> SimState:
        s_ = s_._replace(
            sub_lel=s_.sub_lel.at[t, d].add(
                jnp.maximum(s_.now - s_.sub_arrive[t, d], 0)
            )
        )
        d_final = jnp.max(
            jnp.where(
                (s_.op_state[t] != OP_NONE)
                & (s_.op_ds[t] == d.astype(s_.op_ds.dtype)),
                s_.op_round[t],
                -1,
            )
        )
        is_final = s_.cur_round[t] >= d_final
        centralized = jnp.sum(s_.inv[t].astype(jnp.int32)) == 1
        aborting = s_.sub_state[t, d] == SUB_ABORT_PEER  # peer abort in flight

        rbase, rtau = _mw_link(s_, s_.on_repl[t, d], d, s_.now)
        reply_t = rbase + _delay(s_, rtau, _salt(s_, 37))
        prep_t = s_.now + s_.dyn.lan_rtt_us + s_.dyn.log_flush_us
        local_t = s_.now + s_.dyn.log_flush_us
        single = (
            jnp.max(jnp.where(s_.op_state[t] != OP_NONE, s_.op_round[t], 0)) == 0
        )
        fast = _tiga_fast(s_.dyn, single, s_.inv[t], s_.sub_fast[t])
        new_state, new_time = _round_done_transition(
            s_.dyn, is_final, centralized, reply_t, prep_t, local_t, fast
        )
        s_ = s_._replace(
            fast_commits=s_.fast_commits
            + jnp.where(~aborting & (new_state == SUB_LOCAL_COMMIT), 1, 0)
        )
        return s_._replace(
            sub_state=s_.sub_state.at[t, d].set(
                jnp.where(aborting, s_.sub_state[t, d], new_state).astype(jnp.int8)
            ),
            sub_time=s_.sub_time.at[t, d].set(
                jnp.where(aborting, s_.sub_time[t, d], new_time)
            ),
        )

    return jax.lax.cond(has_next, chain, round_done, s)


def _h_sub_dispatch(cfg: SimConfig, bank, s: SimState, t, d) -> SimState:
    """SUB_SCHED fires: DM sends the current round's statements to DS d.

    Under TIGA the statements carry the synchronized-clock deadline
    `now + tiga_slack_us`: an arrival that beats it (clock skew included)
    buffers and executes at the deadline, and the `sub_fast` flag feeds the
    round-done single-round commit check."""
    abase, atau = _mw_link(s, s.on_repl[t, d], d, s.now)
    arrival = abase + _delay(s, atau, _salt(s, 41))
    first_t, fast = _tiga_arrival(s.dyn, s.clock_skew_us, s.now, arrival)
    row = s.op_state[t]
    mask = (
        (row == OP_PENDING)
        & (s.op_ds[t] == d.astype(s.op_ds.dtype))
        & (s.op_round[t] == s.cur_round[t])
    )
    first = jnp.argmax(mask)
    has = jnp.any(mask)
    new_row = jnp.where(
        mask,
        jnp.where(jnp.arange(cfg.max_ops) == first, OP_ENROUTE, OP_QUEUED),
        row,
    ).astype(jnp.int8)
    s = s._replace(
        op_state=s.op_state.at[t].set(new_row),
        op_time=s.op_time.at[t, first].set(
            jnp.where(has, first_t, s.op_time[t, first])
        ),
        sub_state=s.sub_state.at[t, d].set(SUB_RUN),
        sub_time=s.sub_time.at[t, d].set(INF_US),
        sub_arrive=s.sub_arrive.at[t, d].set(arrival),
        sub_fast=s.sub_fast.at[t, d].set(fast),
    )
    return s


def _ewma_est(cfg, s: SimState, t, d) -> SimState:
    # the monitor samples the *effective* link RTT, so a DEGRADE is observed
    # and the latency-aware scheduler re-plans around the slow link
    if s.fault_time.shape[0]:
        sample = s.tau_mw_eff[d]
        # monitor freeze: messages already in flight from a now-crashed DS
        # must not feed the latency EWMA, and replica-link fan-ins say
        # nothing about the (unreachable) primary link
        freeze = s.ds_down[d] | s.on_repl[t, d]
    else:
        sample = s.tau_true[d]
        freeze = s.ds_down[d]  # all-False on fault-free runs
    new = ewma_update(s.tau_est[d], sample, jnp.int32(cfg.beta_milli))
    new = jnp.where(freeze, s.tau_est[d], new)
    return s._replace(tau_est=s.tau_est.at[d].set(new))


def _h_dm_round_in(cfg: SimConfig, bank, s: SimState, t, d) -> SimState:
    """SUB_ROUND_REPLY / SUB_VOTE fires at the DM.

    One fused handler for both fan-ins: they differ only in the recorded sub
    state, and sharing the body keeps the heavy `_dm_progress` machinery
    traced once in the dispatch switch (smaller compile, cheaper lockstep
    lanes under vmap, where every branch executes)."""
    is_reply = s.sub_state[t, d] == SUB_ROUND_REPLY
    s = _ewma_est(cfg, s, t, d)
    s = s._replace(wan_legs=s.wan_legs + 1)  # DS -> DM reply/vote leg lands
    s = s._replace(
        sub_state=s.sub_state.at[t, d].set(
            jnp.where(is_reply, SUB_ROUND_AT_DM, SUB_VOTED).astype(jnp.int8)
        ),
        sub_time=s.sub_time.at[t, d].set(INF_US),
        rd_done=s.rd_done.at[t, d].set(True),
    )
    return _dm_progress(cfg, s, t)


def _h_ds_prep_cmd(cfg: SimConfig, bank, s: SimState, t, d) -> SimState:
    """SUB_PREP_CMD fires at DS (coordinated 2PC prepare)."""
    return s._replace(
        wan_legs=s.wan_legs + 1,  # DM -> DS prepare-command leg lands
        sub_state=s.sub_state.at[t, d].set(SUB_PREPARING),
        sub_time=s.sub_time.at[t, d].set(s.now + s.dyn.log_flush_us),
    )


def _h_ds_prepared(cfg: SimConfig, bank, s: SimState, t, d) -> SimState:
    """SUB_PREPARING fires: WAL flushed; send the vote to the DM."""
    vbase, vtau = _mw_link(s, s.on_repl[t, d], d, s.now)
    return s._replace(
        sub_state=s.sub_state.at[t, d].set(SUB_VOTE),
        sub_time=s.sub_time.at[t, d].set(
            vbase + _delay(s, vtau, _salt(s, 43))
        ),
    )


def _h_ds_finish(cfg: SimConfig, bank, s: SimState, t, d) -> SimState:
    """SUB_COMMIT_CMD / SUB_LOCAL_COMMIT / SUB_ABORT_PEER fires at DS d:
    apply (or roll back), release locks and ack back to the DM.

    One fused handler for all three lock-releasing DS events: the
    release/grant machinery — the heaviest kernel in the engine — is traced
    once; commit-vs-abort differences reduce to the hotspot `committed` flag,
    the LCS gate and the reply salt/state constants."""
    st0 = s.sub_state[t, d]
    is_commit = (st0 == SUB_COMMIT_CMD) | (st0 == SUB_LOCAL_COMMIT)
    # WAN legs landing here: DM->DS commit commands always rode the WAN,
    # local commits were decided at the DS (no leg), abort commands only
    # when routed via the DM (the early-abort route is geo-agent mesh)
    s = s._replace(
        wan_legs=s.wan_legs
        + jnp.where(st0 == SUB_COMMIT_CMD, 1, 0)
        + jnp.where((st0 == SUB_ABORT_PEER) & ~s.dyn.early_abort, 1, 0)
    )
    s = _lcs_metric(cfg, s, t, d, gate=is_commit)
    s = _hs_complete_ds(cfg, s, t, d, is_commit)
    s = _release_and_grant(cfg, s, t, d)
    salt = _salt(s, 47) + jnp.where(is_commit, 0, 6)  # 47 commit, 53 abort
    kbase, ktau = _mw_link(s, s.on_repl[t, d], d, s.now)
    return s._replace(
        sub_state=s.sub_state.at[t, d].set(
            jnp.where(is_commit, SUB_ACK, SUB_ABORT_ACK).astype(jnp.int8)
        ),
        sub_time=s.sub_time.at[t, d].set(
            kbase + _delay(s, ktau, salt)
        ),
    )


def _h_dm_fin(cfg: SimConfig, bank, s: SimState, t, d) -> SimState:
    """SUB_ACK / SUB_ABORT_ACK fires at the DM: the transaction completes
    when the last ack arrives (fused commit/abort fan-in — `_finish_txn` is
    traced once, with the commit flag derived from the acked state)."""
    committed = s.sub_state[t, d] == SUB_ACK
    s = _ewma_est(cfg, s, t, d)
    s = s._replace(wan_legs=s.wan_legs + 1)  # DS -> DM finish-ack leg lands
    s = s._replace(
        sub_state=s.sub_state.at[t, d].set(
            jnp.where(committed, SUB_DONE, SUB_ABORTED).astype(jnp.int8)
        ),
        sub_time=s.sub_time.at[t, d].set(INF_US),
    )
    want = jnp.where(committed, SUB_DONE, SUB_ABORTED).astype(s.sub_state.dtype)
    done = jnp.all(~s.inv[t] | (s.sub_state[t] == want))
    return jax.lax.cond(
        done, lambda s_: _finish_txn(cfg, s_, t, committed), lambda s_: s_, s
    )


def _h_noop(cfg: SimConfig, bank, s: SimState, t, d) -> SimState:
    # Safety valve: an event fired in an unexpected state. Clear it so the
    # loop cannot spin; `noops` must stay 0 (invariant-checked in tests).
    upd = dict(
        op_time=jnp.where(s.op_time == s.now, INF_US, s.op_time),
        sub_time=jnp.where(s.sub_time == s.now, INF_US, s.sub_time),
        term_time=jnp.where(s.term_time == s.now, INF_US, s.term_time),
        noops=s.noops + 1,
    )
    if s.fault_time.shape[0]:  # fault sections exist only when max_faults > 0
        upd.update(
            fault_time=jnp.where(s.fault_time == s.now, INF_US, s.fault_time),
            hb_time=jnp.where(s.hb_time == s.now, INF_US, s.hb_time),
        )
    return s._replace(**upd)
