"""Deterministic fault injection: typed link/node faults + heartbeat probes.

Fault events live in a per-world schedule (``WorldSpec.faults``, padded to
``SimConfig.max_faults`` rows of ``(t_start_us, kind, endpoint_a,
endpoint_b, t_end_us, severity)`` — see ``state.KIND_CRASH`` /
``KIND_PARTITION`` / ``KIND_DEGRADE``) and fire as first-class events from
the ``_times_flat`` tail sections. The masked event bodies below are shared
verbatim by all four step modes — `step._step` dispatches them as switch
branches, `omni._omni_step` and `fused._omni_window` run them as
identity-when-off sections at the very end of their passes — so faulted runs
stay bitwise-identical across modes by construction. A fault-free config
(``max_faults == 0``) compiles none of this: the tail sections, and every
call site, are gated on the static fault count.

Failure detection is modeled by ``DynProto.detect_delay_us``: `init_state`
shifts every crash/partition start by that much, so the event that fires
here IS the detection point (degrades are physical link changes and shift
nothing; end timestamps are never shifted). Heartbeat probes model the
reachability checks the middleware keeps sending while a data source is
crashed OR partitioned from it — a partitioned DS is up yet unreachable, so
probes (and the availability charge) gate on reachability, not liveness.
"""

from __future__ import annotations

import jax.numpy as jnp

from bench.ref import hotspot as hs_mod
from bench.ref.netmodel import INF_US

from bench.ref.state import (
    CAUSE_CRASH,
    KIND_CRASH,
    KIND_PARTITION,
    KIND_DEGRADE,
    OP_NONE,
    OP_DONE,
    OP_ENROUTE,
    SUB_ROUND_REPLY,
    SUB_PREP_CMD,
    SUB_PREPARING,
    SUB_VOTE,
    SUB_COMMIT_CMD,
    SUB_ACK,
    SUB_LOCAL_COMMIT,
    SUB_DONE,
    SUB_ABORT_PEER,
    SUB_ABORT_ACK,
    SUB_ABORTED,
    T_ACTIVE,
    T_COMMIT_LOG,
    T_ABORT_WAIT,
    SimConfig,
    SimState,
    _delay_salted,
    _ds_send,
    _mw_send,
    _salt,
)


def _fault_event(cfg: SimConfig, s: SimState, f, active) -> SimState:
    """Fault-schedule row f fires (identity when ``active`` is False).

    Stage 0 is the fault start, stage 1 the end; what happens depends on the
    row's kind:

    CRASH (PR 6 semantics): mark the DS down and freeze the latency
    monitor's input, crash-abort every engaged transaction with undecided
    work there (peers route through the ordinary SUB_ABORT_PEER machinery,
    which releases locks and FIFO-regrants waiters at the surviving data
    sources), wipe the victims' ops at the dead DS, defer already-decided
    commands addressed to it until recovery, and arm the heartbeat probe.

    PARTITION of the middleware<->b link: stamp ``mw_heal[b]``, start the
    unreachability charge and arm the probe — the DS stays alive, so there
    is NO crash cascade; messages in flight on the severed link (replies,
    votes, commands, acks) are held to the heal time and then resolve
    through the ordinary timeout/retry machinery, and new sends defer at
    send time via `_mw_send`. Subtxns already failed over to b's replica are
    untouched (their traffic rides the replica link). PARTITION of a mesh
    link a<->b only stamps ``ds_heal`` (both directions): in-flight mesh
    messages are considered already in the pipe and delivered, future sends
    defer via `_ds_send`, and neither endpoint becomes unreachable from the
    middleware — no availability charge.

    DEGRADE: scale the link's effective RTT (`tau_mw_eff` / `tau_ds_eff`)
    by severity/1000 at the start, restore the pristine value at the end.
    Nothing is deferred and nothing aborts — the EWMA monitor keeps
    observing the slow link, so the latency-aware scheduler re-plans.
    """
    T, D = cfg.terminals, cfg.num_ds
    kind = s.fault_kind[f]
    peer = s.fault_peer[f]
    sev = s.fault_sev[f]
    endp_a = s.fault_ds[f]
    is_mw = endp_a < 0  # middleware side of a link fault
    # DS-side endpoint: the crashed DS, the mw-link's far end, or mesh a
    node = jnp.where(is_mw, peer, endp_a)
    a_ix = jnp.maximum(endp_a, 0)  # safe mesh row index (masked when is_mw)

    start = active & (s.fault_stage[f] == 0)
    end = active & (s.fault_stage[f] == 1)
    rec_t = s.fault_recover[f]

    crash = start & (kind == KIND_CRASH)
    crash_rec = end & (kind == KIND_CRASH)
    part_mw = (kind == KIND_PARTITION) & is_mw
    part_ds = (kind == KIND_PARTITION) & ~is_mw
    degr_mw = (kind == KIND_DEGRADE) & is_mw
    degr_ds = (kind == KIND_DEGRADE) & ~is_mw
    # unreachability spell (crash or mw partition): availability + heartbeat
    cut_start = start & ((kind == KIND_CRASH) | part_mw)
    cut_end = end & ((kind == KIND_CRASH) | part_mw)

    # schedule-row + reachability bookkeeping (row f advances start -> end;
    # a detection delay can push the start past t_end, so the end event is
    # floored to strictly-after-now — at zero delay this is exactly rec_t)
    s = s._replace(
        fault_stage=s.fault_stage.at[f].set(
            jnp.where(start, 1, jnp.where(end, 2, s.fault_stage[f])).astype(
                jnp.int8
            )
        ),
        fault_time=s.fault_time.at[f].set(
            jnp.where(
                start,
                jnp.maximum(rec_t, s.now + 1),
                jnp.where(end, INF_US, s.fault_time[f]),
            )
        ),
        ds_down=s.ds_down.at[node].set(
            jnp.where(crash, True, jnp.where(crash_rec, False, s.ds_down[node]))
        ),
        mw_heal=s.mw_heal.at[node].set(
            jnp.where(start & part_mw, rec_t, s.mw_heal[node])
        ),
        down_since=s.down_since.at[node].set(
            jnp.where(cut_start, s.now, s.down_since[node])
        ),
        down_us=s.down_us.at[node].add(
            jnp.where(cut_end, s.now - s.down_since[node], 0)
        ),
        hb_time=s.hb_time.at[node].set(
            jnp.where(
                cut_start,
                s.now + s.dyn.hb_interval_us,
                jnp.where(cut_end, INF_US, s.hb_time[node]),
            )
        ),
    )

    # ---- mesh partition / degrade: pure link-state writes -------------------
    heal_ab = jnp.where(start & part_ds, rec_t, s.ds_heal[a_ix, peer])
    heal_ba = jnp.where(start & part_ds, rec_t, s.ds_heal[peer, a_ix])
    eff_mw = jnp.where(
        start & degr_mw,
        s.tau_true[node] * sev // 1000,
        jnp.where(end & degr_mw, s.tau_true[node], s.tau_mw_eff[node]),
    )
    eff_ab = jnp.where(
        start & degr_ds,
        s.tau_ds[a_ix, peer] * sev // 1000,
        jnp.where(end & degr_ds, s.tau_ds[a_ix, peer], s.tau_ds_eff[a_ix, peer]),
    )
    eff_ba = jnp.where(
        start & degr_ds,
        s.tau_ds[peer, a_ix] * sev // 1000,
        jnp.where(end & degr_ds, s.tau_ds[peer, a_ix], s.tau_ds_eff[peer, a_ix]),
    )
    s = s._replace(
        ds_heal=s.ds_heal.at[a_ix, peer].set(heal_ab).at[peer, a_ix].set(heal_ba),
        tau_mw_eff=s.tau_mw_eff.at[node].set(eff_mw),
        tau_ds_eff=s.tau_ds_eff.at[a_ix, peer].set(eff_ab).at[peer, a_ix].set(eff_ba),
    )

    # ---- crash cascade ------------------------------------------------------
    # victims: engaged transactions whose subtxn at the dead DS has not
    # reached the commit decision and is not already aborting. Post-decision
    # rows keep their locks; their DS-side commands are deferred below.
    std = s.sub_state[:, node]
    post = (
        (std == SUB_COMMIT_CMD)
        | (std == SUB_ACK)
        | (std == SUB_LOCAL_COMMIT)
        | (std == SUB_DONE)
    )
    abortf_d = (
        (std == SUB_ABORT_PEER) | (std == SUB_ABORT_ACK) | (std == SUB_ABORTED)
    )
    engaged = (s.phase == T_ACTIVE) | (s.phase == T_COMMIT_LOG)
    victim = crash & s.inv[:, node] & engaged & ~post & ~abortf_d  # [T]

    # wipe the victims' ops at the dead DS (state is op-derived, so this IS
    # the lock release there; no grants — every waiter at d is a victim too)
    op_at_d = (s.op_state != OP_NONE) & (s.op_ds == node.astype(s.op_ds.dtype))
    wipe = victim[:, None] & op_at_d
    s = s._replace(
        op_state=jnp.where(wipe, OP_DONE, s.op_state).astype(jnp.int8),
        op_time=jnp.where(wipe, INF_US, s.op_time),
    )

    # hot-table bookkeeping for the wiped footprint: a_cnt -> t_cnt like
    # `_hs_complete_ds(committed=False)`, but WITHOUT the Eq.(4) w_lat update
    # — a crash-truncated span is not a latency observation (monitor freeze)
    keys_flat = s.op_key.reshape(-1)
    wipe_flat = wipe.reshape(-1)
    slot, found = hs_mod.lookup_slots(s.hs.slot_key, keys_flat, wipe_flat)
    upd = found.astype(jnp.int32)
    hs = s.hs
    hs = hs._replace(
        a_cnt=jnp.maximum(hs.a_cnt.at[slot].add(-upd), 0),
        t_cnt=hs.t_cnt.at[slot].add(upd),
    )
    s = s._replace(hs=hs)

    # peer-abort fan-out, vectorized over victims (mirrors `_initiate_abort`:
    # direct DS<->DS notify under early_abort, else routed through the DM;
    # the co-located geo-agent acks the dead DS's own slot). Hops ride the
    # *effective* links: concurrently degraded/partitioned mesh or peer-mw
    # links slow or hold the notifications (the dead DS's own mw link cannot
    # carry a concurrent fault — the schedule validator keeps a crash
    # exclusive on both its node and its mw link).
    ids = jnp.arange(D, dtype=jnp.int32)
    tids = jnp.arange(T, dtype=jnp.int32)
    sa = _salt(s, 59) + tids[:, None] * jnp.int32(D) + ids[None, :]  # [T,D]
    mesh_base, mesh_tau = _ds_send(s, node, ids, s.now)  # [D], [D]
    notify_direct = mesh_base[None, :] + _delay_salted(
        s.jitter_milli, mesh_tau[None, :], sa
    )
    to_dm = s.now + _delay_salted(
        s.jitter_milli, s.tau_mw_eff[node], _salt(s, 61) + tids
    )
    dm_base, dm_tau = _mw_send(s, s.on_repl, ids[None, :], to_dm[:, None])
    notify_dm = dm_base + _delay_salted(s.jitter_milli, dm_tau, sa)
    notify = jnp.where(s.dyn.early_abort, notify_direct, notify_dm)  # [T,D]
    own_ack = s.now + _delay_salted(
        s.jitter_milli, s.tau_mw_eff[node], _salt(s, 67) + tids
    )  # [T]

    at_d = ids[None, :] == node  # [1,D] -> broadcasts over [T,D]
    abortf = (
        (s.sub_state == SUB_ABORT_PEER)
        | (s.sub_state == SUB_ABORT_ACK)
        | (s.sub_state == SUB_ABORTED)
    )
    peers = victim[:, None] & s.inv & ~at_d & ~abortf
    own = victim[:, None] & at_d
    new_sub = jnp.where(
        peers, SUB_ABORT_PEER, jnp.where(own, SUB_ABORT_ACK, s.sub_state)
    )
    new_tm = jnp.where(
        peers, notify, jnp.where(own, own_ack[:, None], s.sub_time)
    )

    # defer DS-side commands addressed to the dead DS until it recovers
    # (commit/apply/prepare/abort commands can only pre-exist the crash —
    # nothing new is dispatched to a down DS: starts fail fast, undecided
    # work was just aborted)
    ds_side = (
        (std == SUB_COMMIT_CMD)
        | (std == SUB_LOCAL_COMMIT)
        | (std == SUB_PREP_CMD)
        | (std == SUB_PREPARING)
        | (std == SUB_ABORT_PEER)
    )
    defer = crash & ds_side & ~victim  # [T]
    new_tm = jnp.where(
        defer[:, None] & at_d, jnp.maximum(new_tm, rec_t), new_tm
    )

    # ---- mw-partition in-flight deferral ------------------------------------
    # messages crossing the severed middleware<->node link are held to the
    # heal time: replies/votes/acks traveling up, prepare/commit/abort
    # commands traveling down, and statements en route. DS-local work
    # (SUB_PREPARING log writes, executing ops) proceeds — its *next* send
    # defers at send time via `_mw_send`. Replica-served subtxns are exempt.
    in_flight = (
        (std == SUB_ROUND_REPLY)
        | (std == SUB_PREP_CMD)
        | (std == SUB_VOTE)
        | (std == SUB_COMMIT_CMD)
        | (std == SUB_ACK)
        | (std == SUB_ABORT_PEER)
        | (std == SUB_ABORT_ACK)
    )
    pdefer = (start & part_mw) & in_flight & ~s.on_repl[:, node]  # [T]
    new_tm = jnp.where(
        pdefer[:, None] & at_d, jnp.maximum(new_tm, rec_t), new_tm
    )
    op_enroute = (s.op_state == OP_ENROUTE) & (
        s.op_ds == node.astype(s.op_ds.dtype)
    )
    opdef = (
        (start & part_mw) & op_enroute & ~s.on_repl[:, node][:, None]
    )  # [T,K]
    s = s._replace(
        op_time=jnp.where(opdef, jnp.maximum(s.op_time, rec_t), s.op_time)
    )

    return s._replace(
        sub_state=new_sub.astype(jnp.int8),
        sub_time=new_tm,
        phase=jnp.where(victim, T_ABORT_WAIT, s.phase).astype(jnp.int8),
        term_time=jnp.where(victim, INF_US, s.term_time),
        abort_cause=jnp.where(victim, CAUSE_CRASH, s.abort_cause),
    )


def _hb_event(cfg: SimConfig, s: SimState, d, active) -> SimState:
    """Heartbeat probe at DS d (identity when ``active`` is False): count it
    and re-arm while the DS is *unreachable* — crashed or partitioned from
    the middleware (a partitioned DS is up yet unreachable, so liveness
    alone is the wrong gate). The fault-end event disarms the probe (sets
    hb_time to INF), so probes only ever fire during an outage; the
    ~unreachable clear below is the same can't-spin safety valve as
    `_h_noop`."""
    fire = active & (s.ds_down[d] | (s.mw_heal[d] > s.now))
    return s._replace(
        hb_count=s.hb_count.at[d].add(fire.astype(jnp.int32)),
        hb_time=s.hb_time.at[d].set(
            jnp.where(
                fire,
                s.now + s.dyn.hb_interval_us,
                jnp.where(active, INF_US, s.hb_time[d]),
            )
        ),
    )


def _h_fault(cfg: SimConfig, bank, s: SimState, f, idx) -> SimState:
    return _fault_event(cfg, s, f, jnp.asarray(True))


def _h_hb(cfg: SimConfig, bank, s: SimState, d, idx) -> SimState:
    return _hb_event(cfg, s, d, jnp.asarray(True))
