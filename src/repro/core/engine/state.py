"""Static shapes, state containers and shared scalar helpers.

The data layer of the engine package: event/op/subtxn/terminal state
constants, the dynamic protocol knobs (`DynProto`), the per-cell sweep input
(`WorldSpec`), the static compile key (`SimConfig`), the full carried state
(`SimState`) and its initializers, plus the small pure helpers (delays,
salts, histogram bins, the concatenated event-time view) every step mode
shares. Nothing here dispatches events — see `handlers`/`step`/`omni`/
`window` for the step modes and `batch` for the run/sweep entry points.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import hotspot as hs_mod
from repro.core.netmodel import (
    INF_US,
    PAPER_RTT_MS,
    _hash_u32,
    derive_tau_ds_us,
    make_net_params,
)
from repro.core.protocols import (
    PRESETS,
    PREPARE_DECENTRAL,
    STAGGER_NONE,
    ProtocolConfig,
)

# ---- op states -------------------------------------------------------------
OP_NONE, OP_PENDING, OP_ENROUTE, OP_QUEUED, OP_WAIT, OP_EXEC, OP_HOLD, OP_DONE = range(8)

# ---- subtxn states ---------------------------------------------------------
(
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
) = range(18)

# ---- terminal phases -------------------------------------------------------
T_IDLE, T_ACTIVE, T_COMMIT_LOG, T_COMMIT_WAIT, T_ABORT_WAIT = range(5)

# ---- lock modes ------------------------------------------------------------
LK_FREE, LK_SHARED, LK_X = 0, 1, 2

HIST_BINS = 128
_HIST_BASE_US = 100.0  # bin 0 at 100 µs, 8 bins per octave

_SALT_MUL = jnp.int32(2654435761 % (2**31))

# ---- windowed-drain stop reasons --------------------------------------------
# Why each applied window ended, indexing `SimState.win_stops` (see
# window.py for the stopper mechanics and docs/architecture.md for the table):
#   horizon       first excluded event lies at/after the horizon (or nothing
#                 is left to stop on — every pending event drained)
#   nondrainable  a non-drainable event: txn start, lock-wait timeout, round
#                 advance, chiller stage-2 re-dispatch, txn-completing ack,
#                 release with a queued waiter
#   scheduled     an in-window event schedules new work at or before the
#                 window's timestamps (running-min rule) that the two-pass
#                 chain admitter could NOT absorb — a genuine scheduling
#                 fence (non-chainable follow-up kind, or a chainable one
#                 that lands outside the candidate time range)
#   lock_key      second touch of one lock key (arrival / chain target /
#                 released footprint)
#   dm_row        slot-accurate DM row rule: a fan-in preceded by a non-fan-in
#                 event of its terminal, or any event behind a *triggering*
#                 fan-in / commit-log flush (row-writers stay forward-exclusive)
#   dm_col        more than K_EWMA fan-ins on one data source (the latency
#                 monitor's unrolled EWMA chain caps out)
#   rel_op        a release sharing its (terminal, DS) with an earlier op event
#   cap           the window filled the planner's candidate budget
#                 (window.PLAN_CAP events) — longer windows split, bitwise-
#                 identically, across iterations
#   fault         a fault-schedule event (crash / partition / degrade start
#                 or end) — always pinned: every kind rewrites link, replica
#                 or row state that in-window sends consult. Heartbeat
#                 probes are conflict-free and drain inside windows (their
#                 re-arm time enters the running-min rule like any other
#                 scheduled event)
#   sched_chain   the stopper is a *chained follow-up* the two-pass plan
#                 admitted into the window (a zero-RTT lock grant, exec-chain
#                 completion or prepare flush scheduled by an earlier window
#                 event) whose own follow-up could not also be admitted —
#                 the pre-PR-10 plan would have stopped earlier, at the
#                 scheduling fence, and counted `scheduled`. Together with
#                 `SimState.chained` this splits the old `scheduled` row into
#                 fence-stops (still `scheduled`) and chained-admits.
STOP_REASONS = (
    "horizon",
    "nondrainable",
    "scheduled",
    "lock_key",
    "dm_row",
    "dm_col",
    "rel_op",
    "cap",
    "fault",
    "sched_chain",
)
N_STOP_REASONS = len(STOP_REASONS)

# ---- abort cause codes ------------------------------------------------------
# Recorded per-terminal while a txn is in flight (`SimState.abort_cause`) and
# tallied into `SimState.ab_cause` when the abort finishes; surfaced as the
# `abort_causes` breakdown in `metrics.drain_stats`.
(
    CAUSE_NONE,  # committed / never aborted
    CAUSE_TIMEOUT,  # lock-wait timeout fired (`_h_op_timeout`)
    CAUSE_ADMISSION,  # O3 admission control aborted at start
    CAUSE_CRASH,  # data-source crash killed or fail-fasted the txn
    CAUSE_EXHAUSTED,  # retry budget spent: final abort after max_retries
) = range(5)
N_ABORT_CAUSES = 5
ABORT_CAUSES = ("none", "timeout", "admission", "crash", "exhausted")

# ---- fault kinds ------------------------------------------------------------
# `WorldSpec.faults` rows are (t_start_us, kind, endpoint_a, endpoint_b,
# t_end_us, severity):
#   CRASH      whole data source down (endpoint_a == endpoint_b == ds);
#              severity ignored. The PR 6 semantics: instant cascade through
#              peer-abort/lock-release, admission fail-fast, monitor freeze.
#   PARTITION  one link severed while both endpoints stay up. endpoint_a ==
#              -1 targets the middleware<->endpoint_b link (`tau_true`);
#              endpoint_a >= 0 targets the geo-agent mesh link
#              `tau_ds[a, b]` (both directions). In-flight statements on the
#              severed middleware link are deferred to the heal time and
#              resolve through the ordinary timeout/retry machinery — no
#              crash cascade.
#   DEGRADE    the link's RTT is multiplied by severity/1000 (milli-scale,
#              1000 = 1x) between t_start and t_end. The EWMA monitor keeps
#              observing the degraded link, so the latency-aware scheduler
#              re-plans around it.
KIND_CRASH, KIND_PARTITION, KIND_DEGRADE = 0, 1, 2
FAULT_KINDS = ("crash", "partition", "degrade")
MW = -1  # endpoint_a value selecting the middleware side of a link


class DynProto(NamedTuple):
    """Dynamic (traced) protocol knobs.

    Every `ProtocolConfig` field the event handlers consult lives here as a
    scalar array rather than being baked into the compiled program: one
    compiled engine serves all presets, and a leading batch axis turns the
    engine into a multi-protocol sweep under `jax.vmap`.
    """

    prepare: jax.Array  # i32: PREPARE_COORD / PREPARE_DECENTRAL / PREPARE_NONE
    stagger: jax.Array  # i32: STAGGER_NONE / STAGGER_NET / STAGGER_NET_LEL
    admission: jax.Array  # bool (O3)
    early_abort: jax.Array  # bool (O1 geo-agent peer abort)
    chiller_two_stage: jax.Array  # bool
    middleware_cc: jax.Array  # bool (ScalarDB-style per-op WAN RTT)
    async_local_commit: jax.Array  # bool (YUGA)
    co_commit: jax.Array  # bool (FASTC: co-coordinator decides commit locally)
    opt_abort: jax.Array  # bool (OPTA: abort on lock conflict instead of wait)
    tiga_slack_us: jax.Array  # i32 (TIGA deadline slack; 0 = disabled)
    max_blocked: jax.Array  # i32
    admission_backoff_us: jax.Array  # i32
    block_prob_cap: jax.Array  # f32
    lock_timeout_us: jax.Array  # i32
    exec_us: jax.Array  # i32
    log_flush_us: jax.Array  # i32
    lan_rtt_us: jax.Array  # i32
    retry_backoff_us: jax.Array  # i32
    max_retries: jax.Array  # i32
    hb_interval_us: jax.Array  # i32 — heartbeat probe period while unreachable
    detect_delay_us: jax.Array  # i32 — crash/partition detection lag


def dyn_from_proto(p: ProtocolConfig) -> DynProto:
    if p.max_retries > 0 and p.retry_backoff_us <= 0:
        # the retry loop re-schedules the aborted terminal at now + backoff;
        # a zero backoff would respin the same microsecond until max_events
        raise ValueError(
            f"preset {p.name!r}: max_retries={p.max_retries} needs "
            f"retry_backoff_us > 0 (got {p.retry_backoff_us})"
        )
    if p.detect_delay_us < 0:
        # the schedule shifts crash/partition starts by this much; a negative
        # value would fire the fault before its own scheduled timestamp
        raise ValueError(
            f"preset {p.name!r}: detect_delay_us must be >= 0 "
            f"(got {p.detect_delay_us})"
        )
    if p.co_commit and (p.prepare != PREPARE_DECENTRAL or p.chiller_two_stage):
        # the co-coordinator fast path replaces the decentralized prepare's
        # final-round transition; it has no meaning under DM-coordinated /
        # no-prepare commit, and chiller stage-2 subs would commit before the
        # cross-region stage even dispatched
        raise ValueError(
            f"preset {p.name!r}: co_commit requires PREPARE_DECENTRAL "
            f"without chiller_two_stage"
        )
    if p.tiga_slack_us < 0:
        raise ValueError(
            f"preset {p.name!r}: tiga_slack_us must be >= 0 (got {p.tiga_slack_us})"
        )
    if p.tiga_slack_us > 0 and (
        p.prepare != PREPARE_DECENTRAL
        or p.stagger != STAGGER_NONE
        or p.chiller_two_stage
        or p.co_commit
    ):
        # the deadline fast path decides per data source from the per-sub
        # arrival flags; staggered/chiller dispatch would let one sub's round
        # finish before a sibling's dispatch even fired, making the "all
        # statements arrived in the future" check racy, and co_commit would
        # double-claim the same final-round transition
        raise ValueError(
            f"preset {p.name!r}: tiga_slack_us > 0 requires PREPARE_DECENTRAL "
            f"+ STAGGER_NONE without chiller_two_stage/co_commit"
        )
    i32 = jnp.int32
    return DynProto(
        prepare=i32(p.prepare),
        stagger=i32(p.stagger),
        admission=jnp.asarray(p.admission),
        early_abort=jnp.asarray(p.early_abort),
        chiller_two_stage=jnp.asarray(p.chiller_two_stage),
        middleware_cc=jnp.asarray(p.middleware_cc),
        async_local_commit=jnp.asarray(p.async_local_commit),
        co_commit=jnp.asarray(p.co_commit),
        opt_abort=jnp.asarray(p.opt_abort),
        tiga_slack_us=i32(p.tiga_slack_us),
        max_blocked=i32(p.max_blocked),
        admission_backoff_us=i32(p.admission_backoff_us),
        block_prob_cap=jnp.float32(p.block_prob_cap),
        lock_timeout_us=i32(p.lock_timeout_us),
        exec_us=i32(p.exec_us),
        log_flush_us=i32(p.log_flush_us),
        lan_rtt_us=i32(p.lan_rtt_us),
        retry_backoff_us=i32(p.retry_backoff_us),
        max_retries=i32(p.max_retries),
        hb_interval_us=i32(p.hb_interval_us),
        detect_delay_us=i32(p.detect_delay_us),
    )


class WorldSpec(NamedTuple):
    """One cell of an evaluation grid: every per-run dynamic input.

    Unbatched leaves describe a single world; `stack_worlds` adds a leading
    batch axis for `simulate_batch`. `seed` is an informational tag carried
    through sweeps (the engine itself is deterministic; workload randomness
    lives in the Bank, whose leaves may also be batched).
    """

    tau_true: jax.Array  # [D] DM<->DS RTT µs
    tau_ds: jax.Array  # [D,D] geo-agent mesh RTT µs
    jitter_milli: jax.Array  # scalar
    exec_scale_milli: jax.Array  # [D] heterogeneous engine profile
    lel_scale_milli: jax.Array  # scalar (§IV-C forecast scaling)
    dyn: DynProto
    seed: jax.Array  # scalar tag
    # deterministic fault schedule: [F,6] rows (t_start_us, kind, endpoint_a,
    # endpoint_b, t_end_us, severity) — see the KIND_* table above — padded
    # with (INF_US, CRASH, 0, 0, INF_US, 0). Legacy [F,3] crash triples
    # (t_crash_us, ds, t_recover_us) are auto-widened by `pad_faults`.
    # F is static (`SimConfig.max_faults`).
    faults: jax.Array
    # optional geo-replica per DS: replica-link RTT (INF_US = no replica) and
    # the shared replication lag charged to every stale read. Defaults keep
    # direct WorldSpec(...) constructions from before the replica layer valid.
    replica_tau: jax.Array = None  # [D] i32 (None = no replicas anywhere)
    repl_lag_us: jax.Array = 0  # scalar i32
    # synchronized-clock error bound (µs) between the middleware and the data
    # sources; only TIGA's deadline check consults it. Default keeps direct
    # WorldSpec(...) constructions from before the protocol zoo valid.
    clock_skew_us: jax.Array = 0  # scalar i32


FAULT_COLS = 6
_PAD_ROW = (INF_US, KIND_CRASH, 0, 0, INF_US, 0)


def _widen_faults(rows: jax.Array) -> jax.Array:
    """[n,3] legacy crash triples -> [n,6] typed rows (no-op on [n,6])."""
    if rows.shape[-1] == FAULT_COLS:
        return rows
    if rows.shape[-1] != 3:
        raise ValueError(
            f"fault rows must have 3 (legacy crash) or {FAULT_COLS} columns, "
            f"got {rows.shape[-1]}"
        )
    t, ds, rec = rows[:, 0], rows[:, 1], rows[:, 2]
    kind = jnp.full_like(t, KIND_CRASH)
    sev = jnp.zeros_like(t)
    return jnp.stack([t, kind, ds, ds, rec, sev], axis=1)


def pad_faults(faults, max_faults: int | None = None) -> jax.Array:
    """Normalize a fault schedule to a static [F,6] i32 array.

    `faults` is a sequence of (t_start_us, kind, endpoint_a, endpoint_b,
    t_end_us, severity) rows — legacy (t_crash_us, ds, t_recover_us) crash
    triples are accepted and widened — or an equivalent array; None means no
    faults. Padding rows carry t_start == INF_US so their events never fire
    inside the horizon.
    """
    if faults is None:
        rows = jnp.zeros((0, FAULT_COLS), jnp.int32)
    else:
        rows = jnp.asarray(faults, jnp.int32)
        if rows.ndim != 2:
            # flat sequences: prefer the typed 6-column layout, fall back to
            # legacy triples
            cols = FAULT_COLS if rows.size % FAULT_COLS == 0 else 3
            rows = rows.reshape(-1, cols)
        rows = _widen_faults(rows)
    n = rows.shape[0]
    if max_faults is None:
        max_faults = n
    if n > max_faults:
        raise ValueError(f"{n} fault rows exceed max_faults={max_faults}")
    pad = jnp.tile(jnp.array([_PAD_ROW], jnp.int32), (max_faults - n, 1))
    return jnp.concatenate([rows, pad], axis=0)


def make_world(
    proto,
    rtt_ms=None,
    *,
    tau_true_us=None,
    tau_ds_us=None,
    jitter_milli: int = 0,
    exec_scale_milli=None,
    seed: int = 0,
    faults=None,
    max_faults: int | None = None,
    replica_tau=None,
    repl_lag_us: int = 0,
    clock_skew_us: int = 0,
) -> WorldSpec:
    """Build a WorldSpec from a preset name / ProtocolConfig + RTT vector.

    `replica_tau` is an optional [D] middleware<->replica RTT vector (µs);
    entries of INF_US (and a None vector) mean "no replica at this DS".
    `repl_lag_us` is the replication lag charged to stale reads on failover.
    `clock_skew_us` is the synchronized-clock error bound TIGA's deadline
    check charges against arrivals.
    """
    if isinstance(proto, str):
        proto = PRESETS[proto]
    if tau_true_us is None:
        net = make_net_params(rtt_ms if rtt_ms is not None else PAPER_RTT_MS)
        tau_true_us = net.tau_dm
    tau_true = jnp.asarray(tau_true_us, jnp.int32)
    if tau_ds_us is None:
        # geo-agent mesh always derived from tau_true itself, so
        # caller-supplied tau_true_us stays consistent with the mesh
        tau_ds_us = derive_tau_ds_us(tau_true)
    if exec_scale_milli is None:
        exec_scale_milli = jnp.full(tau_true.shape, 1000, jnp.int32)
    if replica_tau is None:
        replica_tau = jnp.full(tau_true.shape, INF_US, jnp.int32)
    return WorldSpec(
        tau_true=tau_true,
        tau_ds=jnp.asarray(tau_ds_us, jnp.int32),
        jitter_milli=jnp.int32(jitter_milli),
        exec_scale_milli=jnp.asarray(exec_scale_milli, jnp.int32),
        lel_scale_milli=jnp.int32(proto.lel_scale_milli),
        dyn=dyn_from_proto(proto),
        seed=jnp.int32(seed),
        faults=pad_faults(faults, max_faults),
        replica_tau=jnp.asarray(replica_tau, jnp.int32),
        repl_lag_us=jnp.int32(repl_lag_us),
        clock_skew_us=jnp.int32(clock_skew_us),
    )


def stack_worlds(worlds) -> WorldSpec:
    """[W_1..W_B] -> WorldSpec with a leading batch axis on every leaf."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *worlds)


@dataclasses.dataclass(frozen=True)
class SimConfig:
    """Static engine configuration (shapes + defaults).

    `proto` is excluded from the jit compile key (`compare=False`): the
    handlers read every protocol knob dynamically from `SimState.dyn`, so two
    configs differing only in `proto` share one compiled program. `proto` is
    only consulted host-side by `init_state` to populate the default knobs.
    """

    terminals: int
    max_ops: int
    num_ds: int
    bank_txns: int
    proto: ProtocolConfig = dataclasses.field(compare=False)
    # hot-record table slots (paper: bounded AVL+LRU cache). Sized to the hot
    # set, not the keyspace: preset throughputs are unchanged vs 8x this, and
    # the table is the largest leaf in the lockstep while-carry (vmapped
    # while_loops select the full state every iteration) — 8192 slots made
    # the vmap strategy 3x slower for no forecast-quality gain.
    hot_capacity: int = 1024
    warmup_us: int = 2_000_000
    horizon_us: int = 12_000_000
    max_events: int = 4_000_000
    alpha_milli: int = 800  # Eq.(4) EWMA α
    beta_milli: int = 875  # network-latency EWMA (the paper's monitor)
    drain: bool = True  # windowed conflict-free draining (False = seed path)
    # branchless omnibus step (lockstep lanes): every handler is a masked
    # delta in ONE straight-line pass — no lax.switch/cond, which under vmap
    # execute every branch and pay a full-state select per branch. Combined
    # with `drain` the lockstep path runs `_omni_window` (branchless windowed
    # drain). Bitwise-identical to the other step modes either way.
    lockstep: bool = False
    # per-bank-slot commit/abort/latency telemetry ([T, N] x3). Nothing in
    # summarize/figures reads it, and it would dominate the lockstep
    # while-carry — opt-in (tests use it to widen the bitwise fingerprint).
    track_slots: bool = False
    # static fault-schedule capacity F: `SimState.fault_*` are [F] leaves and
    # `_times_flat` grows an [F]-slot section. 0 = fault-free engine; the
    # Simulator derives it from `WorldSpec.faults.shape[-2]` per grid.
    max_faults: int = 0


class SimState(NamedTuple):
    now: jax.Array
    iters: jax.Array
    # terminal
    phase: jax.Array  # [T] i8
    cur: jax.Array  # [T] i32 bank slot
    txn_ctr: jax.Array  # [T] i32
    retries: jax.Array  # [T] i32
    blocked: jax.Array  # [T] i32
    retry_same: jax.Array  # [T] bool
    term_time: jax.Array  # [T] i32
    arrive: jax.Array  # [T] i32
    is_dist: jax.Array  # [T] bool
    cur_round: jax.Array  # [T] i8
    # ops
    op_state: jax.Array  # [T,K] i8
    op_key: jax.Array  # [T,K] i32
    op_write: jax.Array  # [T,K] bool
    op_ds: jax.Array  # [T,K] i8
    op_round: jax.Array  # [T,K] i8
    op_time: jax.Array  # [T,K] i32
    op_enq: jax.Array  # [T,K] i32
    # subtxns
    inv: jax.Array  # [T,D] bool
    sub_state: jax.Array  # [T,D] i8
    sub_time: jax.Array  # [T,D] i32
    sub_arrive: jax.Array  # [T,D] i32
    sub_lel: jax.Array  # [T,D] i32
    first_lock: jax.Array  # [T,D] i32
    rd_done: jax.Array  # [T,D] bool
    # TIGA: this round's dispatch arrived before its synchronized-clock
    # deadline at d (arrival + clock_skew_us <= dispatch + tiga_slack_us)
    sub_fast: jax.Array  # [T,D] bool
    # fault injection (F = cfg.max_faults; all-INF when fault-free)
    fault_ds: jax.Array  # [F] i32 — endpoint_a of row f (crash: the ds; MW = -1)
    fault_recover: jax.Array  # [F] i32 — end timestamp of row f
    fault_time: jax.Array  # [F] i32 — next event of row f (start, then end)
    fault_stage: jax.Array  # [F] i8 — 0 pending start / 1 pending end / 2 done
    fault_kind: jax.Array  # [F] i32 — KIND_CRASH / KIND_PARTITION / KIND_DEGRADE
    fault_peer: jax.Array  # [F] i32 — endpoint_b of row f
    fault_sev: jax.Array  # [F] i32 — DEGRADE severity, milli-scale
    ds_down: jax.Array  # [D] bool — currently crashed (node dead)
    # link state: a heal timestamp > now means the middleware<->d (resp.
    # mesh a<->b) link is severed until then; 0 = link up. tau_*_eff carry the
    # DEGRADE-scaled RTTs (== tau_true/tau_ds while no degrade is live).
    mw_heal: jax.Array  # [D] i32
    ds_heal: jax.Array  # [D,D] i32
    tau_mw_eff: jax.Array  # [D] i32
    tau_ds_eff: jax.Array  # [D,D] i32
    # geo-replica failover
    repl_tau: jax.Array  # [D] i32 — replica-link RTT (INF_US = no replica)
    repl_lag_us: jax.Array  # i32 — replication lag charged per stale read
    on_repl: jax.Array  # [T,D] bool — subtxn currently served by d's replica
    stale_reads: jax.Array  # i32 — read statements served from a replica
    failovers: jax.Array  # i32 — subtxns routed to a replica at admission
    max_stale_us: jax.Array  # i32 — worst staleness window of any stale read
    hb_time: jax.Array  # [D] i32 — next heartbeat probe (INF unless unreachable)
    hb_count: jax.Array  # [D] i32 — heartbeat probes fired while unreachable
    down_since: jax.Array  # [D] i32 — start of the current unreachability spell
    down_us: jax.Array  # [D] i32 — accumulated completed-unreachability time
    abort_cause: jax.Array  # [T] i32 — pending CAUSE_* of the in-flight txn
    ab_cause: jax.Array  # [N_ABORT_CAUSES] i32 — final-abort cause tally
    commits_fault: jax.Array  # i32 — commits while >=1 DS was unreachable
    # hot-record footprint: fixed-capacity hash table [C+1] (+1 = scratch row).
    # (2PL lock state needs no table: it is derived exactly from the op arrays,
    #  since every held/waited lock belongs to exactly one in-flight op.)
    hs: hs_mod.HashHotspot
    # network (dynamic)
    tau_true: jax.Array  # [D] i32
    tau_est: jax.Array  # [D] i32
    tau_ds: jax.Array  # [D,D] i32
    jitter_milli: jax.Array  # i32
    exec_scale_milli: jax.Array  # [D] i32 heterogeneous engine profile
    lel_scale_milli: jax.Array  # i32 (§IV-C forecast scaling)
    clock_skew_us: jax.Array  # i32 — synchronized-clock error bound (TIGA)
    # metrics
    commits: jax.Array
    aborts: jax.Array
    commits_dist: jax.Array
    aborts_dist: jax.Array
    lat_sum: jax.Array  # i32, milliseconds
    lat_sum_dist: jax.Array
    hist_all: jax.Array  # [HIST_BINS] i32
    hist_cen: jax.Array
    hist_dist: jax.Array
    lcs_sum: jax.Array  # i32, milliseconds
    lcs_cnt: jax.Array
    # WAN accounting: one-way middleware<->data-source message legs, charged
    # when the receiving event fires (dispatch arrival, round reply, prepare
    # command, vote, commit command, abort command, finish ack). Geo-agent
    # mesh messages, heartbeats and ScalarDB's per-op middleware RTTs are
    # excluded — the counter measures protocol commit-path rounds
    # (`drain_stats` reports wan_legs / 2 as `wan_rounds`).
    wan_legs: jax.Array  # i32
    # round-done transitions that committed at the data source without a DM
    # round: YUGA's async local commit, FASTC's co-coordinator commit, and
    # TIGA's deadline fast path (the single-round success rate)
    fast_commits: jax.Array  # i32
    noops: jax.Array  # i32 — must stay 0 (state-machine invariant)
    drained: jax.Array  # i32 — events applied via the windowed masked pass
    windows: jax.Array  # i32 — masked window applications (mean len = drained/windows)
    win_stops: jax.Array  # [N_STOP_REASONS] i32 — why each applied window ended
    fused: jax.Array  # i32 — fused plan+step lockstep iterations (`_omni_window`)
    # follow-up events admitted across the scheduling fence by the two-pass
    # window plan (each drained with the salt/timestamp it would have had
    # sequentially); the drain-telemetry twin of the sched_chain stop row
    chained: jax.Array  # i32
    slot_commits: jax.Array  # [T,N] i32
    slot_aborts: jax.Array  # [T,N] i32
    slot_lat: jax.Array  # [T,N] i32 (sum of commit latencies, ms)
    # dynamic protocol knobs (traced; see DynProto)
    dyn: DynProto


def init_state(
    cfg: SimConfig,
    tau_true_us,
    tau_ds_us,
    jitter_milli=0,
    exec_scale_milli=None,
    dyn: DynProto | None = None,
    lel_scale_milli=None,
    faults=None,
    replica_tau=None,
    repl_lag_us=0,
    clock_skew_us=0,
) -> SimState:
    T, K, D, N = (cfg.terminals, cfg.max_ops, cfg.num_ds, cfg.bank_txns)
    F = cfg.max_faults
    i32 = jnp.int32
    if exec_scale_milli is None:
        exec_scale_milli = jnp.full((D,), 1000, i32)
    if dyn is None:
        dyn = dyn_from_proto(cfg.proto)
    if lel_scale_milli is None:
        lel_scale_milli = cfg.proto.lel_scale_milli
    if replica_tau is None:
        replica_tau = jnp.full((D,), INF_US, i32)
    if faults is None:
        faults = pad_faults(None, F)
    faults = jnp.asarray(faults, i32)
    if faults.shape[-1] != FAULT_COLS:  # legacy [F,3] crash schedules
        faults = _widen_faults(faults.reshape(F, -1))
    faults = faults.reshape(F, FAULT_COLS)
    # failure detection lag: crash/partition events fire (and cascade) only
    # detect_delay_us after the scheduled start; degrades are physical link
    # changes and shift nothing. End timestamps are never shifted.
    f_start, f_kind = faults[:, 0], faults[:, 1]
    detect = jnp.where(f_kind == KIND_DEGRADE, 0, dyn.detect_delay_us)
    f_first = jnp.where(f_start < INF_US, f_start + detect, f_start)
    # ramp terminals in over 2ms to avoid a synchronized start
    start = (jnp.arange(T, dtype=i32) * 2000) // max(T, 1)
    return SimState(
        now=i32(0),
        iters=i32(0),
        phase=jnp.zeros((T,), jnp.int8),
        cur=jnp.zeros((T,), i32),
        txn_ctr=jnp.zeros((T,), i32),
        retries=jnp.zeros((T,), i32),
        blocked=jnp.zeros((T,), i32),
        retry_same=jnp.zeros((T,), bool),
        term_time=start,
        arrive=jnp.zeros((T,), i32),
        is_dist=jnp.zeros((T,), bool),
        cur_round=jnp.zeros((T,), jnp.int8),
        op_state=jnp.zeros((T, K), jnp.int8),
        op_key=jnp.zeros((T, K), i32),
        op_write=jnp.zeros((T, K), bool),
        op_ds=jnp.zeros((T, K), jnp.int8),
        op_round=jnp.zeros((T, K), jnp.int8),
        op_time=jnp.full((T, K), INF_US, i32),
        op_enq=jnp.zeros((T, K), i32),
        inv=jnp.zeros((T, D), bool),
        sub_state=jnp.zeros((T, D), jnp.int8),
        sub_time=jnp.full((T, D), INF_US, i32),
        sub_arrive=jnp.zeros((T, D), i32),
        sub_lel=jnp.zeros((T, D), i32),
        first_lock=jnp.full((T, D), INF_US, i32),
        rd_done=jnp.zeros((T, D), bool),
        sub_fast=jnp.zeros((T, D), bool),
        fault_ds=faults[:, 2],
        fault_recover=faults[:, 4],
        fault_time=f_first,
        fault_stage=jnp.zeros((F,), jnp.int8),
        fault_kind=f_kind,
        fault_peer=faults[:, 3],
        fault_sev=faults[:, 5],
        ds_down=jnp.zeros((D,), bool),
        mw_heal=jnp.zeros((D,), i32),
        ds_heal=jnp.zeros((D, D), i32),
        tau_mw_eff=jnp.asarray(tau_true_us, i32),
        tau_ds_eff=jnp.asarray(tau_ds_us, i32),
        repl_tau=jnp.asarray(replica_tau, i32),
        repl_lag_us=jnp.asarray(repl_lag_us, i32),
        on_repl=jnp.zeros((T, D), bool),
        stale_reads=i32(0),
        failovers=i32(0),
        max_stale_us=i32(0),
        hb_time=jnp.full((D,), INF_US, i32),
        hb_count=jnp.zeros((D,), i32),
        down_since=jnp.zeros((D,), i32),
        down_us=jnp.zeros((D,), i32),
        abort_cause=jnp.zeros((T,), i32),
        ab_cause=jnp.zeros((N_ABORT_CAUSES,), i32),
        commits_fault=i32(0),
        hs=hs_mod.hash_init(cfg.hot_capacity + 1),
        tau_true=jnp.asarray(tau_true_us, i32),
        tau_est=jnp.asarray(tau_true_us, i32),
        tau_ds=jnp.asarray(tau_ds_us, i32),
        jitter_milli=jnp.asarray(jitter_milli, i32),
        exec_scale_milli=jnp.asarray(exec_scale_milli, i32),
        lel_scale_milli=jnp.asarray(lel_scale_milli, i32),
        clock_skew_us=jnp.asarray(clock_skew_us, i32),
        commits=i32(0),
        aborts=i32(0),
        commits_dist=i32(0),
        aborts_dist=i32(0),
        lat_sum=i32(0),
        lat_sum_dist=i32(0),
        hist_all=jnp.zeros((HIST_BINS,), i32),
        hist_cen=jnp.zeros((HIST_BINS,), i32),
        hist_dist=jnp.zeros((HIST_BINS,), i32),
        lcs_sum=i32(0),
        lcs_cnt=i32(0),
        wan_legs=i32(0),
        fast_commits=i32(0),
        noops=i32(0),
        drained=i32(0),
        windows=i32(0),
        win_stops=jnp.zeros((N_STOP_REASONS,), i32),
        fused=i32(0),
        chained=i32(0),
        # untracked: a 1-slot stub (size-0 axes reject traced indices at
        # trace time); mode="drop" discards every slot>0 write either way
        slot_commits=jnp.zeros((T, N if cfg.track_slots else 1), i32),
        slot_aborts=jnp.zeros((T, N if cfg.track_slots else 1), i32),
        slot_lat=jnp.zeros((T, N if cfg.track_slots else 1), i32),
        dyn=dyn,
    )


def init_state_world(cfg: SimConfig, world: WorldSpec) -> SimState:
    """Initialize from a WorldSpec (vmap-compatible over a batch axis)."""
    return init_state(
        cfg,
        world.tau_true,
        world.tau_ds,
        world.jitter_milli,
        world.exec_scale_milli,
        dyn=world.dyn,
        lel_scale_milli=world.lel_scale_milli,
        faults=world.faults,
        replica_tau=world.replica_tau,
        repl_lag_us=world.repl_lag_us,
        clock_skew_us=world.clock_skew_us,
    )


# ---------------------------------------------------------------------------
# small helpers
# ---------------------------------------------------------------------------


def _delay_salted(jitter_milli: jax.Array, rtt: jax.Array, salt: jax.Array) -> jax.Array:
    """One-way delay = rtt/2 with deterministic ±jitter (elementwise over any
    broadcastable rtt/salt shapes — shared by the sequential handlers and the
    drain step so both paths use one formula)."""
    half = rtt // 2
    u = (_hash_u32(salt) % jnp.uint32(2001)).astype(jnp.int32) - 1000
    return half + (half * jitter_milli // 1000) * u // 1000


def _delay(s: SimState, rtt: jax.Array, salt: jax.Array) -> jax.Array:
    return _delay_salted(s.jitter_milli, rtt, salt)


def _salt(s: SimState, a: int) -> jax.Array:
    return s.iters * _SALT_MUL + jnp.int32(a)


def _at_ds(x: jax.Array, d: jax.Array) -> jax.Array:
    """`x` read at data source `d`: `jnp.take_along_axis(x, d, axis=-1)` for
    `x` `[..., D]` and `d` sharing its leading dimensions (`[T,D]` read by
    `[T,K]`), `x[d]` for a `[D]` vector and `d` of any shape.

    Precondition: every `d` lies in `[0, D)` (as `op_ds` does). It is read
    with D-1 selects over the static D, never a gather: under `vmap` a
    gather lowers to a batched TPU gather that walks its indices one by one
    (about 10 ns an element), where the selects fuse with their neighbours.
    """
    d = jnp.asarray(d)
    col = x.shape[:-1] + (1,) * (d.ndim - (x.ndim - 1))
    out = x[..., 0].reshape(col)
    for j in range(1, x.shape[-1]):
        out = jnp.where(d == j, x[..., j].reshape(col), out)
    return jnp.broadcast_to(out, jnp.broadcast_shapes(col, d.shape))


def _exec_us(cfg: SimConfig, s: SimState, d: jax.Array) -> jax.Array:
    """Per-op execution time at data source d (scalar or any index array);
    ScalarDB-style middleware CC pays an extra DM round trip per statement
    (at the effective — possibly degraded — link RTT)."""
    base = s.dyn.exec_us * _at_ds(s.exec_scale_milli, d) // 1000
    return base + jnp.where(s.dyn.middleware_cc, _at_ds(s.tau_mw_eff, d), 0)


def _mw_send(s: SimState, on_r: jax.Array, d: jax.Array, t0: jax.Array):
    """Effective (departure base, link RTT) for a middleware<->d message.

    Elementwise over any broadcastable shapes; every step mode and the window
    plan share this one formula. `on_r` marks a subtxn served by d's replica
    (replica links are never severed or degraded in this model). A message on
    a severed primary link departs — equivalently, is delivered — at the heal
    time and then resolves through the ordinary timeout/retry machinery. In
    clean states this is exactly (t0, tau_true[d])."""
    tau = jnp.where(on_r, s.repl_tau[d], s.tau_mw_eff[d])
    base = jnp.where(~on_r & (s.mw_heal[d] > t0), s.mw_heal[d], t0)
    return base, tau


def _mw_link(s: SimState, on_r: jax.Array, d: jax.Array, t0: jax.Array):
    """`_mw_send`, statically reduced to the pristine (t0, tau_true[d]) when
    the config carries no fault schedule — fault-free configs compile the
    exact link-state-free program."""
    if s.fault_time.shape[0]:
        return _mw_send(s, on_r, d, t0)
    return t0, s.tau_true[d]


def _ds_send(s: SimState, a: jax.Array, b: jax.Array, t0: jax.Array):
    """Effective (departure base, link RTT) for a geo-agent a->b mesh message.

    A severed mesh link holds the message until its heal time (`ds_heal`
    self-expires: stale heal stamps lie in the past and the max is a no-op);
    DEGRADE scales the RTT via `tau_ds_eff`."""
    return jnp.maximum(t0, s.ds_heal[a, b]), s.tau_ds_eff[a, b]


def _unreachable(s: SimState) -> jax.Array:
    """[D] bool — data source crashed OR partitioned from the middleware.

    The reachability mask: heartbeat probes, the availability charge and
    admission fail-fast/failover all gate on this, not on liveness alone."""
    return s.ds_down | (s.mw_heal > s.now)


def _round_done_transition(
    dyn: DynProto, is_final, centralized, reply_t, prep_t, local_t, fast=False
):
    """Subtxn state/time after its round's last statement finishes.

    Elementwise over any broadcastable shapes — the sequential round_done
    (scalars) and the drain step ([T,D]) share this selection, so the
    drained path cannot drift from the single-event semantics.

    `fast` is TIGA's per-event deadline flag (`_tiga_fast`). FASTC's
    `co_commit` knob takes the same exit unconditionally: the geo-agent
    co-coordinator logs through the LAN round (`prep_t`) and commits locally
    (SUB_LOCAL_COMMIT) instead of reporting for a DM commit-log round.
    """
    dec = dyn.prepare == PREPARE_DECENTRAL
    go_local = dec & dyn.async_local_commit & is_final & centralized
    go_fast = dec & is_final & ~centralized & (dyn.co_commit | fast)
    go_prep = dec & is_final & ~centralized & ~go_fast
    new_state = jnp.where(
        go_local | go_fast,
        SUB_LOCAL_COMMIT,
        jnp.where(go_prep, SUB_PREPARING, SUB_ROUND_REPLY),
    )
    new_time = jnp.where(
        go_local, local_t, jnp.where(go_fast | go_prep, prep_t, reply_t)
    )
    return new_state, new_time


def _lock_wait_deadline(dyn: DynProto, now) -> jax.Array:
    """When a statement that failed its lock acquisition gives up waiting.

    The ordinary 2PL path parks it in the wait queue for `lock_timeout_us`;
    under OPTA (`opt_abort`) the conflict aborts immediately — the OP_WAIT
    event is scheduled at `now` itself and the existing timeout/peer-abort
    machinery fires it as the very next event of that operation.
    """
    return now + jnp.where(dyn.opt_abort, 0, dyn.lock_timeout_us)


def _tiga_arrival(dyn: DynProto, clock_skew_us, now, arrival):
    """(first-statement time, deadline flag) for a sub dispatch firing at `now`.

    TIGA stamps the dispatch with the synchronized-clock deadline
    `now + tiga_slack_us`; a statement that arrives "in the future" under the
    clock-skew bound buffers and executes exactly at the deadline, otherwise
    (or when TIGA is off) it executes at its network arrival as usual.
    """
    deadline = now + dyn.tiga_slack_us
    fast = (dyn.tiga_slack_us > 0) & (arrival + clock_skew_us <= deadline)
    return jnp.where(fast, deadline, arrival), fast


def _tiga_fast(dyn: DynProto, single_round, inv_row, fast_row):
    """TIGA's round-done fast flag: this txn runs a single statement round and
    every invited sub's dispatch beat its deadline (`sub_fast`), so each
    participant may commit locally in one WAN round. Reduces the trailing [D]
    axis; with STAGGER_NONE every round-0 dispatch shares one timestamp and
    sub slots precede op slots at equal times, so all `sub_fast` flags are
    written before any participant's round-done consults them.
    """
    all_fast = jnp.all(~inv_row | fast_row, axis=-1)
    return (dyn.tiga_slack_us > 0) & single_round & all_fast


def _u01(salt: jax.Array) -> jax.Array:
    return _hash_u32(salt).astype(jnp.float32) / jnp.float32(2**32)


def _hist_bin(lat_us: jax.Array) -> jax.Array:
    l2 = jnp.log2(jnp.maximum(lat_us.astype(jnp.float32), 1.0) / _HIST_BASE_US)
    return jnp.clip((l2 * 8.0).astype(jnp.int32), 0, HIST_BINS - 1)


def _measuring(cfg: SimConfig, s: SimState) -> jax.Array:
    return s.now >= jnp.int32(cfg.warmup_us)


def _times_flat(s: SimState) -> jax.Array:
    """Concatenated [T + T*D + T*K + F + D] event-time view
    (term | sub | op | fault | heartbeat).

    The fault and heartbeat tails exist only when the config carries a
    fault schedule (``max_faults > 0``); a fault-free config compiles the
    exact tail-free view, and an all-INF schedule never wins the
    first-occurrence argmin — either way every step mode stays bitwise-
    identical to the tail-free engine."""
    parts = [s.term_time, s.sub_time.reshape(-1), s.op_time.reshape(-1)]
    if s.fault_time.shape[0]:
        parts += [s.fault_time, s.hb_time]
    return jnp.concatenate(parts)
