"""Latency-aware scheduling (the paper's §IV-B and §IV-C scheduling math).

Pure JAX, fully vectorized; used by
  * the discrete-event engine (repro.core.engine),
  * the geo-serving engine (repro.serving.engine),
  * the Pallas `geo_schedule` kernel's reference oracle.

Formulas (all times in µs, int32):

  Eq.(1)  LCS(T_ij) = t_last_release - t_first_acquire
  Eq.(3)  t_start(T_ij) = max_s tau_is - tau_ij                     (low contention)
  Eq.(8)  t_start(T_ij) = max_s (tau_is + LEL_is) - (tau_ij + LEL_ij)
  Eq.(9)  Pr_abort(T_i) = 1 - prod_r (c_cnt_r / t_cnt_r) ** max(a_cnt_r - 1, 0)

The offsets returned are relative to the transaction's scheduling instant; the
slowest participant always gets offset 0 (never postponed), so the end-to-end
latency constraint of Eq.(2)/Eq.(7) holds by construction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.ref.netmodel import INF_US


def stagger_offsets(
    tau: jax.Array,
    involved: jax.Array,
    lel: jax.Array | None = None,
    scale_milli: jax.Array | int = 1000,
) -> jax.Array:
    """Per-participant dispatch offsets, Eq.(3) / Eq.(8).

    tau:      [..., D] int32 estimated RTT DM<->data-source (µs).
    involved: [..., D] bool, which data sources the transaction touches.
    lel:      [..., D] int32 forecasted local execution latency (µs) or None
              (None => Eq.(3); present => Eq.(8)).
    scale_milli: scale-down factor (in 1/1000) applied to the *forecast* part,
              the paper's §IV-C mitigation for over-prediction ("we can scale
              down the predicted latency before incorporating it").

    Returns offsets [..., D] int32, 0 for the slowest participant and for
    non-involved entries.
    """
    tau = tau.astype(jnp.int32)
    if lel is None:
        cost = tau
    else:
        if isinstance(scale_milli, int) and scale_milli == 1000:
            # identity scale — every in-repo caller pre-scales the forecast
            # upstream (engine `_stagger`). Skipping the *1000//1000 round
            # trip avoids the int32 product wrapping for forecasts above
            # ~2.1e6 µs (the upstream Eq.4 clip allows up to 1e7).
            scaled = lel.astype(jnp.int32)
        else:
            # int32 on purpose: x64 is disabled engine-wide, so an int64
            # request would silently truncate to int32 anyway (and spam
            # truncation UserWarnings). Caveat: the product wraps for
            # lel * scale_milli >= 2**31 — keep forecasts scaled down
            # before calling with a non-identity scale.
            scaled = (
                lel.astype(jnp.int32) * jnp.asarray(scale_milli, jnp.int32) // 1000
            )
        cost = tau + scaled
    masked = jnp.where(involved, cost, jnp.int32(-1))
    cmax = jnp.max(masked, axis=-1, keepdims=True)
    off = jnp.where(involved, cmax - cost, 0)
    return jnp.maximum(off, 0).astype(jnp.int32)


def lock_contention_span(
    tau: jax.Array, involved: jax.Array, offsets: jax.Array
) -> jax.Array:
    """Analytic LCS per participant under the no-data-conflict model of §IV-B.

    With offsets o_j: first acquire = o_j + tau_j/2; last release =
    max_s(o_s + tau_s) + tau_j/2 (commit message arrival, one decentralized-
    prepare round). LCS_j = max_s(o_s + tau_s) - o_j.
    """
    total = jnp.where(involved, offsets + tau, jnp.int32(-1))
    tmax = jnp.max(total, axis=-1, keepdims=True)
    lcs = jnp.where(involved, tmax - offsets, 0)
    return lcs.astype(jnp.int32)


def success_log_prob(
    c_cnt: jax.Array, t_cnt: jax.Array, a_cnt: jax.Array
) -> jax.Array:
    """log of per-record lock-acquisition success probability, Eq.(9) inner term.

    (c/t) ** max(a-1, 0), computed in log space for numerical stability when a
    transaction touches many hot records. Laplace smoothing ((c+1)/(t+1))
    bootstraps cold records to probability 1 instead of 0.
    Inputs are per-record stats gathered for the records of one transaction.
    """
    t = jnp.maximum(t_cnt.astype(jnp.float32), 0.0) + 1.0
    c = jnp.clip(c_cnt.astype(jnp.float32) + 1.0, 0.0, t)
    ratio = jnp.clip(c / t, 1e-6, 1.0)
    expo = jnp.maximum(a_cnt.astype(jnp.float32) - 1.0, 0.0)
    return expo * jnp.log(ratio)


def abort_probability(
    c_cnt: jax.Array, t_cnt: jax.Array, a_cnt: jax.Array, valid: jax.Array
) -> jax.Array:
    """Pr_abort(T_i) of Eq.(9) for a batch of transactions.

    c_cnt/t_cnt/a_cnt: [..., K] per-record stats for the K records the txn
    touches; valid: [..., K] mask for real records (txns shorter than K).
    Returns [...] float32 in [0, 1].
    """
    lp = jnp.where(valid, success_log_prob(c_cnt, t_cnt, a_cnt), 0.0)
    return 1.0 - jnp.exp(jnp.sum(lp, axis=-1))


def admission_decision(
    p_abort: jax.Array, u01: jax.Array, blocked_cnt: jax.Array, max_blocked: int
) -> tuple[jax.Array, jax.Array]:
    """Late transaction scheduling (§IV-C, Algorithm 2 lines 15-18).

    Blocks a transaction with probability p_abort; transactions blocked more
    than `max_blocked` times are aborted instead of blocked again.

    Returns (block, abort) boolean arrays.
    """
    want_block = u01 < p_abort
    abort = want_block & (blocked_cnt >= max_blocked)
    block = want_block & ~abort
    return block, abort


def plan_dispatch(
    tau: jax.Array,
    lel: jax.Array,
    inv: jax.Array,
    c_cnt: jax.Array,
    t_cnt: jax.Array,
    a_cnt: jax.Array,
    valid: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Shared batched scheduling entry: Eq.(8) offsets + Eq.(9) p_abort.

    The single scheduling surface used by the discrete-event engine's sweeps,
    the geo-serving router's admission path and the Pallas `geo_schedule`
    kernel's oracle — one place defines the DM's dispatch math.

    tau/lel: [..., D] int32 µs; inv: [..., D] bool;
    c/t/a_cnt: [..., K] int32 per-record stats; valid: [..., K] bool.
    Returns (offsets [..., D] int32, p_abort [...] float32).
    """
    off = stagger_offsets(tau, inv, lel)
    p_abort = abort_probability(c_cnt, t_cnt, a_cnt, valid)
    return off, p_abort


def commit_decision(
    prepare: jax.Array,
    all_at_dm: jax.Array,
    all_voted: jax.Array,
    centralized: jax.Array,
    prepare_none: int,
    prepare_coord: int,
    prepare_decentral: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The DM's commit-phase decision, elementwise over any batch shape.

    Single source for both the engine's sequential `_dm_progress` and its
    omnibus masked step (the two paths must agree bitwise):
      do_commit  — broadcast commit now (one-phase for centralized txns; the
                   no-prepare preset commits as soon as every sub reported);
      do_prepare — coordinated 2PC prepare broadcast;
      do_log     — all votes in: flush the DM commit log.
    Priority (commit > prepare > log) is applied by the caller.
    """
    do_commit = jnp.where(prepare == prepare_none, all_at_dm, centralized & all_at_dm)
    do_prepare = (prepare == prepare_coord) & all_at_dm & ~centralized
    do_log = (
        ((prepare == prepare_coord) | (prepare == prepare_decentral))
        & all_voted
        & ~centralized
    )
    return do_commit, do_prepare, do_log


def round_barrier_next_dispatch(
    now: jax.Array, tau: jax.Array, involved_next: jax.Array, lel: jax.Array | None
) -> jax.Array:
    """Dispatch times for the next interactive round (paper: "for transactions
    with multiple rounds of interactions, the optimal start time point is
    calculated for each round")."""
    off = stagger_offsets(tau, involved_next, lel)
    return jnp.where(involved_next, now + off, INF_US)
