"""Chunkwise mLSTM TPU kernel (Pallas): xLSTM matrix-memory attention with
gate-weighted online accumulation.

Identical tiling to flash attention — grid (B*H, S/bq, S/bk) with a
sequential kv dimension and VMEM (acc, sum, m) scratch — but the weights are
the xLSTM decay matrix D_ij = exp(F_i - F_j + logi_j - m_i) instead of
softmax, and the normalizer is max(|row sum|, exp(-m_i)) (the row sum can be
negative, so it is accumulated signed, separately from the stabilizer max).

The forget-gate cumsum F is precomputed in ops.py, so each tile only needs
O(bq + bk) gate values (two row vectors), not an O(S^2) decay matrix.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    q_ref,
    k_ref,
    v_ref,
    fq_ref,
    fk_ref,
    li_ref,
    o_ref,
    acc_ref,
    s_ref,
    m_ref,
    *,
    scale: float,
    bq: int,
    bk: int,
    nk: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        s_ref[...] = jnp.zeros_like(s_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)

    q0 = qi * bq
    k0 = ki * bk

    @pl.when(k0 <= q0 + bq - 1)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [bq, dh]
        k = k_ref[0].astype(jnp.float32)  # [bk, dh]
        v = v_ref[0].astype(jnp.float32)  # [bk, dh]
        Fq = fq_ref[0].astype(jnp.float32)  # [bq]
        Fk = fk_ref[0].astype(jnp.float32)  # [bk]
        li = li_ref[0].astype(jnp.float32)  # [bk]

        Dt = Fq[:, None] - Fk[None, :] + li[None, :]  # [bq, bk]
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        Dt = jnp.where(kpos <= qpos, Dt, NEG_INF)

        m_prev = m_ref[...]  # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(Dt, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        D = jnp.exp(Dt - m_new)
        s = (
            jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            * scale
            * D
        )
        s_ref[...] = s_ref[...] * alpha + jnp.sum(s, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            s, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        norm = jnp.maximum(jnp.abs(s_ref[...]), jnp.exp(-m_ref[...]))
        o_ref[0] = (acc_ref[...] / jnp.maximum(norm, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "interpret"))
def mlstm_chunk(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    logi: jax.Array,
    logf: jax.Array,
    *,
    bq: int = 256,
    bk: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """q/k/v: [B,H,S,dh]; logi/logf: [B,H,S] -> h [B,H,S,dh]."""
    B, H, S, dh = q.shape
    bq = min(bq, S)
    bk = min(bk, S)
    while S % bq:
        bq //= 2
    while S % bk:
        bk //= 2
    nq, nk = S // bq, S // bk
    scale = 1.0 / math.sqrt(dh)

    BH = B * H
    qr = q.reshape(BH, S, dh)
    kr = k.reshape(BH, S, dh)
    vr = v.reshape(BH, S, dh)
    F = jnp.cumsum(logf.astype(jnp.float32), axis=-1).reshape(BH, S)
    li = logi.astype(jnp.float32).reshape(BH, S)

    q_map = lambda bh, qi, ki: (bh, qi, 0)
    kv_map = lambda bh, qi, ki: (bh, ki, 0)
    fq_map = lambda bh, qi, ki: (bh, qi)
    fk_map = lambda bh, qi, ki: (bh, ki)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, bq=bq, bk=bk, nk=nk),
        grid=(BH, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), q_map),
            pl.BlockSpec((1, bk, dh), kv_map),
            pl.BlockSpec((1, bk, dh), kv_map),
            pl.BlockSpec((1, bq), fq_map),
            pl.BlockSpec((1, bk), fk_map),
            pl.BlockSpec((1, bk), fk_map),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), q_map),
        out_shape=jax.ShapeDtypeStruct((BH, S, dh), v.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(qr, kr, vr, F, F, li)  # F twice: q-row view and k-row view
    return out.reshape(B, H, S, dh)
