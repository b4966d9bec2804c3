"""Flash attention TPU kernel (Pallas): causal / sliding-window / chunk-local
GQA with online softmax.

Grid: (B*H, S/bq, S/bk) — the kv dimension is sequential ("arbitrary"), the
others parallel. Blocks live in VMEM; the running (acc, m, l) state sits in
VMEM scratch that persists across the kv grid dimension. K/V blocks are
indexed through the query head -> kv head map (GQA) so kv tiles are fetched
once per group, straight from HBM into VMEM. MXU alignment: block sizes are
multiples of 128 on the contracting/lane dims (ops.py pads head_dim).
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
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    scale: float,
    bq: int,
    bk: int,
    nk: int,
    causal: bool,
    window: int,
    chunk_local: bool,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q0 = qi * bq
    k0 = ki * bk
    # block-level relevance: skip fully-masked tiles
    needed = True
    if causal:
        needed = k0 <= q0 + bq - 1
    if window and not chunk_local:
        needed = jnp.logical_and(needed, k0 + bk - 1 > q0 - window)
    if window and chunk_local:
        needed = jnp.logical_and(
            needed, (k0 + bk - 1) // window >= q0 // window
        )
        needed = jnp.logical_and(needed, k0 // window <= (q0 + bq - 1) // window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [bq, dh]
        k = k_ref[0].astype(jnp.float32)  # [bk, dh]
        v = v_ref[0].astype(jnp.float32)  # [bk, dh]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= kpos <= qpos
        if window:
            if chunk_local:
                mask &= (kpos // window) == (qpos // window)
            else:
                mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal",
        "window",
        "chunk_local",
        "bq",
        "bk",
        "interpret",
    ),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    chunk_local: bool = False,
    bq: int = 256,
    bk: int = 256,
    interpret: bool = True,
) -> jax.Array:
    """q: [B,H,S,dh], k/v: [B,KV,S,dh] (dh multiple of 128; see ops.py)."""
    B, H, S, dh = q.shape
    KV = k.shape[1]
    G = H // KV
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0
    nq, nk = S // bq, S // bk
    scale = 1.0 / math.sqrt(dh)

    qr = q.reshape(B * H, S, dh)
    kr = k.reshape(B * KV, S, dh)
    vr = v.reshape(B * KV, S, dh)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        b, h = bh // H, bh % H
        return (b * KV + h // G, ki, 0)

    kernel = functools.partial(
        _kernel,
        scale=scale,
        bq=bq,
        bk=bk,
        nk=nk,
        causal=causal,
        window=window,
        chunk_local=chunk_local,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, dh), q_map),
            pl.BlockSpec((1, bk, dh), kv_map),
            pl.BlockSpec((1, bk, dh), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, S, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, dh), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(qr, kr, vr)
    return out.reshape(B, H, S, dh)
