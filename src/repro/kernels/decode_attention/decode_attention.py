"""Flash-decode TPU kernel (Pallas): single-query attention over a KV cache
with a split-KV grid.

The decode_32k / long_500k hot loop is HBM-bandwidth-bound on the KV read;
this kernel streams KV slabs (grid dim 2, sequential) through VMEM while the
online-softmax state (acc, m, l) persists in VMEM scratch — one pass over the
cache, no score materialization. The group dim of GQA is carried inside the
block (all G query heads of a kv head share each fetched KV slab — the
bandwidth-optimal layout).

Grid: (B, KV, Sc/bk). Blocks: q [1,1,G,dh] (tiny), k/v [1,bk,1,dh],
valid [1,bk].
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, acc_ref, m_ref, l_ref, *, scale, nk):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # [G, dh]
    k = k_ref[0, :, 0].astype(jnp.float32)  # [bk, dh]
    v = v_ref[0, :, 0].astype(jnp.float32)  # [bk, dh]
    ok = valid_ref[0] != 0  # [bk]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [G, bk]
    s = jnp.where(ok[None, :], s, NEG_INF)

    m_prev = m_ref[...]  # [G,1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    valid: jax.Array,
    *,
    bk: int = 512,
    interpret: bool = True,
) -> jax.Array:
    """q: [B,H,dh]; caches [B,Sc,KV,dh]; valid: [B,Sc] int8 -> [B,H,dh]."""
    B, H, dh = q.shape
    Sc, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    bk = min(bk, Sc)
    while Sc % bk:
        bk //= 2
    nk = Sc // bk
    scale = 1.0 / math.sqrt(dh)

    qr = q.reshape(B, KV, G, dh)
    vr8 = valid.astype(jnp.int8)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, nk=nk),
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec((1, 1, G, dh), lambda b, n, ki: (b, n, 0, 0)),
            pl.BlockSpec((1, bk, 1, dh), lambda b, n, ki: (b, ki, n, 0)),
            pl.BlockSpec((1, bk, 1, dh), lambda b, n, ki: (b, ki, n, 0)),
            pl.BlockSpec((1, bk), lambda b, n, ki: (b, ki)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, dh), lambda b, n, ki: (b, n, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, dh), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(qr, k_cache, v_cache, vr8)
    return out.reshape(B, H, dh)
