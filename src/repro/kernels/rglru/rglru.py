"""RG-LRU linear-recurrence TPU kernel (Pallas).

h_t = a_t * h_{t-1} + b_t, elementwise over the channel dim. The sequence is
tiled into chunks; the grid's chunk dimension is sequential ("arbitrary") and
the carry h lives in VMEM scratch, so the recurrence streams [chunk, bE]
slabs from HBM exactly once — the kernel is purely bandwidth-bound, matching
the VPU's elementwise throughput. Within a chunk the scan is a fori_loop over
rows (the TPU-native replacement for the GPU's warp-parallel scan: the VPU
processes the full 128-lane channel block per step, so sequential-in-time,
parallel-in-channel is the natural mapping — see DESIGN.md hardware notes).

Grid: (B, E/bE, S/cs) — chunk dim sequential.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(la_ref, b_ref, o_ref, h_ref, *, cs: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    la = la_ref[0].astype(jnp.float32)  # [cs, bE]
    b = b_ref[0].astype(jnp.float32)

    def step(t, h):
        h = jnp.exp(la[t]) * h + b[t]
        o_ref[pl.ds(0, 1), pl.ds(t, 1), :] = h[None, None].astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, cs, step, h_ref[0])
    h_ref[0] = h


@functools.partial(jax.jit, static_argnames=("chunk", "be", "interpret"))
def rglru_scan(
    log_a: jax.Array,
    b: jax.Array,
    *,
    chunk: int = 256,
    be: int = 512,
    interpret: bool = True,
) -> jax.Array:
    """log_a/b: [B,S,E] -> h [B,S,E]."""
    B, S, E = log_a.shape
    cs = min(chunk, S)
    while S % cs:
        cs //= 2
    bE = min(be, E)
    while E % bE:
        bE //= 2
    nc, ne = S // cs, E // bE

    return pl.pallas_call(
        functools.partial(_kernel, cs=cs),
        grid=(B, ne, nc),
        in_specs=[
            pl.BlockSpec((1, cs, bE), lambda bi, ei, ci: (bi, ci, ei)),
            pl.BlockSpec((1, cs, bE), lambda bi, ei, ci: (bi, ci, ei)),
        ],
        out_specs=pl.BlockSpec((1, cs, bE), lambda bi, ei, ci: (bi, ci, ei)),
        out_shape=jax.ShapeDtypeStruct((B, S, E), b.dtype),
        scratch_shapes=[pltpu.VMEM((1, bE), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
    )(log_a, b)
