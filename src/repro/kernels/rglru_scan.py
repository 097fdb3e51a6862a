"""rglru_scan — time-blocked linear recurrence h_t = a_t·h_{t-1} + x_t.

Grid: (B/block_b, W/block_w, T/block_t); the time dimension is sequential
("arbitrary") and the hidden state h lives in VMEM scratch across time
blocks.  Within a block the recurrence runs as an unrolled/fori loop over
VMEM rows — elementwise VPU work; the win over a naive lax.scan is the
blocking: one HBM round-trip per (block_t × width) tile instead of per
step.  Used by the recurrentgemma (RG-LRU) path on real TPUs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _rglru_kernel(a_ref, x_ref, o_ref, h_ref, *, block_t):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        h = a_ref[:, t, :] * h + x_ref[:, t, :]
        o_ref[:, t, :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_t, step, h_ref[...])
    h_ref[...] = h


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_t", "block_w", "interpret")
)
def rglru_scan(
    a: jax.Array,
    x: jax.Array,
    *,
    block_b: int = 8,
    block_t: int = 128,
    block_w: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """a, x: (B, T, W) — returns h: (B, T, W) in f32."""
    b, t, w = a.shape
    bb = min(block_b, b)
    bt = min(block_t, t)
    bw = min(block_w, w)
    assert b % bb == 0 and t % bt == 0 and w % bw == 0, (a.shape, (bb, bt, bw))

    kernel = functools.partial(_rglru_kernel, block_t=bt)
    return pl.pallas_call(
        kernel,
        grid=(b // bb, w // bw, t // bt),
        in_specs=[
            pl.BlockSpec((bb, bt, bw), lambda bi, wi, ti: (bi, ti, wi)),
            pl.BlockSpec((bb, bt, bw), lambda bi, wi, ti: (bi, ti, wi)),
        ],
        out_specs=pl.BlockSpec((bb, bt, bw), lambda bi, wi, ti: (bi, ti, wi)),
        out_shape=jax.ShapeDtypeStruct((b, t, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bb, bw), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a.astype(jnp.float32), x.astype(jnp.float32))
