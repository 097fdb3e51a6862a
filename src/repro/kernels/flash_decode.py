"""flash_decode — single-token GQA attention against a long KV cache.

The decode-shape cells (decode_32k, long_500k) shard the KV cache's
sequence axis; on-device each shard runs exactly this kernel: stream KV
blocks HBM→VMEM, keep the (G, D) query tile and running (m, l, acc)
statistics resident, mask by the current cache length, and emit once.
Valid-length masking uses a scalar-prefetched per-batch ``cur_index`` —
the same scalar-prefetch mechanism as the LIRS batch_gather kernel.

Grid: (B, K_heads, T/block_k); the KV-block dimension is sequential.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _decode_kernel(cur_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, block_k, nk, scale):
    b = pl.program_id(0)
    tj = pl.program_id(2)

    @pl.when(tj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cur = cur_ref[b]  # current cache position (attend to pos <= cur)
    run = tj * block_k <= cur

    @pl.when(run)
    def _body():
        q = q_ref[0, 0]    # (G, D)
        k = k_ref[0, :, 0]  # (block_k, D)
        v = v_ref[0, :, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (G, block_k)
        pos = tj * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos <= cur, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv
        m_ref[...] = m_new

    @pl.when(tj == nk - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cur_index: jax.Array,
    *,
    block_k: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """q: (B,H,D); caches: (B,T,K,D); cur_index: (B,) int32.
    Attends to cache positions <= cur_index.  Returns (B,H,D)."""
    b, h, d = q.shape
    t, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh
    bk = min(block_k, t)
    assert t % bk == 0, (t, bk)
    nk = t // bk

    qg = q.reshape(b, kh, g, d)
    kernel = functools.partial(
        _decode_kernel, block_k=bk, nk=nk, scale=1.0 / math.sqrt(d)
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kh, nk),
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda bi, hi, ti, cur: (bi, hi, 0, 0)),
                pl.BlockSpec((1, bk, 1, d), lambda bi, hi, ti, cur: (bi, ti, hi, 0)),
                pl.BlockSpec((1, bk, 1, d), lambda bi, hi, ti, cur: (bi, ti, hi, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d), lambda bi, hi, ti, cur: (bi, hi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g,), jnp.float32),
                pltpu.VMEM((g,), jnp.float32),
                pltpu.VMEM((g, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(cur_index.astype(jnp.int32), qg, k_cache, v_cache)
    return out.reshape(b, h, d)
