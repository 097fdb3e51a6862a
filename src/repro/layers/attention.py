"""Grouped-query attention: full-causal, sliding-window (chunked,
sub-quadratic), bidirectional, cross, and single-token decode paths.

Sharding notes (GSPMD/TP over the ``model`` axis):
  * train/prefill paths EXPAND the KV heads to the full head count before
    the score einsum, so every einsum carries a clean per-head sharding
    (Megatron-style TP; K·G reshapes of a sharded head axis confuse GSPMD).
    The repeat of a replicated KV tensor is comm-free under SPMD.
  * decode keeps GROUPED KV (the cache stays at num_kv_heads) and shards
    the cache's sequence axis over ``model`` (flash-decode style): score
    and output contractions reduce over the sharded axis, so XLA inserts
    only small psum combines.
Shapes: q: (B,S,H,D); k/v: (B,T,K,D); H = K·G.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.layers.common import dense_init

NEG_INF = -1e30


def init_attn(rng, d_model: int, num_heads: int, num_kv_heads: int, head_dim: int, dtype,
              qkv_bias: bool = False):
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], (d_model, num_heads, head_dim), dtype),
        "wk": dense_init(ks[1], (d_model, num_kv_heads, head_dim), dtype),
        "wv": dense_init(ks[2], (d_model, num_kv_heads, head_dim), dtype),
        "wo": dense_init(ks[3], (num_heads, head_dim, d_model), dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((num_heads, head_dim), dtype)
        p["bk"] = jnp.zeros((num_kv_heads, head_dim), dtype)
        p["bv"] = jnp.zeros((num_kv_heads, head_dim), dtype)
    return p


def qkv(params, x, dtype):
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dtype))
    if "bq" in params:  # present only where the config asks for a bias
        q = q + params["bq"].astype(dtype)
        k = k + params["bk"].astype(dtype)
        v = v + params["bv"].astype(dtype)
    return q, k, v


def out_proj(params, o, dtype, pet=None):
    # pet=bf16 halves the TP partial-sum all-reduce (see ModelConfig)
    return jnp.einsum(
        "bshk,hkd->bsd", o, params["wo"].astype(dtype), preferred_element_type=pet
    ).astype(dtype)


def _expand_kv(q, k, v):
    g = q.shape[2] // k.shape[2]
    if g > 1:
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    return k, v


def sdpa(q, k, v, mask=None):
    """Expanded-head attention. mask broadcastable to (B,H,S,T), True=keep."""
    b, s, h, d = q.shape
    k, v = _expand_kv(q, k, v)
    scores = jnp.einsum("bshd,bthd->bhst", q, k).astype(jnp.float32) / math.sqrt(d)
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bthd->bshd", w, v)


def causal_mask(s: int, t: Optional[int] = None, offset: int = 0):
    t = t if t is not None else s
    qpos = offset + jnp.arange(s)[:, None]
    kpos = jnp.arange(t)[None, :]
    return (kpos <= qpos)[None, None]  # (1,1,S,T)


def full_attention(q, k, v, causal: bool = True):
    mask = causal_mask(q.shape[1], k.shape[1]) if causal else None
    return sdpa(q, k, v, mask=mask)


def blocked_attention(q, k, v, block: int = 1024):
    """Flash-style causal attention: scan over query blocks, online softmax
    over key blocks.  Never materializes the full (S,T) score matrix, in
    the forward pass or for the backward one — the memory-roofline
    optimization path (§Perf)."""
    b, s, h, d = q.shape
    k, v = _expand_kv(q, k, v)
    if s % block != 0 or s <= block:
        return full_attention(q, k, v, causal=True)
    n = s // block
    qb = jnp.moveaxis(q.reshape(b, n, block, h, d), 1, 0)  # (n,b,block,h,d)
    scale = 1.0 / math.sqrt(d)

    def per_qblock(carry, xs):
        qi, idx = xs

        # recomputed in the backward pass: the scan then keeps only its
        # carries, not every block's scores (O(S·d) residuals, not O(S²))
        @jax.checkpoint
        def inner(icarry, jxs):
            m, l, acc = icarry
            kj, vj, jdx = jxs
            sc = jnp.einsum("bshd,bthd->bhst", qi, kj).astype(jnp.float32) * scale
            qpos = idx * block + jnp.arange(block)[:, None]
            kpos = jdx * block + jnp.arange(block)[None, :]
            keep = (kpos <= qpos)[None, None]
            sc = jnp.where(keep, sc, NEG_INF)
            m_new = jnp.maximum(m, sc.max(-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhst,bthd->bhsd", p.astype(qi.dtype), vj
            ).astype(jnp.float32)
            return (m_new, l_new, acc), None

        kb = jnp.moveaxis(k.reshape(b, n, block, h, d), 1, 0)
        vb = jnp.moveaxis(v.reshape(b, n, block, h, d), 1, 0)
        m0 = jnp.full((b, h, block), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, h, block), jnp.float32)
        a0 = jnp.zeros((b, h, block, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(inner, (m0, l0, a0), (kb, vb, jnp.arange(n)))
        o = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(qi.dtype)
        return carry, jnp.moveaxis(o, 2, 1)  # (b,block,h,d)

    _, ob = jax.lax.scan(per_qblock, 0, (qb, jnp.arange(n)))
    return jnp.moveaxis(ob, 0, 1).reshape(b, s, h, d)


def local_attention(q, k, v, window: int):
    """Chunked sliding-window attention: O(S·w) instead of O(S²)."""
    b, s, h, d = q.shape
    k, v = _expand_kv(q, k, v)
    if s <= window:
        mask = causal_mask(s) & (
            jnp.arange(s)[:, None] - jnp.arange(s)[None, :] < window
        )[None, None]
        return sdpa(q, k, v, mask=mask)
    c = window
    assert s % c == 0, f"seq {s} must be a multiple of window {c}"
    n = s // c
    qc = q.reshape(b, n, c, h, d)
    kc = k.reshape(b, n, c, h, d)
    vc = v.reshape(b, n, c, h, d)
    kprev = jnp.pad(kc, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    vprev = jnp.pad(vc, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    kk = jnp.concatenate([kprev, kc], axis=2)  # (B,n,2c,H,D)
    vv = jnp.concatenate([vprev, vc], axis=2)
    scores = jnp.einsum("bnchd,bnthd->bnhct", qc, kk).astype(jnp.float32)
    scores = scores / math.sqrt(d)
    qpos = jnp.arange(c)[:, None] + c
    kpos = jnp.arange(2 * c)[None, :]
    delta = qpos - kpos
    mask = (delta >= 0) & (delta < window)  # (c, 2c)
    first = jnp.arange(2 * c)[None, :] >= c  # chunk 0: previous chunk is padding
    nmask = jnp.concatenate(
        [(mask & first)[None], jnp.broadcast_to(mask[None], (n - 1, c, 2 * c))], axis=0
    )  # (n,c,2c)
    scores = jnp.where(nmask[None, :, None], scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bnhct,bnthd->bnchd", w, vv)
    return o.reshape(b, s, h, d)


# ------------------------------------------------------------- decode


def _grouped_sdpa(q, k, v, mask):
    """Grouped path for decode: caches stay at K heads (no expansion).
    mask broadcastable to (B,K,G,S,T)."""
    b, s, h, d = q.shape
    kheads = k.shape[2]
    qg = q.reshape(b, s, kheads, h // kheads, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    scores = scores / math.sqrt(d)
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return o.reshape(b, s, h, d)


def decode_attention(q, k_cache, v_cache, cur_index):
    """q: (B,1,H,D); caches: (B,T,K,D); attends to positions <= cur_index."""
    t = k_cache.shape[1]
    cur = jnp.reshape(cur_index, (-1, 1))
    mask = (jnp.arange(t)[None, :] <= cur)[:, None, None, None, :]  # (B,1,1,1,T)
    return _grouped_sdpa(q, k_cache, v_cache, mask)


def decode_local_attention(q, k_ring, v_ring, cur_index, window: int):
    """Ring-buffer sliding window cache: slot = pos % window."""
    t = k_ring.shape[1]  # == window (or prompt len if shorter)
    slots = jnp.arange(t)[None, :]
    cur = jnp.reshape(cur_index, (-1, 1))
    pos = cur - ((cur - slots) % t)  # position stored in each slot
    valid = (pos >= 0) & (cur - pos < window)
    mask = valid[:, None, None, None, :]
    return _grouped_sdpa(q, k_ring, v_ring, mask)
