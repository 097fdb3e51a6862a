"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def batch_gather_ref(table, indices, rows_per_block: int = 1):
    n, d = table.shape
    r = rows_per_block
    blocks = table.reshape(n // r, r, d)
    return blocks[indices].reshape(indices.shape[0] * r, d)


@jax.jit
def csr_dot_ref(indices, values, w):
    """Padded-CSR inner products: ``out[b] = Σ_k values[b,k]·w[indices[b,k]]``.

    The oracle for ``ops.csr_dot``.  Jitted so the comparison is
    bit-exact: XLA's compiled gather→mul→reduce emits the same
    accumulation order at any leading batch extent, whereas the eager
    path reassociates differently (~1 ulp)."""
    gathered = w.astype(jnp.float32)[indices]
    return jnp.sum(values.astype(jnp.float32) * gathered, axis=-1)


def flash_attention_ref(q, k, v, causal: bool = True):
    """q: (B,S,H,D); k,v: (B,T,K,D) — plain softmax attention, f32 math."""
    b, s, h, d = q.shape
    kh = k.shape[2]
    if kh != h:
        g = h // kh
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    scores = jnp.einsum(
        "bshd,bthd->bhst", q.astype(jnp.float32), k.astype(jnp.float32)
    ) / math.sqrt(d)
    if causal:
        t = k.shape[1]
        mask = jnp.arange(t)[None, :] <= jnp.arange(s)[:, None]
        scores = jnp.where(mask[None, None], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhst,bthd->bshd", w, v.astype(jnp.float32))
    return o.astype(q.dtype)


def rglru_scan_ref(a, x, h0=None):
    """h_t = a_t * h_{t-1} + x_t over axis 1.  a, x: (B, T, W) f32."""
    def step(h, inputs):
        at, xt = inputs
        h = at * h + xt
        return h, h

    b, t, w = a.shape
    h0 = jnp.zeros((b, w), jnp.float32) if h0 is None else h0
    _, hs = jax.lax.scan(
        step, h0, (jnp.moveaxis(a, 1, 0).astype(jnp.float32), jnp.moveaxis(x, 1, 0).astype(jnp.float32))
    )
    return jnp.moveaxis(hs, 0, 1)


def flash_decode_ref(q, k_cache, v_cache, cur_index):
    """q: (B,H,D); caches: (B,T,K,D); masked softmax attention (oracle)."""
    from repro.layers.attention import decode_attention

    return decode_attention(q[:, None], k_cache, v_cache, cur_index)[:, 0]
