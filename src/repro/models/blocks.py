"""Block-level init/apply dispatch.

A *block* is one residual unit of a stage pattern.  Every block kind
supports three modes:
    train    — full sequence, no cache
    prefill  — full sequence, emits a decode cache
    decode   — one token, consumes + re-emits its cache

Blocks return ``(x, cache, aux)`` where aux is a dict of f32 scalars that
the model sums over layers: ``"aux"``, the auxiliary loss (MoE load
balancing; 0 elsewhere), and for MoE blocks the slot counters
``MOE_COUNTERS``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.layers import attention as attn
from repro.layers import moe as moe_lib
from repro.layers import rglru as rglru_lib
from repro.layers import xlstm as xlstm_lib
from repro.layers.common import rms_norm
from repro.layers.mlp import apply_ffn, init_ffn
from repro.layers.positional import apply_rope
from repro.models.config import ModelConfig

ATTN_KINDS = ("attn", "local_attn", "enc_attn", "dec_attn", "moe")
MOE_COUNTERS = moe_lib.COUNTERS


def _slstm_ff(cfg: ModelConfig) -> int:
    # xLSTM sLSTM blocks use a ~4/3 GeGLU FFN even when cfg.d_ff == 0.
    if cfg.d_ff:
        return cfg.d_ff
    return ((int(cfg.d_model * 4 / 3) + 127) // 128) * 128


# ------------------------------------------------------------------ init


def init_block(rng, kind: str, cfg: ModelConfig):
    dt = cfg.store_dtype
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.kq_dim
    ks = jax.random.split(rng, 6)
    p: Dict[str, Any] = {"norm1": jnp.zeros((d,), dt)}
    if kind in ("attn", "local_attn", "enc_attn"):
        p["attn"] = attn.init_attn(ks[0], d, h, kv, hd, dt, cfg.qkv_bias)
        p["norm2"] = jnp.zeros((d,), dt)
        p["ffn"] = init_ffn(ks[1], d, cfg.d_ff, cfg.activation, dt)
    elif kind == "dec_attn":
        p["attn"] = attn.init_attn(ks[0], d, h, kv, hd, dt, cfg.qkv_bias)
        p["norm2"] = jnp.zeros((d,), dt)
        p["cross"] = attn.init_attn(ks[1], d, h, kv, hd, dt)
        p["norm3"] = jnp.zeros((d,), dt)
        p["ffn"] = init_ffn(ks[2], d, cfg.d_ff, cfg.activation, dt)
    elif kind == "moe":
        assert cfg.moe is not None
        p["attn"] = attn.init_attn(ks[0], d, h, kv, hd, dt, cfg.qkv_bias)
        p["norm2"] = jnp.zeros((d,), dt)
        p["moe"] = moe_lib.init_moe(ks[1], cfg, cfg.moe, dt)
    elif kind == "rglru":
        w = cfg.rnn_width or d
        p["rglru"] = rglru_lib.init_rglru(ks[0], d, w, cfg.conv_width, dt, cfg.num_heads)
        p["norm2"] = jnp.zeros((d,), dt)
        p["ffn"] = init_ffn(ks[1], d, cfg.d_ff, cfg.activation, dt)
    elif kind == "mlstm":
        p["mlstm"] = xlstm_lib.init_mlstm(ks[0], d, cfg.num_heads, cfg.mlstm_proj_factor, dt)
    elif kind == "slstm":
        p["slstm"] = xlstm_lib.init_slstm(ks[0], d, cfg.num_heads, dt)
        p["norm2"] = jnp.zeros((d,), dt)
        p["ffn"] = init_ffn(ks[1], d, _slstm_ff(cfg), "geglu", dt)
    else:  # pragma: no cover
        raise ValueError(kind)
    return p


# ----------------------------------------------------------------- cache


def init_cache(kind: str, cfg: ModelConfig, batch: int, capacity: int):
    """Abstract per-block decode cache (shapes; dtypes chosen for stability)."""
    d, kv, hd = cfg.d_model, cfg.num_kv_heads, cfg.kq_dim
    kvdt = cfg.compute_dtype
    if kind in ("attn", "moe"):
        return {
            "k": jnp.zeros((batch, capacity, kv, hd), kvdt),
            "v": jnp.zeros((batch, capacity, kv, hd), kvdt),
        }
    if kind == "local_attn":
        w = min(cfg.local_window, capacity)
        return {
            "k": jnp.zeros((batch, w, kv, hd), kvdt),
            "v": jnp.zeros((batch, w, kv, hd), kvdt),
        }
    if kind == "dec_attn":
        enc_len = cfg.encoder.num_frames if cfg.encoder else 0
        return {
            "k": jnp.zeros((batch, capacity, kv, hd), kvdt),
            "v": jnp.zeros((batch, capacity, kv, hd), kvdt),
            "ck": jnp.zeros((batch, enc_len, kv, hd), kvdt),
            "cv": jnp.zeros((batch, enc_len, kv, hd), kvdt),
        }
    if kind == "rglru":
        w = cfg.rnn_width or d
        return {
            "h": jnp.zeros((batch, w), jnp.float32),  # recurrent state stays f32
            "conv": jnp.zeros((batch, cfg.conv_width - 1, w), cfg.compute_dtype),
        }
    if kind == "mlstm":
        dp = int(cfg.d_model * cfg.mlstm_proj_factor)
        dp = ((dp + 127) // 128) * 128
        hd_m = dp // cfg.num_heads
        return {
            "C": jnp.zeros((batch, cfg.num_heads, hd_m, hd_m), jnp.float32),
            "n": jnp.zeros((batch, cfg.num_heads, hd_m), jnp.float32),
            "m": jnp.full((batch, cfg.num_heads), -1e30, jnp.float32),
        }
    if kind == "slstm":
        hd_s = d // cfg.num_heads
        z = jnp.zeros((batch, cfg.num_heads, hd_s), jnp.float32)
        return {"c": z, "n": z, "m": jnp.full_like(z, -1e30), "h": z}
    raise ValueError(kind)  # pragma: no cover


# ----------------------------------------------------------------- apply


def _self_attention(p, x, cfg: ModelConfig, kind: str, mode: str, cache, pos, aux):
    dt = cfg.compute_dtype
    q, k, v = attn.qkv(p["attn"], x, dt)
    angles = aux.get("rope_angles")
    if angles is not None:
        q = apply_rope(q, angles)
        k = apply_rope(k, angles)
    if mode == "train" or (mode == "prefill" and kind == "enc_attn"):
        if kind == "local_attn":
            o = attn.local_attention(q, k, v, cfg.local_window)
        elif kind == "enc_attn":
            o = attn.sdpa(q, k, v)  # bidirectional
        elif cfg.attn_impl == "blocked":
            o = attn.blocked_attention(q, k, v, cfg.attn_block)
        else:
            o = attn.full_attention(q, k, v, causal=True)
        new_cache = None
        if mode == "prefill":
            new_cache = {"k": k, "v": v}
        return attn.out_proj(p["attn"], o, dt, cfg.reduce_pet), new_cache
    if mode == "prefill":
        s = k.shape[1]
        if kind == "local_attn":
            w = min(cfg.local_window, s)
            o = attn.local_attention(q, k, v, cfg.local_window)
            ring_k, ring_v = k, v
            if s >= w:
                ring_k, ring_v = k[:, s - w :], v[:, s - w :]
                # ring layout: slot = pos % w for pos in [s-w, s)
                roll = (s - w) % w
                ring_k = jnp.roll(ring_k, roll, axis=1)
                ring_v = jnp.roll(ring_v, roll, axis=1)
            cache = {"k": ring_k, "v": ring_v}
        else:
            if cfg.attn_impl == "blocked":
                o = attn.blocked_attention(q, k, v, cfg.attn_block)
            else:
                o = attn.full_attention(q, k, v, causal=True)
            cache = {"k": k, "v": v}
        return attn.out_proj(p["attn"], o, dt, cfg.reduce_pet), cache
    # decode
    if kind == "local_attn":
        w = cache["k"].shape[1]
        slot = pos % w
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))
        cur = jnp.full((x.shape[0],), pos, jnp.int32)
        o = attn.decode_local_attention(q, ck, cv, cur, cfg.local_window)
    elif jnp.ndim(pos) == 1:
        # per-slot decode (continuous batching): each row appends at its
        # own position — vmapped single-row writes, per-row causal mask
        write = jax.vmap(
            lambda c, new, p: jax.lax.dynamic_update_slice(c, new, (p, 0, 0))
        )
        ck = write(cache["k"], k, pos)
        cv = write(cache["v"], v, pos)
        cur = pos.astype(jnp.int32)
        o = attn.decode_attention(q, ck, cv, cur)
    else:
        ck = jax.lax.dynamic_update_slice(cache["k"], k, (0, pos, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache["v"], v, (0, pos, 0, 0))
        cur = jnp.full((x.shape[0],), pos, jnp.int32)
        o = attn.decode_attention(q, ck, cv, cur)
    return attn.out_proj(p["attn"], o, dt, cfg.reduce_pet), {"k": ck, "v": cv}


def apply_block(
    kind: str,
    p,
    x,
    cfg: ModelConfig,
    mode: str,
    cache=None,
    pos=None,
    aux: Optional[Dict[str, Any]] = None,
    ctx=None,
):
    aux = aux or {}
    dt = cfg.compute_dtype
    zero = {"aux": jnp.zeros((), jnp.float32)}
    h = rms_norm(x, p["norm1"], cfg.norm_eps)

    if kind in ("attn", "local_attn", "enc_attn", "moe"):
        o, new_cache = _self_attention(p, h, cfg, kind, mode, cache, pos, aux)
        x = x + o
        if ctx is not None:
            if cfg.sequence_parallel and mode == "train":
                x = ctx.hint(x, "DP", "TP", None)  # Megatron-SP residual
            else:
                x = ctx.hint(x, "DP", None, None)
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        if kind == "moe":
            y, m = moe_lib.apply_moe(p["moe"], h2, cfg, cfg.moe, dt)
            return x + y, new_cache, {"aux": m["moe_aux"], **{
                k: m[k] for k in MOE_COUNTERS}}
        y = apply_ffn(p["ffn"], h2, cfg.activation, dt, cfg.reduce_pet)
        return x + y, new_cache, zero

    if kind == "dec_attn":
        o, new_cache = _self_attention(p, h, cfg, "attn", mode, cache, pos, aux)
        x = x + o
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        enc = aux.get("enc")
        if mode == "train" or (mode == "prefill" and enc is not None):
            ck = jnp.einsum("btd,dhk->bthk", enc, p["cross"]["wk"].astype(dt))
            cv = jnp.einsum("btd,dhk->bthk", enc, p["cross"]["wv"].astype(dt))
            if new_cache is not None:
                new_cache = dict(new_cache, ck=ck, cv=cv)
        else:  # decode: cross KV comes from the cache
            ck, cv = cache["ck"], cache["cv"]
            new_cache = dict(new_cache, ck=ck, cv=cv)
        q = jnp.einsum("bsd,dhk->bshk", h2, p["cross"]["wq"].astype(dt))
        o2 = attn.sdpa(q, ck, cv)
        o2 = jnp.einsum(
            "bshk,hkd->bsd", o2, p["cross"]["wo"].astype(dt),
            preferred_element_type=cfg.reduce_pet,
        ).astype(dt)
        x = x + o2
        h3 = rms_norm(x, p["norm3"], cfg.norm_eps)
        y = apply_ffn(p["ffn"], h3, cfg.activation, dt, cfg.reduce_pet)
        return x + y, new_cache, zero

    if kind == "rglru":
        if mode == "decode":
            o, (hs, hist) = rglru_lib.apply_rglru_step(
                p["rglru"], h, (cache["h"], cache["conv"]), dt
            )
        else:
            o, (hs, hist) = rglru_lib.apply_rglru(p["rglru"], h, dt)
        new_cache = {"h": hs, "conv": hist.astype(dt)} if mode != "train" else None
        x = x + o
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        y = apply_ffn(p["ffn"], h2, cfg.activation, dt, cfg.reduce_pet)
        return x + y, new_cache, zero

    if kind == "mlstm":
        if mode == "decode":
            state = (cache["C"], cache["n"], cache["m"])
            o, (C, n, m) = xlstm_lib.mlstm_step(p["mlstm"], h, state, cfg.num_heads, dt)
        else:
            # dry-run cost mode unrolls the chunk scan so HLO analysis sees
            # every chunk — but only up to 32 chunks (tracing cost); longer
            # sequences keep the scan and dryrun adds an analytic correction
            nc = h.shape[1] // min(cfg.mlstm_chunk, h.shape[1])
            o, (C, n, m) = xlstm_lib.mlstm_chunkwise(
                p["mlstm"], h, cfg.num_heads, cfg.mlstm_chunk, dt,
                unroll=(not cfg.scan_layers) and nc <= 32,
            )
        new_cache = {"C": C, "n": n, "m": m} if mode != "train" else None
        return x + o, new_cache, zero

    if kind == "slstm":
        if mode == "decode":
            state = (cache["c"], cache["n"], cache["m"], cache["h"])
            o, (c, n, m, hh) = xlstm_lib.slstm_step(p["slstm"], h, state, cfg.num_heads, dt)
        else:
            o, (c, n, m, hh) = xlstm_lib.slstm_scan(p["slstm"], h, cfg.num_heads, dt)
        new_cache = {"c": c, "n": n, "m": m, "h": hh} if mode != "train" else None
        x = x + o
        h2 = rms_norm(x, p["norm2"], cfg.norm_eps)
        y = apply_ffn(p["ffn"], h2, "geglu", dt, cfg.reduce_pet)
        return x + y, new_cache, zero

    raise ValueError(kind)  # pragma: no cover
