"""Model assembly: init, forward, loss, prefill and decode.

Every stage is executed with ``lax.scan`` over parameters stacked on a
leading ``repeats`` axis (compact HLO → fast 512-way SPMD compiles).
Hybrid patterns scan over whole pattern periods.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.layers.common import ShardCtx, dense_init, rms_norm
from repro.layers.positional import (
    default_positions,
    mrope_angles,
    rope_angles,
    sinusoidal,
)
from repro.models.blocks import MOE_COUNTERS, apply_block, init_block
from repro.models.config import ModelConfig
from repro.utils.tree import map_with_path


# ------------------------------------------------------------------ init


def _stacked(rng, kind: str, repeats: int, cfg: ModelConfig):
    keys = jax.random.split(rng, repeats)
    return jax.vmap(lambda k: init_block(k, kind, cfg))(keys)


def _init_stages(rng, stages, cfg: ModelConfig):
    out = []
    for si, (pattern, repeats) in enumerate(stages):
        srng = jax.random.fold_in(rng, si)
        out.append(
            tuple(
                _stacked(jax.random.fold_in(srng, pi), kind, repeats, cfg)
                for pi, kind in enumerate(pattern)
            )
        )
    return out


def init_params(cfg: ModelConfig, rng) -> Dict[str, Any]:
    dt = cfg.store_dtype
    k_embed, k_stage, k_head, k_enc = jax.random.split(rng, 4)
    params: Dict[str, Any] = {
        "embed": dense_init(k_embed, (cfg.vocab_size, cfg.d_model), dt, scale=0.02),
        "stages": _init_stages(k_stage, cfg.stages, cfg),
        "final_norm": jnp.zeros((cfg.d_model,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (cfg.d_model, cfg.vocab_size), dt)
    if cfg.encoder is not None:
        enc = {"stages": _init_stages(jax.random.fold_in(k_enc, 1), cfg.encoder.stages, cfg)}
        if cfg.encoder.d_input != cfg.d_model:
            enc["proj"] = dense_init(
                jax.random.fold_in(k_enc, 2), (cfg.encoder.d_input, cfg.d_model), dt
            )
        enc["norm"] = jnp.zeros((cfg.d_model,), dt)
        params["encoder"] = enc
    return params


def param_count(cfg: ModelConfig, active_only: bool = False) -> int:
    shapes = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.PRNGKey(0))
    if not active_only or cfg.moe is None:
        return int(
            sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes))
        )
    frac = cfg.moe.experts_per_token / cfg.moe.num_experts
    total = 0.0

    def count(path, x):
        nonlocal total
        n = math.prod(x.shape)
        if "/moe/w_" in "/" + path and "shared" not in path:
            n = n * frac
        total += n
        return x

    map_with_path(count, shapes)
    return int(total)


# ------------------------------------------------------------ stage scan


def _remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def _layer_slice(tree, i):
    return jax.tree_util.tree_map(lambda x: x[i], tree)


def _stack_trees(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _add_stats(total, more):
    """Sum two dicts of scalars key by key (a key missing counts 0)."""
    return {k: total.get(k, 0.0) + more.get(k, 0.0) for k in {**total, **more}}


def _run_stage_train(stage_params, pattern, x, cfg, aux, ctx):
    """Returns the stage's output and its blocks' ``aux`` dicts summed
    over layers: the auxiliary loss, and the MoE counters where the
    pattern has MoE blocks."""
    def body(carry, lp):
        x, stats = carry
        for pi, kind in enumerate(pattern):
            x, _, a = apply_block(kind, lp[pi], x, cfg, "train", aux=aux, ctx=ctx)
            stats = _add_stats(stats, a)
        return (x, stats), None

    body = _remat_wrap(body, cfg)
    keys = ("aux",) + (MOE_COUNTERS if "moe" in pattern else ())
    carry = (x, {k: jnp.zeros((), jnp.float32) for k in keys})
    if cfg.scan_layers:
        (x, stats), _ = jax.lax.scan(body, carry, stage_params)
        return x, stats
    repeats = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    for i in range(repeats):  # unrolled: accurate cost_analysis (dry-run)
        carry, _ = body(carry, _layer_slice(stage_params, i))
    return carry


def _run_stage_prefill(stage_params, pattern, x, cfg, aux, ctx):
    def body(carry, lp):
        x = carry
        caches = []
        for pi, kind in enumerate(pattern):
            x, c, _ = apply_block(kind, lp[pi], x, cfg, "prefill", aux=aux, ctx=ctx)
            caches.append(c)
        return x, tuple(caches)

    body = _remat_wrap(body, cfg)
    if cfg.scan_layers:
        x, caches = jax.lax.scan(body, x, stage_params)
        return x, caches
    repeats = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    outs = []
    for i in range(repeats):
        x, c = body(x, _layer_slice(stage_params, i))
        outs.append(c)
    return x, _stack_trees(outs)


def _run_stage_decode(stage_params, pattern, x, cfg, aux, ctx, caches, pos):
    def body(carry, xs):
        x = carry
        lp, cslice = xs
        new = []
        for pi, kind in enumerate(pattern):
            x, c, _ = apply_block(
                kind, lp[pi], x, cfg, "decode", cache=cslice[pi], pos=pos, aux=aux, ctx=ctx
            )
            new.append(c)
        return x, tuple(new)

    if cfg.scan_layers:
        x, new_caches = jax.lax.scan(body, x, (stage_params, caches))
        return x, new_caches
    repeats = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    outs = []
    for i in range(repeats):
        x, c = body(x, (_layer_slice(stage_params, i), _layer_slice(caches, i)))
        outs.append(c)
    return x, _stack_trees(outs)


# --------------------------------------------------------------- forward


def _rope_aux(cfg: ModelConfig, batch_size: int, seq: int, extras, offset=0):
    if not cfg.rope and not cfg.mrope_sections:
        return {}
    if cfg.mrope_sections:
        p3 = extras.get("positions_3d")
        if p3 is None:
            base = default_positions(batch_size, seq, offset)
            p3 = jnp.stack([base, base, base], axis=1)
        return {"rope_angles": mrope_angles(p3, cfg.kq_dim, cfg.rope_theta, cfg.mrope_sections)}
    positions = extras.get("positions")
    if positions is None:
        positions = default_positions(batch_size, seq, offset)
    return {"rope_angles": rope_angles(positions, cfg.kq_dim, cfg.rope_theta)}


def _embed(cfg, params, tokens):
    return jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)


def encode(cfg: ModelConfig, params, frames, ctx=None):
    """Whisper-style encoder over precomputed (stub) frontend frames."""
    enc_cfg = cfg.encoder
    x = frames.astype(cfg.compute_dtype)
    if "proj" in params["encoder"]:
        x = jnp.einsum("bfd,de->bfe", x, params["encoder"]["proj"].astype(cfg.compute_dtype))
    x = x + sinusoidal(x.shape[1], cfg.d_model, cfg.compute_dtype)[None]
    aloss = jnp.zeros((), jnp.float32)
    for si, (pattern, repeats) in enumerate(enc_cfg.stages):
        x, a = _run_stage_train(params["encoder"]["stages"][si], pattern, x, cfg, {}, ctx)
        aloss += a["aux"]
    return rms_norm(x, params["encoder"]["norm"], cfg.norm_eps), aloss


def forward_hidden(
    cfg: ModelConfig,
    params,
    tokens,
    mode: str = "train",
    extras: Optional[Dict[str, Any]] = None,
    ctx: Optional[ShardCtx] = None,
    caches=None,
    pos=None,
):
    extras = extras or {}
    b, s = tokens.shape
    x = _embed(cfg, params, tokens)
    if ctx is not None:
        x = ctx.hint(x, "DP", None, None)
    offset = 0 if mode != "decode" else pos
    aux = _rope_aux(cfg, b, s, extras, offset=offset)
    if cfg.encoder is not None:
        if mode == "decode":
            aux["enc"] = None  # cross-KV lives in the cache
        else:
            enc_out, enc_aux = encode(cfg, params, extras["encoder_frames"], ctx)
            aux["enc"] = enc_out

    stats = {"aux": jnp.zeros((), jnp.float32)}
    new_caches = []
    for si, (pattern, repeats) in enumerate(cfg.stages):
        sp = params["stages"][si]
        if mode == "train":
            x, a = _run_stage_train(sp, pattern, x, cfg, aux, ctx)
            stats = _add_stats(stats, a)
        elif mode == "prefill":
            x, c = _run_stage_prefill(sp, pattern, x, cfg, aux, ctx)
            new_caches.append(c)
        else:
            x, c = _run_stage_decode(sp, pattern, x, cfg, aux, ctx, caches["stages"][si], pos)
            new_caches.append(c)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, new_caches, stats


def _logits(cfg, params, hidden):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum(
        "...d,dv->...v", hidden, w.astype(cfg.compute_dtype),
        preferred_element_type=cfg.reduce_pet,
    ).astype(cfg.compute_dtype)


# ------------------------------------------------------------------ loss


def loss_fn(cfg: ModelConfig, params, batch, ctx=None, rng=None):
    tokens, labels = batch["tokens"], batch["labels"]
    extras = {k: v for k, v in batch.items() if k not in ("tokens", "labels")}
    hidden, _, stats = forward_hidden(cfg, params, tokens, "train", extras, ctx)

    valid = (labels >= 0).astype(jnp.float32)
    safe_labels = jnp.maximum(labels, 0)

    def ce(h, lab, val):
        logits = _logits(cfg, params, h).astype(jnp.float32)
        if cfg.loss_impl == "lse":
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
            nll = lse - picked
        else:
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * val), jnp.sum(val)

    if cfg.loss_chunk and hidden.shape[1] % cfg.loss_chunk == 0:
        nchunk = hidden.shape[1] // cfg.loss_chunk
        hs = hidden.reshape(hidden.shape[0], nchunk, cfg.loss_chunk, -1)
        ls = safe_labels.reshape(labels.shape[0], nchunk, cfg.loss_chunk)
        vs = valid.reshape(valid.shape[0], nchunk, cfg.loss_chunk)

        def body(carry, xs):
            h, lab, val = xs
            s, n = ce(h, lab, val)
            return (carry[0] + s, carry[1] + n), None

        (tot, cnt), _ = jax.lax.scan(
            body,
            (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            (jnp.moveaxis(hs, 1, 0), jnp.moveaxis(ls, 1, 0), jnp.moveaxis(vs, 1, 0)),
        )
    else:
        tot, cnt = ce(hidden, safe_labels, valid)
    loss = tot / jnp.maximum(cnt, 1.0)
    # the MoE counters, summed over layers, ride out with the metrics
    metrics = {"ce": loss, **stats}
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss_coef * stats["aux"]
    return loss, metrics


# --------------------------------------------------------------- serving


def prefill(cfg: ModelConfig, params, tokens, extras=None, ctx=None):
    hidden, caches, _ = forward_hidden(cfg, params, tokens, "prefill", extras, ctx)
    logits = _logits(cfg, params, hidden[:, -1])
    return {"pos": jnp.asarray(tokens.shape[1], jnp.int32), "stages": caches}, logits


def decode_step(cfg: ModelConfig, params, cache, tokens, extras=None, ctx=None):
    """tokens: (B, 1) — appends one token at cache['pos']."""
    pos = cache["pos"]
    hidden, new_caches, _ = forward_hidden(
        cfg, params, tokens, "decode", extras, ctx, caches=cache, pos=pos
    )
    logits = _logits(cfg, params, hidden[:, -1])
    return {"pos": pos + 1, "stages": new_caches}, logits


def extend_cache(cfg: ModelConfig, cache, extra: int):
    """Pad the self-attention KV capacity of a prefill cache by ``extra``
    positions.  Cross-attention KV, local-attention rings, and recurrent
    state leaves are untouched.  Stacked leaves are (L, B, T, K, D)."""
    new_stages = []
    for si, (pattern, repeats) in enumerate(cfg.stages):
        per_pos = []
        for pi, kind in enumerate(pattern):
            c = cache["stages"][si][pi]
            if kind in ("attn", "moe", "dec_attn"):
                c = dict(c)
                for key in ("k", "v"):
                    c[key] = jnp.pad(
                        c[key], ((0, 0), (0, 0), (0, extra), (0, 0), (0, 0))
                    )
            per_pos.append(c)
        new_stages.append(tuple(per_pos))
    return {"pos": cache["pos"], "stages": new_stages}


def prefill_at(cfg: ModelConfig, params, tokens, lengths, extras=None, ctx=None):
    """Right-padded prefill: logits at each row's *last real* token.

    ``tokens`` is (B, T) with row ``i`` real through ``lengths[i]`` and
    pad junk after; causal attention means positions ``< lengths[i]``
    never attend the junk, and the returned per-row KV past ``lengths``
    is overwritten by decode writes before it is ever attended (the
    decode step at position ``p`` writes ``p`` *then* masks ``<= p``).
    """
    hidden, caches, _ = forward_hidden(cfg, params, tokens, "prefill", extras, ctx)
    lengths = jnp.asarray(lengths, jnp.int32)
    last = hidden[jnp.arange(tokens.shape[0]), lengths - 1]
    logits = _logits(cfg, params, last)
    return {"pos": lengths, "stages": caches}, logits


def decode_step_slots(cfg: ModelConfig, params, cache, tokens, extras=None, ctx=None):
    """Per-slot decode: ``cache['pos']`` is (B,), one position per row.

    Row ``i`` appends at ``pos[i]`` and attends ``<= pos[i]`` — the
    continuous-batching primitive.  All ops downstream of the KV write
    are row-independent, so each row's output is bitwise identical to a
    run where it is the only live slot in the same-shape arena.
    """
    pos = cache["pos"]
    hidden, new_caches, _ = forward_hidden(
        cfg, params, tokens, "decode", extras, ctx, caches=cache, pos=pos
    )
    logits = _logits(cfg, params, hidden[:, -1])
    return {"pos": pos + 1, "stages": new_caches}, logits


def write_prefill_slot(cfg: ModelConfig, arena, slot, pre):
    """Copy a one-row prefill cache into row ``slot`` of a decode arena.

    ``arena`` self-attention leaves are (L, B, C, K, D); ``pre`` comes
    from a batch-1 :func:`prefill` / :func:`prefill_at` with T <= C.
    Only self-attention KV is written — the serving engine is restricted
    to attention-kind blocks, whose state lives entirely in the KV
    arena.  Returns the arena with ``pos[slot]`` set to the prefill's.
    """
    new_stages = []
    for si, (pattern, repeats) in enumerate(cfg.stages):
        per_pos = []
        for pi, kind in enumerate(pattern):
            a = arena["stages"][si][pi]
            if kind in ("attn", "moe"):
                p = pre["stages"][si][pi]
                a = dict(a)
                for key in ("k", "v"):
                    a[key] = jax.lax.dynamic_update_slice(
                        a[key],
                        p[key].astype(a[key].dtype),
                        (0, slot, 0, 0, 0),
                    )
            per_pos.append(a)
        new_stages.append(tuple(per_pos))
    pos = arena["pos"].at[slot].set(jnp.asarray(pre["pos"], jnp.int32).reshape(()))
    return {"pos": pos, "stages": new_stages}


def init_decode_cache(cfg: ModelConfig, batch: int, capacity: int, pos: int = 0):
    """Build a zeroed decode cache (concrete); mirrors prefill's structure."""
    from repro.models.blocks import init_cache

    stages = []
    for pattern, repeats in cfg.stages:
        per_pos = []
        for kind in pattern:
            one = init_cache(kind, cfg, batch, capacity)
            stacked = jax.tree_util.tree_map(
                lambda x: jnp.broadcast_to(x[None], (repeats,) + x.shape), one
            )
            per_pos.append(stacked)
        stages.append(tuple(per_pos))
    return {"pos": jnp.asarray(pos, jnp.int32), "stages": stages}
