"""Plain reference of the granite decoder as the configuration file states
it: float32 ``jax.numpy`` at ``default_matmul_precision("highest")``,
no kernels, no cache, no batching tricks.  It imports nothing of the
program; weights come from ``benchmarks.chip.weights`` by seed.

Per layer (pre-norm): ``x += Wo·attn(rope(Wq·n1(x)), rope(Wk·n1(x)),
Wv·n1(x))`` with grouped-query heads and a causal softmax scaled by
``1/sqrt(head_dim)``; ``x += W_out·(silu(W_gate·n2(x)) * W_in·n2(x))``.
``n(x) = x / sqrt(mean(x²) + eps) · (1 + scale)``.  RoPE rotates the two
halves of each head (``theta`` from the file).  Logits are
``final_norm(x)·embedᵀ`` where the file ties the head to the embedding,
else ``final_norm(x)·lm_head``; the loss is the mean cross entropy.

``Numerics`` rounds every matmul operand: exact float32 for the
reference, and float8 (e4m3, one scale per tensor) for the control,
the step below the bfloat16 the configuration computes in.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

LOSS_BLOCK = 512  # positions of the vocabulary projection at a time


@dataclasses.dataclass(frozen=True)
class Numerics:
    """How matmul operands are rounded before a float32 product."""

    name: str = "float32"

    def cast(self, x):
        if self.name == "float32":
            return x
        if self.name == "float8":
            # the forward operand rounded to float8; the gradient passes
            # through unrounded (JAX would round the cotangent to float8
            # too, unscaled, and lose most of it)
            amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
            scale = jnp.where(amax > 0, amax / 448.0, 1.0)
            q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            return x + jax.lax.stop_gradient(q * scale - x)
        raise ValueError(self.name)

    def einsum(self, spec, a, b):
        return jnp.einsum(spec, self.cast(a), self.cast(b),
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)


F32 = Numerics("float32")
FP8 = Numerics("float8")


def layout(cfg: Dict[str, Any]):
    """The parameter tree's shapes (the program's layout: layers stacked
    on a leading axis, one stage of one ``attn`` block kind)."""
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, ff, v = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    n = cfg["num_hidden_layers"]

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    block = {
        "norm1": s(n, d),
        "attn": {"wq": s(n, d, h, hd), "wk": s(n, d, kv, hd),
                 "wv": s(n, d, kv, hd), "wo": s(n, h, hd, d)},
        "norm2": s(n, d),
        "ffn": {"w_in": s(n, d, ff), "w_out": s(n, ff, d),
                "w_gate": s(n, d, ff)},
    }
    tree = {"embed": s(v, d), "stages": [(block,)], "final_norm": s(d)}
    if not cfg["tie_word_embeddings"]:
        tree["lm_head"] = s(d, v)
    return tree


def head(params, cfg):
    """The output projection ``(d, vocab)``."""
    if cfg["tie_word_embeddings"]:
        return params["embed"].T
    return params["lm_head"]


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + scale)


def _rope(x, theta):
    """x: (B, S, H, D); rotate the halves by position · theta^(-2i/D)."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (np.arange(half, dtype=np.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, nm: Numerics):
    """Causal grouped-query attention, one KV head's group at a time
    (checkpointed, so the backward pass keeps no score matrices)."""
    b, s, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = jnp.moveaxis(q.reshape(b, s, kvh, g, d), 2, 0)   # (K,B,S,G,D)
    kg, vg = jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)  # (K,B,S,D)
    mask = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def one(args):
        qi, ki, vi = args
        sc = nm.einsum("bsgd,btd->bgst", qi, ki) / math.sqrt(d)
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return nm.einsum("bgst,btd->bsgd", p, vi)

    o = jax.lax.map(one, (qg, kg, vg))                     # (K,B,S,G,D)
    return jnp.moveaxis(o, 0, 2).reshape(b, s, h, d)


def hidden(params, tokens, cfg, nm: Numerics = F32):
    """Final-normed hidden states ``(B, S, d)``."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    x = params["embed"][tokens]
    blk = params["stages"][0][0]
    for i in range(cfg["num_hidden_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[i], blk)
        h = _norm(x, p["norm1"], eps)
        q = _rope(nm.einsum("bsd,dhk->bshk", h, p["attn"]["wq"]), theta)
        k = _rope(nm.einsum("bsd,dhk->bshk", h, p["attn"]["wk"]), theta)
        v = nm.einsum("bsd,dhk->bshk", h, p["attn"]["wv"])
        x = x + nm.einsum("bshk,hkd->bsd", _attention(q, k, v, nm),
                          p["attn"]["wo"])
        h = _norm(x, p["norm2"], eps)
        gate = jax.nn.silu(nm.einsum("bsd,df->bsf", h, p["ffn"]["w_gate"]))
        up = nm.einsum("bsd,df->bsf", h, p["ffn"]["w_in"])
        x = x + nm.einsum("bsf,fd->bsd", gate * up, p["ffn"]["w_out"])
    return _norm(x, params["final_norm"], eps)


def logits(params, tokens, cfg, nm: Numerics = F32):
    return nm.einsum("bsd,dv->bsv", hidden(params, tokens, cfg, nm),
                     head(params, cfg))


def loss(params, tokens, labels, cfg, nm: Numerics = F32):
    """Mean cross entropy over every position, the vocabulary projection
    taken ``LOSS_BLOCK`` positions at a time."""
    hs = hidden(params, tokens, cfg, nm)
    b, s, d = hs.shape
    blk = min(LOSS_BLOCK, s)
    hb = jnp.moveaxis(hs.reshape(b, s // blk, blk, d), 1, 0)
    lb = jnp.moveaxis(labels.reshape(b, s // blk, blk), 1, 0)

    @jax.checkpoint
    def one(args):
        h, lab = args
        lg = nm.einsum("bsd,dv->bsv", h, head(params, cfg))
        lse = jax.nn.logsumexp(lg, -1)
        picked = jnp.take_along_axis(lg, lab[..., None], -1)[..., 0]
        return jnp.sum(lse - picked)

    return jnp.sum(jax.lax.map(one, (hb, lb))) / (b * s)


@dataclasses.dataclass(frozen=True)
class AdamW:
    """The optimizer the configuration states: AdamW with global-norm
    clipping and a linear warm-up."""

    lr: float
    warmup_steps: int
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.01
    grad_clip: float = 1.0

    def clip(self, grads):
        gnorm = jnp.sqrt(sum(jnp.sum(g * g)
                             for g in jax.tree_util.tree_leaves(grads)))
        scale = jnp.where(gnorm > self.grad_clip,
                          self.grad_clip / jnp.maximum(gnorm, 1e-12), 1.0)
        return jax.tree_util.tree_map(lambda g: g * scale, grads)

    def update(self, params, grads, mu, nu, step):
        """One update with already clipped ``grads``; ``step`` counts
        from 0."""
        t = (step + 1).astype(jnp.float32)
        lr = self.lr * jnp.minimum(1.0, t / max(1, self.warmup_steps))
        mu = jax.tree_util.tree_map(
            lambda m, g: self.b1 * m + (1 - self.b1) * g, mu, grads)
        nu = jax.tree_util.tree_map(
            lambda n, g: self.b2 * n + (1 - self.b2) * g * g, nu, grads)
        c1, c2 = 1 - self.b1 ** t, 1 - self.b2 ** t
        params = jax.tree_util.tree_map(
            lambda p, m, n: p - lr * (
                (m / c1) / (jnp.sqrt(n / c2) + self.eps)
                + self.weight_decay * p),
            params, mu, nu)
        return params, mu, nu


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def half_batch(tokens, labels):
    """A fault: half of the batch left out, the mean taken over the rest
    (the first half of the rows, or of the positions of a single row)."""
    b, s = tokens.shape
    if b > 1:
        return tokens[: b // 2], labels[: b // 2]
    return tokens[:, : s // 2], labels[:, : s // 2]


def train_steps(make_params, batches, cfg, opt: AdamW,
                nm: Numerics = F32, fault=None):
    """Run ``len(batches)`` steps from ``make_params()``.  Returns each
    step's loss, the per-leaf norms of the first clipped gradient and of
    the parameters' change over all the steps.  ``fault="half_batch"``
    plants that fault (:func:`half_batch`) in every step."""
    if fault == "half_batch":
        batches = [half_batch(*b) for b in batches]
    elif fault is not None:
        raise ValueError(f"no fault {fault!r}")
    with jax.default_matmul_precision("highest"):
        grad_fn = jax.jit(jax.value_and_grad(
            lambda p, t, l: loss(p, t, l, cfg, nm)))
        clip = jax.jit(opt.clip)
        update = jax.jit(opt.update, donate_argnums=(0, 2, 3))
        norms = jax.jit(leaf_norms)
        diff_norms = jax.jit(lambda a, b: leaf_norms(
            jax.tree_util.tree_map(lambda x, y: x - y, a, b)))
        params = make_params()
        losses, first = [], None
        mu = nu = None
        for step, (tokens, labels) in enumerate(batches):
            value, grads = grad_fn(params, jnp.asarray(tokens),
                                   jnp.asarray(labels))
            grads = clip(grads)
            losses.append(float(value))
            if first is None:
                first = np.asarray(norms(grads))
                mu = jax.tree_util.tree_map(jnp.zeros_like, params)
                nu = jax.tree_util.tree_map(jnp.zeros_like, params)
            params, mu, nu = update(params, grads, mu, nu,
                                    jnp.asarray(step, jnp.int32))
            del grads
        del mu, nu
        change = np.asarray(diff_norms(params, make_params()))
        del params
    return {"losses": losses, "grad_norms": first, "change_norms": change}


def served_gaps(params, sequences, cfg, capacity: int, nm: Numerics = F32,
                control: Numerics = None):
    """For each ``(prompt, served)`` pair, the widest gap by which a served
    token's logit lies below the best logit at its position.  With
    ``control``, the gap (in this reference's logits) of the token that
    ``control`` ranks first at each position instead.  Every sequence is
    padded to ``capacity`` (causal attention keeps the padding out of the
    positions compared), so one program serves them all."""

    def gap(p, toks, served_next, mask):
        lg = logits(p, toks, cfg, nm)[0]
        pick = served_next if control is None else jnp.argmax(
            logits(p, toks, cfg, control)[0], -1)
        g = jnp.max(lg, -1) - jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
        return jnp.max(jnp.where(mask, g, -jnp.inf))

    with jax.default_matmul_precision("highest"):
        run = jax.jit(gap)
        worst = []
        for prompt, served in sequences:
            seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
            toks = np.zeros((1, capacity), np.int32)
            toks[0, :len(seq)] = seq
            rows = np.arange(len(prompt) - 1, len(seq))
            served_next = np.zeros(capacity, np.int32)
            served_next[rows] = served
            mask = np.zeros(capacity, bool)
            mask[rows] = True
            worst.append(float(run(params, toks, served_next, mask)))
    return worst
