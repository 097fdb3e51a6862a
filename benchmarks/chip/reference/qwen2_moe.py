"""Plain reference of Qwen1.5-MoE-A2.7B's decoder over one chip's expert
share, as the configuration file states it: float32 ``jax.numpy`` at
``default_matmul_precision("highest")``, no kernels, no token sort, no
cache.  It imports nothing of the program; weights come by seed.

Per layer (pre-norm)::

    h = x + Wo·attn(rope(Wq·n1(x) + bq), rope(Wk·n1(x) + bk), Wv·n1(x) + bv)
    p = softmax(n2(h)·W_r)                       over all num_experts
    y = h + Σ_{e ∈ top_k(p), e held} p_e · E_e(n2(h)) + sigmoid(n2(h)·w_sg) · S(n2(h))

with multi-head causal attention scaled by ``1/sqrt(head_dim)``, RoPE
rotating each head's two halves, ``E_e`` and ``S`` SwiGLU MLPs
(``W_out·(silu(W_gate·x) * W_in·x)``) and ``n(x) = x / sqrt(mean(x²) +
eps) · (1 + scale)``.  The gates are the softmax's own top-k values, not
re-normalised.  Logits are ``final_norm(x)·lm_head`` over the vocabulary
slice; the loss is the mean cross entropy plus ``aux_coef`` times the
layers' load-balancing losses, each ``E · Σ_e density_e · mean_p_e`` with
``density_e`` the share of tokens that have ``e`` among their top k.

Departures from the published model, each shared with the program:

- the expert share: only held experts ``[first, first + held)`` are
  computed; slots routed to the others add nothing (they are other
  chips' part of the layer, and their exchange is left out);
- the vocabulary is a slice: ids, logits and the loss are over it;
- the load-balancing loss is summed over layers, each over its own
  tokens; transformers' ``load_balancing_loss_func`` takes one over the
  layers' tokens concatenated;
- the norms scale by ``1 + scale`` (the weights' norm scales start at 0).

Each held expert is computed on every token and weighted by its gate
(zero off its slots): plain and exact, at ``held / k`` times the
program's expert work.  ``Numerics`` rounds every matmul operand: exact
float32 for the reference, float8 (e4m3, one scale per tensor) for the
control, the step below the bfloat16 the configuration computes in.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List

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
            # through unrounded
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
    on a leading axis, one stage of one ``moe`` block kind)."""
    d, h, kv = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["num_key_value_heads"]
    hd, v, n = cfg["head_dim"], cfg["vocab_size"], cfg["num_hidden_layers"]
    e, held = cfg["router_experts"], cfg["num_experts"]
    f, fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]

    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32)

    block = {
        "norm1": s(n, d),
        "attn": {"wq": s(n, d, h, hd), "wk": s(n, d, kv, hd),
                 "wv": s(n, d, kv, hd), "wo": s(n, h, hd, d),
                 "bq": s(n, h, hd), "bk": s(n, kv, hd), "bv": s(n, kv, hd)},
        "norm2": s(n, d),
        "moe": {"router": s(n, d, e), "w_in": s(n, held, d, f),
                "w_gate": s(n, held, d, f), "w_out": s(n, held, f, d),
                "shared": {"w_in": s(n, d, fs), "w_gate": s(n, d, fs),
                           "w_out": s(n, fs, d), "w_sg": s(n, d, 1)}},
    }
    tree = {"embed": s(v, d), "stages": [(block,)], "final_norm": s(d)}
    if not cfg["tie_word_embeddings"]:
        tree["lm_head"] = s(d, v)
    return tree


def fan_in(name: str, cfg: Dict[str, Any]) -> int:
    """The input width of the projection a parameter path names."""
    if "'wo'" in name:
        return cfg["num_attention_heads"] * cfg["head_dim"]
    if "'w_out'" in name:
        return cfg["shared_expert_intermediate_size"] if "'shared'" in name \
            else cfg["moe_intermediate_size"]
    return cfg["hidden_size"]


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
    """Causal multi-head attention, one head at a time (checkpointed, so
    the backward pass keeps no score matrices)."""
    b, s, h, d = q.shape
    mask = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def one(args):
        qi, ki, vi = args
        sc = nm.einsum("bsd,btd->bst", qi, ki) / math.sqrt(d)
        sc = jnp.where(mask, sc, -jnp.inf)
        return nm.einsum("bst,btd->bsd", jax.nn.softmax(sc, axis=-1), vi)

    o = jax.lax.map(one, tuple(jnp.moveaxis(t, 2, 0) for t in (q, k, v)))
    return jnp.moveaxis(o, 0, 2)


def _swiglu(x, w_gate, w_in, w_out, nm: Numerics):
    gate = jax.nn.silu(nm.einsum("bsd,df->bsf", x, w_gate))
    return nm.einsum("bsf,fd->bsd", gate * nm.einsum("bsd,df->bsf", x, w_in),
                     w_out)


def route(x, w_router, cfg, nm: Numerics = F32):
    """Router probabilities over all experts, the top-k ids, and the
    layer's load-balancing loss."""
    e, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(nm.einsum("bsd,de->bse", x, w_router), -1)
    _, ids = jax.lax.top_k(probs, k)
    topk = jnp.sum(jax.nn.one_hot(ids, e), axis=-2)          # (B,S,E) 0/1
    if cfg["norm_topk_prob"]:
        raise NotImplementedError("the configuration states unnormalised gates")
    aux = e * jnp.sum(jnp.mean(topk, (0, 1)) * jnp.mean(probs, (0, 1)))
    return probs, topk, ids, aux


def _moe(x, p, cfg, nm: Numerics):
    """The held experts' part and the gated shared expert."""
    first = cfg["first_expert"]
    held = cfg["num_experts"]
    probs, topk, ids, aux = route(x, p["router"], cfg, nm)
    gates = (probs * topk)[..., first:first + held]           # (B,S,held)

    @jax.checkpoint
    def one(y, args):
        g, w_gate, w_in, w_out = args
        return y + g[..., None] * _swiglu(x, w_gate, w_in, w_out, nm), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        jnp.moveaxis(gates, -1, 0), p["w_gate"], p["w_in"], p["w_out"]))
    sp = p["shared"]
    sg = jax.nn.sigmoid(nm.einsum("bsd,do->bso", x, sp["w_sg"]))
    return y + sg * _swiglu(x, sp["w_gate"], sp["w_in"], sp["w_out"], nm), \
        ids, aux


def _forward(params, tokens, cfg, nm: Numerics):
    """Final-normed hidden states, the summed load-balancing loss and
    each layer's top-k ids."""
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]

    @jax.checkpoint  # the backward pass keeps only each layer's input
    def layer(x, p):
        h = _norm(x, p["norm1"], eps)
        pa = p["attn"]
        q = _rope(nm.einsum("bsd,dhk->bshk", h, pa["wq"]) + pa["bq"], theta)
        k = _rope(nm.einsum("bsd,dhk->bshk", h, pa["wk"]) + pa["bk"], theta)
        v = nm.einsum("bsd,dhk->bshk", h, pa["wv"]) + pa["bv"]
        x = x + nm.einsum("bshk,hkd->bsd", _attention(q, k, v, nm), pa["wo"])
        y, ids, a = _moe(_norm(x, p["norm2"], eps), p["moe"], cfg, nm)
        return x + y, ids, a

    x = params["embed"][tokens]
    blk = params["stages"][0][0]
    aux, routes = 0.0, []
    for i in range(cfg["num_hidden_layers"]):
        x, ids, a = layer(x, jax.tree_util.tree_map(lambda t: t[i], blk))
        aux = aux + a
        routes.append(ids)
    return _norm(x, params["final_norm"], eps), aux, routes


def logits(params, tokens, cfg, nm: Numerics = F32):
    return nm.einsum("bsd,dv->bsv", _forward(params, tokens, cfg, nm)[0],
                     head(params, cfg))


def routes(params, tokens, cfg, nm: Numerics = F32) -> List[jnp.ndarray]:
    """Each layer's top-k expert ids ``(B, S, k)``."""
    return _forward(params, tokens, cfg, nm)[2]


def loss(params, tokens, labels, cfg, nm: Numerics = F32):
    """Mean cross entropy over every position, the vocabulary projection
    taken ``LOSS_BLOCK`` positions at a time, plus the weighted
    load-balancing loss."""
    hs, aux, _ = _forward(params, tokens, cfg, nm)
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

    ce = jnp.sum(jax.lax.map(one, (hb, lb))) / (b * s)
    return ce + cfg["router_aux_loss_coef"] * aux


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
