"""Mixture-of-Experts FFN with two interchangeable implementations.

``dense``  — MeshTF/flaxformer-style one-hot dispatch/combine einsums with a
             fixed per-sequence capacity.  Fully XLA-SPMD friendly: expert
             weights shard over the tensor axis (EP) and XLA derives the
             all-to-all-free schedule.  Baseline for the roofline.
``ragged`` — per-shard token sort + grouped matmul (``jax.lax.ragged_dot``)
             over a static T·k slots, removing the one-hot dispatch FLOPs;
             dispatch becomes data movement instead of matmul work, and no
             slot is ever dropped.

Expert share: the router scores all ``num_experts`` and picks the top k
of them; the layer holds and computes only experts ``[first_expert,
first_expert + held)``.  Slots routed elsewhere belong to other chips'
shares and add nothing here.  The shared experts, scaled by
``sigmoid(x · w_sg)``, are computed on every chip alike.

Both return (y, aux) where aux holds the load-balancing loss
``moe_aux`` and the counters ``COUNTERS``: ``moe_held_slots``, the
token-slots routed to held experts, and ``moe_max_expert_slots``, the
slots of the busiest held expert.

Device work is scoped ``moe/route`` (router, dispatch and combine),
``moe/experts`` (the experts' matmuls) and ``moe/shared`` so a profiler
trace attributes it.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.layers.common import activation_fn, dense_init
from repro.models.config import ModelConfig, MoEConfig

COUNTERS = ("moe_held_slots", "moe_max_expert_slots")


def init_moe(rng, cfg: ModelConfig, moe: MoEConfig, dtype):
    d, f = cfg.d_model, moe.d_ff_expert
    ks = jax.random.split(rng, 5)
    p = {
        "router": dense_init(ks[0], (d, moe.num_experts), dtype, scale=0.02),
        "w_in": dense_init(ks[1], (moe.held, d, f), dtype, scale=d ** -0.5),
        "w_gate": dense_init(ks[2], (moe.held, d, f), dtype, scale=d ** -0.5),
        "w_out": dense_init(ks[3], (moe.held, f, d), dtype, scale=f ** -0.5),
    }
    if moe.num_shared_experts:
        sk = jax.random.split(ks[4], 4)
        p["shared"] = {
            "w_in": dense_init(sk[0], (d, moe.d_ff_shared), dtype),
            "w_gate": dense_init(sk[1], (d, moe.d_ff_shared), dtype),
            "w_out": dense_init(sk[2], (moe.d_ff_shared, d), dtype),
            "w_sg": dense_init(sk[3], (d, 1), dtype),
        }
    return p


def _capacity(moe: MoEConfig, seq: int) -> int:
    cap = int(math.ceil(moe.experts_per_token * seq * moe.capacity_factor / moe.num_experts))
    return max(8, ((cap + 7) // 8) * 8)


def route(params, x, moe: MoEConfig):
    """Top-k over the softmax of all ``num_experts`` router logits.

    Returns the gates (re-normalised over the k where
    ``moe.norm_topk_prob``), the expert ids, and the load-balancing loss
    ``E · Σ_e density_e · mean_prob_e`` with the top-k density (each
    token counts once for each of its k experts), as transformers'
    ``load_balancing_loss_func`` computes it for one layer."""
    # float32 at full precision: a TPU's default would round both operands
    # to bfloat16, and the top-k choice is discontinuous in the logits
    logits = jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32), params["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = jax.lax.top_k(probs, moe.experts_per_token)  # (B,S,k)
    if moe.norm_topk_prob:
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    density = jnp.mean(jax.nn.one_hot(ids, moe.num_experts).sum(-2), axis=(0, 1))
    mean_probs = jnp.mean(probs, axis=(0, 1))
    aux = moe.num_experts * jnp.sum(density * mean_probs)
    return gate, ids, aux


def _counters(slots_per_expert):
    """The two counters from the slots each held expert received."""
    slots = slots_per_expert.astype(jnp.float32)
    return {"moe_held_slots": slots.sum(), "moe_max_expert_slots": slots.max()}


def apply_moe_dense(params, x, cfg: ModelConfig, moe: MoEConfig, dtype):
    """Dispatch cost is O(B·S·E·C·d) with C = k·cf·group/E — i.e. QUADRATIC
    in the group length.  ``moe.group_size`` re-chunks the sequence into
    groups so the dispatch one-hots stay small (§Perf lever).  A
    ``capacity_factor`` of ``num_experts / experts_per_token`` gives every
    expert a whole group's capacity, so no slot is dropped."""
    b0, s0, d0 = x.shape
    g = moe.group_size or s0
    if 0 < g < s0 and s0 % g == 0:
        x = x.reshape(b0 * (s0 // g), g, d0)
    b, s, d = x.shape
    k, e = moe.experts_per_token, moe.held
    cap = _capacity(moe, s)
    with jax.named_scope("moe/route"):
        gate, ids, aux = route(params, x, moe)
        # experts outside the share one-hot to all zeros: no slot here
        mask = jax.nn.one_hot(ids - moe.first_expert, e, dtype=jnp.int32)  # (B,S,k,E)
        counters = _counters(mask.sum((0, 1, 2)))
        flat = mask.reshape(b, s * k, e)
        pos = jnp.cumsum(flat, axis=1) * flat - 1  # 0-based slot, -1 where unrouted
        pos = pos.reshape(b, s, k, e)
        keep = (pos >= 0) & (pos < cap) & (mask > 0)

        dispatch = jnp.zeros((b, s, e, cap), dtype)
        combine = jnp.zeros((b, s, e, cap), dtype)
        for j in range(k):  # k is small (≤4); keeps peak memory at one (B,S,E,C)
            oh = jax.nn.one_hot(jnp.clip(pos[:, :, j, :], 0, cap - 1), cap, dtype=dtype)
            oh = oh * keep[:, :, j, :, None].astype(dtype)
            dispatch = dispatch + oh
            combine = combine + oh * gate[:, :, j, None, None].astype(dtype)
        xin = jnp.einsum("bsec,bsd->ebcd", dispatch, x)  # (E,B,C,d)

    with jax.named_scope("moe/experts"):
        act = activation_fn(cfg.activation)
        h = jnp.einsum("ebcd,edf->ebcf", xin, params["w_in"].astype(dtype))
        gt = jnp.einsum("ebcd,edf->ebcf", xin, params["w_gate"].astype(dtype))
        h = act(gt) * h
        yout = jnp.einsum(
            "ebcf,efd->ebcd", h, params["w_out"].astype(dtype),
            preferred_element_type=cfg.reduce_pet,
        ).astype(dtype)

    with jax.named_scope("moe/route"):  # the combine: back to token order
        y = jnp.einsum(
            "ebcd,bsec->bsd", yout, combine, preferred_element_type=cfg.reduce_pet
        ).astype(dtype)

    y = y + _shared(params, x, cfg, dtype)
    if y.shape[:2] != (b0, s0):
        y = y.reshape(b0, s0, d0)
    return y, {"moe_aux": aux, **counters}


def apply_moe_ragged(params, x, cfg: ModelConfig, moe: MoEConfig, dtype):
    """Sort the T·k (token, expert) slots by held expert, run one grouped
    matmul per weight (ragged_dot), scatter-add back.

    The slot count is static (T·k), so however the router sends them no
    slot is dropped; slots of experts outside the share sort past the last
    group and are masked out of the combine.  Inside jit/SPMD this is
    applied per data shard (token dim sharded over DP axes).
    """
    b, s, d = x.shape
    k, e = moe.experts_per_token, moe.held
    with jax.named_scope("moe/route"):
        gate, ids, aux = route(params, x, moe)
        local = ids.reshape(-1) - moe.first_expert              # (T*k,)
        mine = (local >= 0) & (local < e)
        key = jnp.where(mine, local, e)                         # others last
        order = jnp.argsort(key, stable=True)
        sorted_tok = order // k                                 # slot -> token
        group_sizes = jnp.bincount(key, length=e + 1)[:e].astype(jnp.int32)
        counters = _counters(group_sizes)
        w = jnp.where(mine, gate.reshape(-1), 0.0)[order].astype(dtype)[:, None]
        held_slot = mine[order][:, None]

    with jax.named_scope("moe/experts"):
        # rows past the last group are not the share's, and the TPU's
        # grouped matmul leaves them unwritten: every operand and result
        # of one is masked there, so neither the forward values nor the
        # backward pass's cotangents carry what those rows hold
        zero = jnp.zeros((), dtype)

        def gmm(lhs, w):
            out = jax.lax.ragged_dot(lhs, w.astype(dtype), group_sizes)
            return jnp.where(held_slot, out, zero)

        gathered = jnp.where(held_slot, x.reshape(b * s, d)[sorted_tok], zero)
        act = activation_fn(cfg.activation)
        h = act(gmm(gathered, params["w_gate"])) * gmm(gathered, params["w_in"])
        out = gmm(h, params["w_out"]) * w
        y = jnp.zeros((b * s, d), dtype).at[sorted_tok].add(out)
    y = y.reshape(b, s, d) + _shared(params, x, cfg, dtype)
    return y, {"moe_aux": aux, **counters}


def _shared(params, x, cfg: ModelConfig, dtype):
    if "shared" not in params:
        return jnp.zeros_like(x)
    with jax.named_scope("moe/shared"):
        sp = params["shared"]
        act = activation_fn(cfg.activation)
        h = jnp.einsum("bsd,df->bsf", x, sp["w_in"].astype(dtype))
        g = jnp.einsum("bsd,df->bsf", x, sp["w_gate"].astype(dtype))
        y = jnp.einsum(
            "bsf,fd->bsd", act(g) * h, sp["w_out"].astype(dtype),
            preferred_element_type=cfg.reduce_pet,
        ).astype(dtype)
        sg = jnp.einsum("bsd,do->bso", x, sp["w_sg"].astype(dtype))
        return y * jax.nn.sigmoid(sg.astype(jnp.float32)).astype(dtype)


def apply_moe(params, x, cfg: ModelConfig, moe: MoEConfig, dtype):
    if moe.impl == "ragged":
        return apply_moe_ragged(params, x, cfg, moe, dtype)
    return apply_moe_dense(params, x, cfg, moe, dtype)
