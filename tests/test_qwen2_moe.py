"""qwen2-moe-a2.7b against its plain reference at smoke widths, and the
expert share: shares that sum to the uncut layer, no slot dropped however
the router sends them, the published switches (gate normalisation, the
shared expert's gate, the aux-loss weight, the q/k/v bias), and the
dense configurations left as they were."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _qwen2_moe_ref as ref
from repro.configs import get_config
from repro.layers import moe as moe_lib
from repro.models import model as M
from repro.models.config import MoEConfig

F32 = jnp.float32


def _cfg(first=0, held=0, impl="ragged", **kw):
    """The smoke qwen2-moe in float32 (so the program and the reference
    round alike), holding experts [first, first + held); on the dense
    path in groups of 16, each held expert with a whole group's capacity
    (num_experts / k), so no slot is dropped."""
    cfg = get_config("qwen2-moe-a2.7b", smoke=True)
    m = cfg.moe
    dense = dict(capacity_factor=m.num_experts / m.experts_per_token,
                 group_size=16) if impl == "dense" else {}
    return cfg.replace(dtype="float32", **kw, moe=dataclasses.replace(
        m, first_expert=first, held_experts=held, impl=impl, **dense))


def _ref_cfg(cfg):
    """The reference's keys for a program configuration."""
    m = cfg.moe
    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.kq_dim,
        "vocab_size": cfg.vocab_size, "num_hidden_layers": cfg.num_layers,
        "router_experts": m.num_experts, "num_experts": m.held,
        "first_expert": m.first_expert, "num_experts_per_tok": m.experts_per_token,
        "moe_intermediate_size": m.d_ff_expert,
        "shared_expert_intermediate_size": m.d_ff_shared,
        "norm_topk_prob": m.norm_topk_prob,
        "router_aux_loss_coef": m.aux_loss_coef, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta, "tie_word_embeddings": cfg.tie_embeddings,
    }


def _params(cfg, seed=0):
    """The program's init with every leaf redrawn, so the biases and the
    norm scales are not zero."""
    p = M.init_params(cfg, jax.random.PRNGKey(seed))
    leaves, tree = jax.tree_util.tree_flatten(p)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    leaves = [x + 0.1 * jax.random.normal(k, x.shape, x.dtype)
              for x, k in zip(leaves, keys)]
    return jax.tree_util.tree_unflatten(tree, leaves)


def _batch(cfg, b=2, s=32, seed=3):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (b, s + 1), 0,
                              cfg.vocab_size)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


# ------------------------------------------------ program against reference


@pytest.mark.parametrize("impl", ["ragged", "dense"])
@pytest.mark.parametrize("first,held", [(0, 0), (4, 4)])
def test_logits_loss_and_gradients_match_the_reference(impl, first, held):
    """With the q/k/v bias, the shared expert's gate and the top-k gates
    left unnormalised, over the whole layer and over a share of it."""
    cfg = _cfg(first, held, impl)
    assert cfg.qkv_bias and cfg.moe.num_shared_experts == 1
    assert not cfg.moe.norm_topk_prob
    rc = _ref_cfg(cfg)
    params, batch = _params(cfg), _batch(cfg)
    tokens, labels = batch["tokens"], batch["labels"]
    with jax.default_matmul_precision("highest"):
        hidden, _, stats = M.forward_hidden(cfg, params, tokens)
        np.testing.assert_allclose(
            M._logits(cfg, params, hidden), ref.logits(params, tokens, rc),
            rtol=2e-4, atol=2e-4)
        loss, metrics = M.loss_fn(cfg, params, batch)
        want = ref.loss(params, tokens, labels, rc)
        assert float(loss) == pytest.approx(float(want), rel=2e-5)
        # every token's k slots land on held experts, or on none here
        assert float(metrics["moe_held_slots"]) <= cfg.num_layers * \
            tokens.size * cfg.moe.experts_per_token
        g = jax.grad(lambda p: M.loss_fn(cfg, p, batch)[0])(params)
        gr = jax.grad(lambda p: ref.loss(p, tokens, labels, rc))(params)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                            jax.tree_util.tree_leaves(gr)):
        assert _rel(a, b) < 2e-3, jax.tree_util.keystr(path)


def test_the_routes_match_the_reference():
    cfg = _cfg()
    params, batch = _params(cfg), _batch(cfg)
    seen = []
    real = moe_lib.route

    def recording(p, x, moe):
        out = real(p, x, moe)
        seen.append(out[1])
        return out

    with jax.default_matmul_precision("highest"):
        orig, moe_lib.route = moe_lib.route, recording
        try:
            M.forward_hidden(cfg.replace(scan_layers=False, remat="none"), params,
                             batch["tokens"])
        finally:
            moe_lib.route = orig
        want = ref.routes(params, batch["tokens"], _ref_cfg(cfg))
    assert len(seen) == len(want) == cfg.num_layers
    for a, b in zip(seen, want):
        np.testing.assert_array_equal(np.sort(a, -1), np.sort(b, -1))


# ------------------------------------------------------------ the share


def _layer(cfg, seed=5):
    moe = cfg.moe
    p = moe_lib.init_moe(jax.random.PRNGKey(seed), cfg, moe, F32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, 24, cfg.d_model))
    return p, x


def _share(p, first, held):
    """The uncut layer's parameters as the chip holding experts
    [first, first + held) holds them."""
    out = dict(p)
    for k in ("w_in", "w_gate", "w_out"):
        out[k] = p[k][first:first + held]
    return out


@pytest.mark.parametrize("impl", ["ragged", "dense"])
def test_six_shares_sum_to_the_uncut_layer(impl):
    """Six chips of 2 experts each: their outputs, with the shared expert
    (which every chip computes alike) counted once, are the uncut
    layer's; their held slots are every slot; the aux loss is each
    chip's alike."""
    cfg = _cfg(impl=impl)
    e, chips = cfg.moe.num_experts, 6
    held = e // chips
    p, x = _layer(cfg)
    y, aux = moe_lib.apply_moe(p, x, cfg, cfg.moe, F32)
    shared = moe_lib._shared(p, x, cfg, F32)
    total, slots = jnp.zeros_like(y), 0.0
    for c in range(chips):
        moe = dataclasses.replace(cfg.moe, first_expert=c * held,
                                  held_experts=held)
        ys, auxs = moe_lib.apply_moe(_share(p, c * held, held), x, cfg, moe, F32)
        total = total + ys - shared
        slots += float(auxs["moe_held_slots"])
        assert float(auxs["moe_aux"]) == pytest.approx(float(aux["moe_aux"]))
    np.testing.assert_allclose(total + shared, y, rtol=1e-5, atol=1e-5)
    assert slots == float(aux["moe_held_slots"]) == \
        x.shape[0] * x.shape[1] * cfg.moe.experts_per_token


@pytest.mark.parametrize("impl", ["ragged", "dense"])
def test_no_slot_is_dropped_when_every_token_picks_one_expert(impl):
    """A router that sends every token to expert 3 first: the held
    share's output is the plain sum of its gated experts, and expert 3
    counts every token's slot."""
    cfg = _cfg(impl=impl)
    p, x = _layer(cfg)
    x = jnp.abs(x)
    p["router"] = p["router"].at[:, 3].set(10.0)
    y, aux = moe_lib.apply_moe(p, x, cfg, cfg.moe, F32)
    rc = _ref_cfg(cfg)
    with jax.default_matmul_precision("highest"):
        want, ids, _ = ref._moe(x, p, rc, ref.F32)
    assert bool(jnp.all(jnp.any(ids == 3, -1)))
    tokens = x.shape[0] * x.shape[1]
    assert float(aux["moe_max_expert_slots"]) == tokens
    assert float(aux["moe_held_slots"]) == tokens * cfg.moe.experts_per_token
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-4)


def test_the_share_must_lie_inside_the_router():
    with pytest.raises(ValueError):
        MoEConfig(num_experts=60, experts_per_token=4, d_ff_expert=8,
                  held_experts=10, first_expert=55)
    assert MoEConfig(60, 4, 8).held == 60


# ------------------------------------------------------ published switches


def test_norm_topk_prob_renormalises_the_gates_and_off_leaves_them():
    cfg = _cfg()
    p, x = _layer(cfg)
    gate, _, _ = moe_lib.route(p, x, cfg.moe)
    probs = jax.nn.softmax(jnp.einsum("bsd,de->bse", x, p["router"]), -1)
    np.testing.assert_allclose(gate, jax.lax.top_k(probs, 4)[0], rtol=1e-5)
    assert float(jnp.max(gate.sum(-1))) < 1.0
    norm, _, _ = moe_lib.route(p, x, dataclasses.replace(cfg.moe,
                                                         norm_topk_prob=True))
    np.testing.assert_allclose(norm.sum(-1), 1.0, rtol=1e-5)
    assert get_config("dbrx-132b").moe.norm_topk_prob
    assert not get_config("qwen2-moe-a2.7b").moe.norm_topk_prob


def test_the_shared_expert_gate_scales_the_shared_output():
    cfg = _cfg()
    p, x = _layer(cfg)
    sp = p["shared"]
    gated = moe_lib._shared(p, x, cfg, F32)
    with jax.default_matmul_precision("highest"):
        plain = jnp.einsum(
            "bsf,fd->bsd", jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_in"]),
            sp["w_out"])
        sg = jax.nn.sigmoid(jnp.einsum("bsd,do->bso", x, sp["w_sg"]))
    np.testing.assert_allclose(gated, plain * sg, rtol=1e-5, atol=1e-6)
    dbrx = get_config("dbrx-132b", smoke=True)
    assert "shared" not in M.init_params(dbrx, jax.random.PRNGKey(0))[
        "stages"][0][0]["moe"]


@pytest.mark.parametrize("arch,coef", [("qwen2-moe-a2.7b", 0.001),
                                       ("dbrx-132b", 0.01)])
def test_the_aux_loss_enters_the_loss_at_the_configured_weight(arch, coef):
    cfg = get_config(arch, smoke=True).replace(dtype="float32")
    assert cfg.moe.aux_loss_coef == coef
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    loss, metrics = M.loss_fn(cfg, params, _batch(cfg))
    assert float(metrics["aux"]) > 0
    assert float(loss) == pytest.approx(
        float(metrics["ce"]) + coef * float(metrics["aux"]), rel=1e-6)


def test_the_aux_loss_counts_every_top_k_slot():
    """E · Σ_e density_e · mean_prob_e with the top-k density: a router
    that is uniform reads k."""
    cfg = _cfg()
    p, x = _layer(cfg)
    p["router"] = jnp.zeros_like(p["router"])
    _, _, aux = moe_lib.route(p, x, cfg.moe)
    assert float(aux) == pytest.approx(cfg.moe.experts_per_token, rel=1e-5)


def test_the_qkv_bias_is_there_only_where_the_config_asks():
    qwen = M.init_params(_cfg(), jax.random.PRNGKey(0))["stages"][0][0]["attn"]
    assert {"bq", "bk", "bv"} <= set(qwen)
    assert get_config("qwen2-vl-72b").qkv_bias
    for arch in ("granite-3-8b", "dbrx-132b", "minitron-8b"):
        p = jax.eval_shape(lambda k: M.init_params(get_config(arch, smoke=True), k),
                           jax.random.PRNGKey(0))
        assert not {"bq", "bk", "bv"} & set(p["stages"][0][0]["attn"]), arch


def test_granite_parameter_tree_is_unchanged():
    """The published granite-3-8b's parameter layout, leaf by leaf."""
    p = jax.eval_shape(lambda k: M.init_params(get_config("granite-3-8b"), k),
                       jax.random.PRNGKey(0))
    got = {jax.tree_util.keystr(k): tuple(x.shape)
           for k, x in jax.tree_util.tree_leaves_with_path(p)}
    blk = "['stages'][0][0]"
    assert got == {
        "['embed']": (49155, 4096), "['final_norm']": (4096,),
        "['lm_head']": (4096, 49155),
        f"{blk}['norm1']": (40, 4096), f"{blk}['norm2']": (40, 4096),
        f"{blk}['attn']['wq']": (40, 4096, 32, 128),
        f"{blk}['attn']['wk']": (40, 4096, 8, 128),
        f"{blk}['attn']['wv']": (40, 4096, 8, 128),
        f"{blk}['attn']['wo']": (40, 32, 128, 4096),
        f"{blk}['ffn']['w_in']": (40, 4096, 12800),
        f"{blk}['ffn']['w_gate']": (40, 4096, 12800),
        f"{blk}['ffn']['w_out']": (40, 12800, 4096),
    }
    metrics = M.loss_fn(get_config("granite-3-8b", smoke=True),
                        M.init_params(get_config("granite-3-8b", smoke=True),
                                      jax.random.PRNGKey(0)),
                        _batch(get_config("granite-3-8b", smoke=True)))[1]
    assert set(metrics) == {"ce", "aux"}


def test_qwen_full_config_has_the_published_size():
    """14.3 B parameters, 2.7 B of them active a token (Qwen1.5-MoE-A2.7B)."""
    cfg = get_config("qwen2-moe-a2.7b")
    n, active = M.param_count(cfg), M.param_count(cfg, active_only=True)
    assert 13e9 <= n <= 15e9
    assert n == pytest.approx(14.3e9, rel=0.01)
    assert active == pytest.approx(2.7e9, rel=0.02)
    m = cfg.moe
    assert (cfg.d_model, cfg.num_layers, cfg.num_heads, cfg.num_kv_heads,
            cfg.kq_dim, cfg.vocab_size, cfg.rope_theta, cfg.norm_eps) == (
        2048, 24, 16, 16, 128, 151936, 1e6, 1e-6)
    assert (m.num_experts, m.experts_per_token, m.d_ff_expert,
            m.num_shared_experts, m.d_ff_shared, m.aux_loss_coef) == (
        60, 4, 1408, 1, 5632, 0.001)
    assert not cfg.tie_embeddings and m.impl == "ragged"
