"""Dry-run machinery smoke test on the in-process (single-device) mesh:
input specs -> shardings -> lower -> compile for all three step kinds.
The full 256/512-chip runs live in repro.launch.dryrun (separate process
with forced host devices)."""
import jax
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # heavy; excluded from tier-1 (see pytest.ini)
from jax.sharding import NamedSharding

from repro.configs import get_config
from repro.launch.input_specs import input_specs
from repro.launch.mesh import dp_axes, make_host_mesh
from repro.layers.common import ShardCtx
from repro.sharding.specs import batch_pspecs, cache_pspecs, param_pspecs, state_pspecs
from repro.train.optimizer import AdamW
from repro.train.steps import make_decode_step, make_prefill_step, make_train_step


def _ns(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )


@pytest.mark.parametrize("arch", ["granite-3-8b", "dbrx-132b", "recurrentgemma-2b"])
@pytest.mark.parametrize("shape_kind", ["train", "prefill", "decode"])
def test_lower_compile_smoke(arch, shape_kind):
    cfg = get_config(arch, smoke=True)
    mesh = make_host_mesh(1, 1)
    dp = dp_axes(mesh)
    ctx = ShardCtx(mesh=mesh, dp=dp)
    opt = AdamW()

    # miniature shapes standing in for the assigned cells
    import repro.configs as C

    saved = dict(C.SHAPES)
    C.SHAPES["_test"] = dict(
        seq_len=32, global_batch=2,
        kind={"train": "train", "prefill": "prefill", "decode": "decode"}[shape_kind],
    )
    try:
        kind, specs = input_specs(cfg, "_test", opt)
        if kind == "train":
            in_sh = (
                _ns(mesh, state_pspecs(cfg, specs[0], mesh, "tp")),
                _ns(mesh, batch_pspecs(specs[1], mesh, dp)),
            )
            jf = jax.jit(make_train_step(cfg, opt, ctx), in_shardings=in_sh)
        elif kind == "prefill":
            in_sh = (
                _ns(mesh, param_pspecs(cfg, specs[0], mesh, "tp")),
                _ns(mesh, batch_pspecs(specs[1], mesh, dp)),
                _ns(mesh, batch_pspecs(specs[2], mesh, dp)),
            )
            jf = jax.jit(make_prefill_step(cfg, ctx), in_shardings=in_sh)
        else:
            in_sh = (
                _ns(mesh, param_pspecs(cfg, specs[0], mesh, "tp")),
                _ns(mesh, cache_pspecs(specs[1], mesh, dp)),
                _ns(mesh, batch_pspecs(specs[2], mesh, dp)),
                _ns(mesh, batch_pspecs(specs[3], mesh, dp)),
            )
            jf = jax.jit(make_decode_step(cfg, ctx), in_shardings=in_sh)
        with mesh:
            compiled = jf.lower(*specs).compile()
        cost = compiled.cost_analysis()
        assert cost.get("flops", 0) > 0
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes > 0
    finally:
        C.SHAPES.clear()
        C.SHAPES.update(saved)


def test_unrolled_matches_scanned_semantics():
    """scan_layers=False must be numerically identical to the scan form."""
    from repro.models import model as M

    # f32 compute so scan-vs-unroll accumulation is bitwise comparable
    cfg = get_config("granite-3-8b", smoke=True).replace(dtype="float32")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    l_scan, _ = M.loss_fn(cfg, params, batch)
    l_unroll, _ = M.loss_fn(cfg.replace(scan_layers=False), params, batch)
    np.testing.assert_allclose(float(l_scan), float(l_unroll), rtol=1e-5)


def test_sequence_parallel_preserves_loss():
    """SP is a sharding hint — numerics must be identical under a mesh."""
    from repro.layers.common import ShardCtx
    from repro.models import model as M

    cfg = get_config("granite-3-8b", smoke=True).replace(dtype="float32")
    mesh = make_host_mesh(1, 1)
    ctx = ShardCtx(mesh=mesh, dp=("data",))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    with mesh:
        l0, _ = M.loss_fn(cfg, params, batch, ctx)
        l1, _ = M.loss_fn(cfg.replace(sequence_parallel=True), params, batch, ctx)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
