"""Ahead-of-time compiles of the main paths for one TPU v5e chip.

Nothing runs: the TPU compiler, installed beside JAX, compiles for a
described ``v5e:2x2`` topology and raises what the chip's compiler would
raise (a Mosaic kernel it cannot lower, a program over HBM).  Each test
compiles one program at the widths ``chip_smoke.py`` runs on the chip:

  * the train step of granite-3-8b cut to 1 layer at seq 4096, batch 1,
    whose arguments plus temporaries must fit the HBM the compiler
    reports;
  * the serve programs at the smoke run's arena (8 slots, 128 prompt +
    32 generated positions);
  * ``csr_dot`` at the kdd set's feature count.

The topology is described inside a fixture, never at import, and the
persistent compilation cache is off around the compiles (an entry
written for a described chip cannot be read back without one).
"""
import os
import re

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.train.optimizer import AdamW, AdamWConfig  # noqa: E402
from repro.train.steps import init_train_state, make_train_step  # noqa: E402

KDD_FEATURES = 29_890_095
SEQ, BATCH = 4096, 1
SLOTS, PROMPT, GEN = 8, 128, 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    set_log_dir = "TPU_LOG_DIR" not in os.environ
    if set_log_dir:
        os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    if set_log_dir:
        del os.environ["TPU_LOG_DIR"]


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def hbm_bytes(one_chip):
    """The chip's usable HBM as its compiler reports it: the capacity
    named in the out-of-memory error for a program that cannot fit."""
    too_big = jax.ShapeDtypeStruct((2**33,), jnp.float32, sharding=one_chip)
    with pytest.raises(Exception, match="hbm") as err:
        jax.jit(lambda x: x * 2).lower(too_big).compile()
    m = re.search(r"of ([0-9.]+)G hbm", str(err.value))
    assert m, str(err.value)[:500]
    return float(m.group(1)) * 2**30


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


@pytest.fixture(scope="module")
def granite_1l():
    return get_config("granite-3-8b").with_layers(1)


def test_train_step_fits_one_chip(one_chip, hbm_bytes, granite_1l):
    cfg = granite_1l
    opt = AdamW(AdamWConfig(lr=1e-3, warmup_steps=10))
    state = jax.eval_shape(
        lambda k: init_train_state(cfg, k, opt), jax.random.PRNGKey(0)
    )
    tok = jax.ShapeDtypeStruct((BATCH, SEQ), jnp.int32, sharding=one_chip)
    compiled = (
        jax.jit(make_train_step(cfg, opt), donate_argnums=(0,))
        .lower(_on(one_chip, state), {"tokens": tok, "labels": tok})
        .compile()
    )
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < used < hbm_bytes, (used, hbm_bytes)


@pytest.mark.parametrize("program", ["prefill_at", "decode_step_slots"])
def test_serve_program_compiles(one_chip, hbm_bytes, granite_1l, program):
    cfg = granite_1l
    params = _on(
        one_chip, jax.eval_shape(lambda k: M.init_params(cfg, k),
                                 jax.random.PRNGKey(0))
    )
    if program == "prefill_at":
        fn = jax.jit(lambda p, t, n: M.prefill_at(cfg, p, t, n))
        args = (
            jax.ShapeDtypeStruct((1, PROMPT), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip),
        )
    else:
        arena = jax.eval_shape(
            lambda: M.init_decode_cache(
                cfg, SLOTS, PROMPT + GEN, pos=jnp.zeros((SLOTS,), jnp.int32)
            )
        )
        fn = jax.jit(lambda p, c, t: M.decode_step_slots(cfg, p, c, t))
        args = (
            _on(one_chip, arena),
            jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32, sharding=one_chip),
        )
    mem = fn.lower(params, *args).compile().memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < hbm_bytes


@pytest.mark.parametrize("b,k", [(4096, 48), (1024, 64)])
def test_csr_dot_compiles_at_kdd_width(one_chip, b, k):
    """(4096, 48): kdd's mean record; (1024, 64): the padded batch the
    smoke run reads (nnz up to 58, rounded up to a multiple of 8)."""
    compiled = ops.csr_dot.lower(
        jax.ShapeDtypeStruct((b, k), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((b, k), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((KDD_FEATURES,), jnp.float32, sharding=one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= 4 * KDD_FEATURES
    assert mem.output_size_in_bytes == 4 * b
