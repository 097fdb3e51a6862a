"""Model weights made from ``--seed`` on the device, in one jitted call.

The program's parameter tree gives only the layout (``jax.eval_shape``
of its ``init_params``); every value is drawn here, so the reference can
make the same weights again without taking anything the program made.
Projections are truncated normals scaled by their fan-in, the embedding
by 0.02, norm scales start at zero (the program's norms scale by
``1 + scale``).
"""
from __future__ import annotations

import math
from typing import Any, Dict

STREAM = 7  # the weights' JAX stream of a seed


def _fan_in(name: str, cfg: Dict[str, Any]) -> int:
    if "'wo'" in name:
        return cfg["num_attention_heads"] * cfg["head_dim"]
    if "'w_out'" in name:
        return cfg["intermediate_size"]
    return cfg["hidden_size"]


def make_params(layout, cfg: Dict[str, Any], seed: int):
    """A tree shaped like ``layout`` (ShapeDtypeStructs) with values from
    ``seed``, built on the default device by one jitted call."""
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.data import jax_key

    flat, treedef = jax.tree_util.tree_flatten_with_path(layout)
    names = [jax.tree_util.keystr(p) for p, _ in flat]
    shapes = [(tuple(x.shape), jnp.dtype(x.dtype)) for _, x in flat]

    def build(key):
        leaves = []
        for i, (name, (shape, dtype)) in enumerate(zip(names, shapes)):
            if "norm" in name:
                leaves.append(jnp.zeros(shape, dtype))
                continue
            scale = 0.02 if name == "['embed']" else 1.0 / math.sqrt(
                _fan_in(name, cfg))
            x = jax.random.truncated_normal(
                jax.random.fold_in(key, i), -2.0, 2.0, shape, jnp.float32)
            leaves.append((scale * x).astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build)(jax_key(seed, STREAM))
