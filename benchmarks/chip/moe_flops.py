"""Operations and bytes of an MoE train step over one chip's expert share,
from its shapes and its slot counter.

Expert work is counted on the slots routed to held experts only (the
program's ``moe_held_slots`` counter, summed over layers), not on the
static T·k rows the grouped matmuls are laid out over, nor on the slots
other chips' experts take.  The rest is the 6·N convention of
``benchmarks/chip/flops.py``.  ``cfg`` uses the configuration file's keys.
"""
from __future__ import annotations

from typing import Dict

BF16 = 2  # bytes an operand of the grouped matmuls takes (compute dtype)


def dense_params(cfg: Dict[str, int]) -> int:
    """Parameters every token passes through in a matmul: attention, the
    router over every expert, the shared expert and its gate, and the
    output head (the embedding is a gather)."""
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    attn = d * h * hd * 2 + d * kv * hd * 2  # q and o; k and v
    router = d * cfg["router_experts"]
    shared = 3 * d * cfg["shared_expert_intermediate_size"] + d  # + w_sg
    return cfg["num_hidden_layers"] * (attn + router + shared) + \
        d * cfg["vocab_size"]


def expert_params_per_slot(cfg: Dict[str, int]) -> int:
    """One expert's SwiGLU projections: the work of one token-slot."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def train_flops_per_token(cfg: Dict[str, int], seq: int,
                          held_slots_per_token: float) -> float:
    """6·N over the dense parameters and the held experts' slots
    (``held_slots_per_token``: slots summed over layers, per token), plus
    12·L·h·hd·S for attention scores and values, as ``flops.py`` counts
    them."""
    n = dense_params(cfg) + expert_params_per_slot(cfg) * held_slots_per_token
    attn = 12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * \
        cfg["head_dim"] * seq
    return 6.0 * n + attn


def gmm_flops(cfg: Dict[str, int], held_slots: float) -> float:
    """The grouped expert matmuls of a step, forward (2·) and backward
    (4·), over ``held_slots`` (summed over layers)."""
    return 6.0 * expert_params_per_slot(cfg) * held_slots


def gmm_bytes(cfg: Dict[str, int], held_slots: float) -> float:
    """Bytes the grouped matmuls of a step must move: for each of the
    three projections (m rows of width K in, N out, over the held
    experts' K×N weights), the forward product and the two of its
    backward pass (input and weight gradients) each read two of
    ``m·K``, ``m·N``, ``g·K·N`` and write the third."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    g, layers = cfg["num_experts"], cfg["num_hidden_layers"]
    rows = held_slots * (d + f)          # m·K + m·N, summed over layers
    weights = layers * g * d * f         # g·K·N, each layer
    return float(3 * 3 * (rows + weights) * BF16)
