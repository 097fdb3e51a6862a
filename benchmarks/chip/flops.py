"""Operations and bytes of the benchmark's programs, from their shapes.

These are the algorithm's needs, not what a compiler emitted: a train
step counts no recomputation, and ``csr_dot`` counts each byte it must
touch once.
"""
from __future__ import annotations

from typing import Dict


def matmul_params(cfg: Dict[str, int]) -> int:
    """Parameters that take part in a matmul for each token: every
    projection of every layer and the output head (the embedding is a
    gather).  ``cfg`` uses the configuration file's keys."""
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    ff = cfg["intermediate_size"]
    attn = d * h * hd * 2 + d * kv * hd * 2  # q and o; k and v
    ffn = 3 * d * ff  # gate, in, out (SwiGLU)
    return cfg["num_hidden_layers"] * (attn + ffn) + d * cfg["vocab_size"]


def train_flops_per_token(cfg: Dict[str, int], seq: int) -> float:
    """6·N for the matmuls (forward 2·N, backward 4·N) plus 12·L·d·S for
    causal-free attention scores and values, as counted by the PaLM
    paper's MFU (Chowdhery et al. 2022, appendix B)."""
    n = matmul_params(cfg)
    attn = 12 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] * \
        cfg["head_dim"] * seq
    return 6.0 * n + attn


def csr_dot_flops(batch: int, k: int) -> float:
    """A multiply and an add per padded entry."""
    return 2.0 * batch * k


def csr_dot_bytes(batch: int, k: int, index_bytes: int = 4,
                  value_bytes: int = 4, weight_bytes: int = 4,
                  out_bytes: int = 4) -> float:
    """Indices and values read, one weight gathered per entry, one
    output written per row."""
    return float(batch * k * (index_bytes + value_bytes + weight_bytes)
                 + batch * out_bytes)


def roofline_seconds(flops: float, nbytes: float, peaks: Dict[str, float]) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
