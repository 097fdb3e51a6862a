"""Unified model configuration for the architecture zoo.

A model is a token embedding, a sequence of *stages*, a final norm and an
LM head.  Each stage is a repeating *pattern* of block kinds — e.g.
recurrentgemma is ``(("rglru", "rglru", "local_attn"), 8)`` followed by
``(("rglru", "rglru"), 1)``.  Stages with ``repeats > 1`` are executed with
``lax.scan`` over stacked parameters so the HLO stays compact regardless of
depth (critical for 512-way SPMD compiles on this box).

Block kinds:
  attn        pre-norm causal GQA self-attention + pre-norm FFN
  local_attn  as above with sliding-window (chunked, sub-quadratic) attention
  enc_attn    bidirectional attention + FFN (encoder)
  dec_attn    causal self-attn + cross-attn to encoder + FFN (decoder)
  moe         attention + mixture-of-experts FFN (optionally shared experts)
  rglru       Griffin-style gated linear recurrent block + gated FFN
  mlstm       xLSTM matrix-memory block (chunkwise parallel)
  slstm       xLSTM scalar-memory block (sequential scan)
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp

Stage = Tuple[Tuple[str, ...], int]  # (pattern, repeats)


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int  # the router's width: every expert of the layer
    experts_per_token: int
    d_ff_expert: int
    num_shared_experts: int = 0
    d_ff_shared: int = 0
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    impl: str = "dense"  # "dense" (MeshTF one-hot dispatch) | "ragged" (sort + ragged_dot EP)
    # dispatch-einsum cost is O(tokens · group · k · cf · d): grouping the
    # sequence bounds it (0 = one group per sequence — quadratic in S!)
    group_size: int = 0
    # this chip's expert share under expert parallelism: experts
    # [first_expert, first_expert + held_experts) of the num_experts the
    # router scores (0 = all of them).  Slots routed to other experts
    # belong to other chips' shares and add nothing here.
    held_experts: int = 0
    first_expert: int = 0
    # re-normalise the top-k gates to sum to one (DBRX does, Qwen-MoE not)
    norm_topk_prob: bool = True
    # weight of the load-balancing loss in the training loss
    aux_loss_coef: float = 0.01

    @property
    def held(self) -> int:
        """Experts whose weights this chip holds."""
        return self.held_experts or self.num_experts

    def __post_init__(self):
        if not 0 <= self.first_expert < self.first_expert + self.held <= self.num_experts:
            raise ValueError(
                f"held experts [{self.first_expert}, "
                f"{self.first_expert + self.held}) outside the "
                f"{self.num_experts} the router scores"
            )


@dataclasses.dataclass(frozen=True)
class EncoderConfig:
    stages: Tuple[Stage, ...]
    num_frames: int  # sequence length of (stub) modality frontend output
    d_input: int     # feature dim of precomputed frame embeddings


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str  # dense | moe | hybrid | ssm | audio | vlm
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    stages: Tuple[Stage, ...]
    head_dim: int = 0  # 0 -> d_model // num_heads
    activation: str = "swiglu"  # swiglu | gelu | geglu
    norm_eps: float = 1e-6
    # positional encodings
    rope: bool = True
    rope_theta: float = 10000.0
    mrope_sections: Tuple[int, ...] = ()  # non-empty -> M-RoPE (qwen2-vl)
    qkv_bias: bool = False  # q, k and v projections add a bias (Qwen2)
    # attention implementation: "full" materializes scores; "blocked" is the
    # flash-style online-softmax path (memory-roofline lever, §Perf)
    attn_impl: str = "full"
    attn_block: int = 1024
    # sliding-window attention
    local_window: int = 2048
    # recurrence widths
    rnn_width: int = 0       # rglru width; 0 -> d_model
    conv_width: int = 4      # temporal conv in recurrent blocks
    mlstm_proj_factor: float = 2.0
    mlstm_chunk: int = 256
    # encoder-decoder
    encoder: Optional[EncoderConfig] = None
    # MoE
    moe: Optional[MoEConfig] = None
    # numerics
    dtype: str = "bfloat16"      # compute dtype
    param_dtype: str = "float32"  # storage dtype
    logit_dtype: str = "float32"
    # accumulation/reduction dtype of TP-sharded matmuls.  float32 (XLA
    # default) makes GSPMD all-reduce the PARTIAL SUMS in f32; bfloat16
    # halves every tensor-parallel activation collective (§Perf lever;
    # one extra rounding per shard partial)
    matmul_reduce_dtype: str = "float32"
    # Megatron-style sequence parallelism: between attention regions the
    # residual stream is sharded (B, S/tp, d) over the model axis, so
    # norms/FFN/elementwise work and memory shard 1/tp; GSPMD converts the
    # TP all-reduces into reduce-scatter + all-gather pairs (§Perf lever)
    sequence_parallel: bool = False
    # training
    remat: str = "dots"   # none | dots | full
    loss_chunk: int = 0   # 0 -> unchunked vocab loss; else chunk seq by this
    # "log_softmax" materializes the normalized (B,S,V) matrix; "lse"
    # computes nll = logsumexp(logits) - logits[label] directly (one fewer
    # full-vocab tensor written — §Perf memory lever)
    loss_impl: str = "log_softmax"
    tie_embeddings: bool = False
    # scan_layers=True: lax.scan over stacked layers (compact HLO, fast
    # compiles).  False: unrolled python loop — bigger HLO but XLA's
    # cost_analysis then counts every layer (the dry-run's roofline mode,
    # since HloCostAnalysis counts while-loop bodies only once).
    scan_layers: bool = True
    # sharding lever (§Perf): True = vocab dim of the embedding table
    # shards over the tensor axis (classic vocab parallelism — but the
    # token gather from a vocab-sharded table triggers GSPMD's
    # "involuntary full rematerialization").  False = embedding shards on
    # d over the data axis instead; the gather stays local.
    shard_vocab_embed: bool = True

    # ------------------------------------------------------------------
    @property
    def kq_dim(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def num_layers(self) -> int:
        return sum(len(p) * r for p, r in self.stages)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def store_dtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def reduce_pet(self):
        """preferred_element_type for TP-sharded contractions (None = XLA
        default: f32 accumulation, f32 partial-sum all-reduce)."""
        return jnp.bfloat16 if self.matmul_reduce_dtype == "bfloat16" else None

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def with_layers(self, layers: int) -> "ModelConfig":
        """Depth cut: the first ``layers`` layers of the stack, as whole
        repeats of each stage's pattern; every width stays as published.
        Refuses a depth that would end inside a pattern period."""
        if not 1 <= layers <= self.num_layers:
            raise ValueError(
                f"layers={layers} outside 1..{self.num_layers} for {self.name}"
            )
        stages, left = [], layers
        for pattern, repeats in self.stages:
            period = len(pattern)
            take = min(repeats, left // period)
            if take < repeats and left % period:
                raise ValueError(
                    f"layers={layers} is not a whole number of periods of "
                    f"{self.name}'s layer pattern {pattern}"
                )
            stages.append((pattern, take))
            left -= take * period
            if left == 0:
                break
        return self.replace(stages=tuple(stages))

    # Parameter count (exact — from abstract init; for MODEL_FLOPS = 6·N·D)
    def param_count(self, active_only: bool = False) -> int:
        from repro.models import model as _model  # lazy, avoids cycle

        return _model.param_count(self, active_only=active_only)
