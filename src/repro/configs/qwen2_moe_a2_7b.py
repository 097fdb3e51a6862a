"""qwen2-moe-a2.7b [moe]: Qwen1.5-MoE-A2.7B, 60 routed top-4 + 1 gated shared expert.

24L d_model=2048 16H (kv=16, MHA) head_dim=128 vocab=151936, every layer
sparse (``decoder_sparse_step`` 1) [hf:Qwen/Qwen1.5-MoE-A2.7B config.json].
Routed experts: 60 of width 1408, top-4 gates taken from the softmax over
all 60 and not re-normalised (``norm_topk_prob`` false).  One shared
expert of width 5632, scaled by ``sigmoid(x · w_sg)``.  q/k/v carry a
bias, o does not.  rope_theta 1e6, rms_norm_eps 1e-6, untied head,
``router_aux_loss_coef`` 0.001.  ``intermediate_size`` (5632) is the
width of a dense FFN, which no layer of this model has.
"""
from repro.models.config import ModelConfig, MoEConfig


def full_config() -> ModelConfig:
    return ModelConfig(
        name="qwen2-moe-a2.7b",
        family="moe",
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        head_dim=128,
        d_ff=5632,
        vocab_size=151936,
        activation="swiglu",
        norm_eps=1e-6,
        rope_theta=1e6,
        qkv_bias=True,
        stages=((("moe",), 24),),
        moe=MoEConfig(
            num_experts=60,
            experts_per_token=4,
            d_ff_expert=1408,
            num_shared_experts=1,
            d_ff_shared=5632,
            # the published model drops no token: the ragged path keeps
            # every slot, and its experts compute only the slots routed
            # to them
            impl="ragged",
            norm_topk_prob=False,
            aux_loss_coef=0.001,
        ),
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name="qwen2-moe-a2.7b-smoke",
        family="moe",
        d_model=64,
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        d_ff=128,
        vocab_size=512,
        activation="swiglu",
        norm_eps=1e-6,
        rope_theta=1e6,
        qkv_bias=True,
        stages=((("moe",), 2),),
        moe=MoEConfig(
            num_experts=12,
            experts_per_token=4,
            d_ff_expert=32,
            num_shared_experts=1,
            d_ff_shared=128,
            impl="ragged",
            norm_topk_prob=False,
            aux_loss_coef=0.001,
        ),
    )
