"""kimi-k2-1t-a32b — Kimi-K2-Instruct: DeepSeek-V3's block at 61 layers
[hf:moonshotai/Kimi-K2-Instruct config.json, model_type kimi_k2].

MLA (q_lora 1536, kv_lora 512, qk_nope 128 + qk_rope 64, v 128) with yarn
RoPE (theta 50000, factor 32 over 4096, betas 1/1, mscale 1/1); layer 0 is
a dense SwiGLU of width 18432 (`first_k_dense_replace` 1), layers 1-60 are
MoE: 384 routed experts of width 2048, top-8 by sigmoid score plus a
selection bias (noaux_tc, one group), gates normalised and scaled by
2.827, one shared expert. Untied vocabulary of 163840, no MTP layer.
~1.03 T parameters, ~32.9 B active per token.
"""
import dataclasses

from repro.config.base import (ArchFamily, MLAConfig, ModelConfig, MoEConfig,
                               YarnConfig)
from repro.config.registry import register


def full() -> ModelConfig:
    return ModelConfig(
        name="kimi-k2-1t-a32b",
        family=ArchFamily.MOE,
        num_layers=61,
        d_model=7168,
        num_heads=64,
        num_kv_heads=64,
        head_dim=192,
        d_ff=18432,
        vocab_size=163840,
        rope_theta=50000.0,
        rms_eps=1e-6,
        mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512,
                      qk_nope_head_dim=128, qk_rope_head_dim=64,
                      v_head_dim=128),
        rope_scaling=YarnConfig(factor=32.0,
                                original_max_position_embeddings=4096,
                                beta_fast=1.0, beta_slow=1.0, mscale=1.0,
                                mscale_all_dim=1.0),
        first_k_dense=1,
        moe=MoEConfig(
            num_experts=384,
            num_experts_per_tok=8,
            num_shared_experts=1,
            expert_ff_dim=2048,
            shared_ff_dim=2048,
            scoring="sigmoid",
            routed_scale=2.827,
        ),
        source="hf:moonshotai/Kimi-K2-Instruct",
    )


def chip() -> ModelConfig:
    """One TPU v5e chip's share of Kimi-K2-Instruct.

    Deployment: 12 pipeline stages of about 5 layers. Attention is data
    parallel inside a stage (each chip holds the whole MLA of its own
    requests); the MoE layers are expert parallel over 48 chips, 8 routed
    experts each, the shared expert replicated. This chip holds the first
    stage, the embedding and the LM head.

    reduced: num_layers 61 -> 5 (the dense layer, then 4 MoE layers);
    held experts 384 -> 8 (experts 0-7; the router keeps its 384 outputs
    and top-8). Every width is published. ~4.85 B parameters, 9.69 GB of
    bf16 weights; the latent pool takes 5 x 576 x 2 = 5,760 B a token.
    """
    f = full()
    return dataclasses.replace(
        f, name="kimi-k2-1t-a32b-chip", num_layers=5,
        moe=dataclasses.replace(f.moe, num_held_experts=8,
                                first_held_expert=0))


def reduced() -> ModelConfig:
    """The same structure at CPU test widths: a dense first layer, MLA
    with yarn, sigmoid routing with a selection bias, and 2 of 4 experts
    held."""
    return ModelConfig(
        name="kimi-k2-1t-a32b-reduced",
        family=ArchFamily.MOE,
        num_layers=3,
        d_model=128,
        num_heads=4,
        num_kv_heads=4,
        head_dim=24,
        d_ff=192,
        vocab_size=512,
        rope_theta=10000.0,
        mla=MLAConfig(q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16),
        rope_scaling=YarnConfig(factor=4.0,
                                original_max_position_embeddings=32,
                                beta_fast=1.0, beta_slow=1.0, mscale=1.0,
                                mscale_all_dim=1.0),
        first_k_dense=1,
        moe=MoEConfig(
            num_experts=4,
            num_experts_per_tok=2,
            num_shared_experts=1,
            expert_ff_dim=64,
            shared_ff_dim=64,
            scoring="sigmoid",
            routed_scale=2.827,
            num_held_experts=2,
            first_held_expert=0,
        ),
        source="reduced",
    )


register("kimi-k2-1t-a32b", full, reduced, chip=chip)
