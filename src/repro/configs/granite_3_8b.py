"""granite-3-8b — dense GQA [hf:ibm-granite/granite-3.0-8b-base]."""
import dataclasses

from repro.config.base import ArchFamily, ModelConfig
from repro.config.registry import register


def full() -> ModelConfig:
    return ModelConfig(
        name="granite-3-8b",
        family=ArchFamily.DENSE,
        num_layers=40,
        d_model=4096,
        num_heads=32,
        num_kv_heads=8,
        d_ff=12800,
        vocab_size=49155,
        tie_embeddings=True,
        source="hf:ibm-granite/granite-3.0-8b-base",
    )


def chip() -> ModelConfig:
    """One TPU v5e chip's share of granite-3.0-8b-base.

    Deployment: two pipeline stages of 20 layers each, one chip per stage;
    this chip holds one stage. Every published width is kept — d_model
    4096, 32 query / 8 kv heads of 128, SwiGLU d_ff 12800, vocab 49155,
    tied embeddings, bf16.

    reduced: num_layers 40 -> 20 (the other stage's layers would live on
    the next chip). The dense stack has no layer pattern, so any depth is
    a whole period. 20 layers + the tied embedding are ~4.19 B params,
    ~8.4 GB of bf16 weights: about half of the chip's 16 GB HBM, leaving
    the rest for the paged KV pool (80 KiB per token at this depth).

    assumed: nothing beyond the published config; weights are random
    from a seed.
    """
    return dataclasses.replace(full(), name="granite-3-8b-chip",
                               num_layers=20)


def reduced() -> ModelConfig:
    return ModelConfig(
        name="granite-3-8b-reduced",
        family=ArchFamily.DENSE,
        num_layers=2,
        d_model=128,
        num_heads=4,
        num_kv_heads=2,
        d_ff=256,
        vocab_size=512,
        tie_embeddings=True,
        source="reduced",
    )


register("granite-3-8b", full, reduced, chip=chip)
