"""The 10 assigned architecture configs must match the assignment exactly."""
import dataclasses

import pytest

from repro.config.base import ArchFamily
from repro.config.registry import get_config, list_archs

ASSIGNED = {
    # arch: (family, L, d_model, H, kv, d_ff, vocab)
    "qwen2-moe-a2.7b": ("moe", 24, 2048, 16, 16, 1408, 151936),
    "recurrentgemma-9b": ("hybrid", 38, 4096, 16, 1, 12288, 256000),
    "seamless-m4t-medium": ("encdec", 12, 1024, 16, 16, 4096, 256206),
    "qwen1.5-32b": ("dense", 64, 5120, 40, 40, 27392, 152064),
    "granite-3-8b": ("dense", 40, 4096, 32, 8, 12800, 49155),
    "mistral-nemo-12b": ("dense", 40, 5120, 32, 8, 14336, 131072),
    "starcoder2-7b": ("dense", 32, 4608, 36, 4, 18432, 49152),
    # Kimi-K2-Instruct: 64 kv heads as its config states (MLA), d_ff the
    # dense first layer's width
    "kimi-k2-1t-a32b": ("moe", 61, 7168, 64, 64, 18432, 163840),
    "mamba2-2.7b": ("ssm", 64, 2560, 0, 0, 0, 50280),
    "llama-3.2-vision-90b": ("vlm", 80, 8192, 64, 8, 28672, 128256),
}


def test_all_archs_registered():
    assert sorted(list_archs()) == sorted(ASSIGNED)


@pytest.mark.parametrize("arch", sorted(ASSIGNED))
def test_exact_dims(arch):
    fam, L, d, H, kv, ff, V = ASSIGNED[arch]
    c = get_config(arch)
    assert c.family == ArchFamily(fam)
    assert c.num_layers == L
    assert c.d_model == d
    assert c.num_heads == H
    assert c.num_kv_heads == kv
    assert c.d_ff == ff
    assert c.vocab_size == V


def test_moe_structure():
    q = get_config("qwen2-moe-a2.7b")
    assert q.moe.num_experts == 60 and q.moe.num_experts_per_tok == 4
    assert q.moe.num_shared_experts == 4
    k = get_config("kimi-k2-1t-a32b")
    assert k.moe.num_experts == 384 and k.moe.num_experts_per_tok == 8
    assert k.moe.num_shared_experts == 1 and k.moe.expert_ff_dim == 2048
    assert k.moe.held == 384


def test_kimi_k2_is_the_published_block():
    """Kimi-K2-Instruct's config.json: MLA sizes, yarn, sigmoid/noaux
    routing scaled by 2.827, a dense first layer of width 18432; `chip`
    is that block cut to 5 layers and experts 0-7 of 384 held."""
    k = get_config("kimi-k2-1t-a32b")
    m = k.mla
    assert (m.q_lora_rank, m.kv_lora_rank, m.qk_nope_head_dim,
            m.qk_rope_head_dim, m.v_head_dim) == (1536, 512, 128, 64, 128)
    assert k.resolved_head_dim == 192 and k.rope_theta == 50000
    y = k.rope_scaling
    assert (y.factor, y.original_max_position_embeddings, y.beta_fast,
            y.beta_slow, y.mscale, y.mscale_all_dim) == (32, 4096, 1, 1, 1, 1)
    assert (k.moe.scoring, k.moe.routed_scale) == ("sigmoid", 2.827)
    assert k.first_k_dense == 1 and k.d_ff == 18432
    assert not k.tie_embeddings
    chip = get_config("kimi-k2-1t-a32b", "chip")
    assert chip.num_layers == 5
    assert (chip.moe.num_held_experts, chip.moe.first_held_expert,
            chip.moe.num_experts, chip.moe.num_experts_per_tok) == \
        (8, 0, 384, 8)
    assert dataclasses.replace(chip, name=k.name, num_layers=61,
                               moe=k.moe) == k
    # one [c ; k_rope] latent of (512 + 64) x 2 B per layer, no V
    assert chip.kv_bytes_per_token() == 5 * 576 * 2 == 5760
    assert 4.8e9 < chip.param_count() < 4.9e9


def test_param_scales():
    # sanity: total params in the right ballpark per the model names
    assert 0.9e12 < get_config("kimi-k2-1t-a32b").param_count() < 1.2e12
    assert 30e9 < get_config("kimi-k2-1t-a32b").active_param_count() < 40e9
    assert 2.4e9 < get_config("mamba2-2.7b").param_count() < 3.1e9
    assert 7e9 < get_config("granite-3-8b").param_count() < 9e9
    assert 80e9 < get_config("llama-3.2-vision-90b").param_count() < 95e9


def test_vlm_is_100_layers_total():
    c = get_config("llama-3.2-vision-90b")
    assert c.num_layers + c.num_cross_layers == 100


def test_reduced_variants_small():
    for arch in ASSIGNED:
        r = get_config(arch, "reduced")
        assert r.d_model <= 512
        assert r.num_layers <= 3
        if r.moe:
            assert r.moe.num_experts <= 4


def test_kv_bytes_per_token():
    # SSM has no growing KV; hybrid grows only in its attention layers
    assert get_config("mamba2-2.7b").kv_bytes_per_token() == 0
    rg = get_config("recurrentgemma-9b")
    n_att = sum(1 for k in rg.layer_kinds() if k == "attention")
    assert rg.kv_bytes_per_token() == 2 * n_att * 1 * 256 * 2


def test_granite_chip_variant_keeps_published_widths():
    """`chip` cuts only depth, and its bf16 weights fit in half of one
    16 GiB chip, leaving the other half for the KV pool."""
    full = get_config("granite-3-8b", "full")
    chip = get_config("granite-3-8b", "chip")
    assert chip.num_layers == 20 < full.num_layers
    assert dataclasses.replace(chip, name=full.name,
                               num_layers=full.num_layers) == full
    assert chip.param_count() * 2 <= 16 * 2**30 // 2


def test_unknown_variant_names_the_known_ones():
    with pytest.raises(KeyError, match="reduced"):
        get_config("mamba2-2.7b", "chip")
