"""kimi-k2-instruct-chip's configuration file cut to the program's
`reduced` widths (`configs/kimi_k2_1t_a32b.reduced`): MLA with yarn, a
dense first layer, sigmoid routing over 4 experts of which 2 are held."""

KIMI_SMALL = dict(
    variant="reduced", hidden_size=128, num_attention_heads=4,
    num_key_value_heads=4, head_dim=24, intermediate_size=192,
    num_hidden_layers=3, vocab_size=512, q_lora_rank=48, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    moe_intermediate_size=64, n_routed_experts=2, num_experts_per_tok=2,
    rope_theta=10000,
    rope_scaling={"beta_fast": 1, "beta_slow": 1, "factor": 4, "mscale": 1,
                  "mscale_all_dim": 1, "original_max_position_embeddings": 32,
                  "type": "yarn"},
    expert_share={"router_experts": 4, "first_held_expert": 0,
                  "chips_per_layer": 2})
