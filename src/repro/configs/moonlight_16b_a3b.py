"""moonlight-16b-a3b-ep8 — one chip's share of Moonlight-16B-A3B
[hf:moonshotai/Moonlight-16B-A3B, model_type deepseek_v3].

Published: 27 layers (the first dense, FFN 11,264; the rest MoE), d_model
2048, 16 heads of multi-head latent attention (kv_lora_rank 512, no
q-LoRA, qk 128 + 64 RoPE, v 128, rope_theta 50,000), 64 routed experts of
width 1,408 (top-6, sigmoid scores with a correction bias, normalised
top-k weights times 2.446) plus 2 shared experts, vocab 163,840 untied,
rms_norm_eps 1e-5.

The deployment this config is the share of: each layer's 64 experts and
the vocabulary are split over an 8-chip expert-parallel group, and the
27 layers lie across pipeline stages. This chip holds stage 0's share:
the dense layer and 4 MoE layers, experts 0-7 of each, and a 20,480-id
slice of the vocabulary. The router keeps its 64 outputs and top-6;
attention, the shared experts and the dense layer are whole. No width is
cut. Weights are f32 (the arena's dtype); matmuls run at the default
precision.
"""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b-ep8", family="moe",
    source="hf:moonshotai/Moonlight-16B-A3B",
    num_layers=5, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=1408, vocab_size=20480,
    attention="mla", kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, rope_theta=50000.0,
    norm_eps=1e-5,
    num_experts=64, top_k=6, router="sigmoid", routed_scale=2.446,
    router_bias_std=0.02, router_bias_seed=0, shared_experts=2,
    dense_layers=1, dense_d_ff=11264, experts_held=8, expert_offset=0,
    router_aux_weight=0.0, dtype="float32", remat=True, optimizer="sgd",
)

# CPU test size (8 shares of 2 experts, as the chip's 8 of 64): the same block (MLA with unequal qk/v heads, a dense
# prefix of another width, sigmoid routing over more experts than held)
SMOKE = CONFIG.replace(
    num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, d_ff=32,
    vocab_size=256, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, num_experts=16, top_k=4,
    shared_experts=2, dense_layers=1, dense_d_ff=96, experts_held=2,
    remat=False)
