"""Architecture configuration dataclasses.

Every assigned architecture is expressed as an ``ArchConfig``. Families:
  dense   — llama-style decoder (GQA + RoPE + SwiGLU or variants)
  moe     — dense skeleton with mixture-of-experts FFN (top-k routing);
            with ``attention="mla"`` the DeepSeek-V3 block (latent
            attention, sigmoid-routed experts beside shared experts, a
            leading dense prefix), training only
  ssm     — RWKV6 "Finch" (attention-free, data-dependent decay)
  hybrid  — Hymba (parallel attention + mamba heads per layer)
  audio   — Whisper encoder-decoder backbone (conv frontend stubbed)
  vlm     — InternVL2 (InternLM2 decoder consuming stubbed patch embeds)
  mlp     — the paper's own 256-128-64 anomaly-detection MLP
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                      # dense|moe|ssm|hybrid|audio|vlm|mlp
    num_layers: int
    d_model: int
    num_heads: int = 0
    num_kv_heads: int = 0
    d_ff: int = 0
    vocab_size: int = 0
    head_dim: int = 0                # 0 -> d_model // num_heads
    source: str = ""                 # citation for the config

    # attention / norm variants -------------------------------------------
    qkv_bias: bool = False
    attention_impl: str = "full"     # full | blockwise (flash-oracle path)
    norm: str = "rmsnorm"            # rmsnorm | layernorm
    norm_eps: float = 1e-6           # rmsnorm epsilon
    mlp_act: str = "swiglu"          # swiglu | gelu
    rope_theta: float = 10000.0
    rope_fraction: float = 1.0       # partial rotary (stablelm uses 0.25)
    tie_embeddings: bool = False
    sliding_window: Optional[int] = None   # set for long_500k dense variant

    # multi-head latent attention (attention="mla"; DeepSeek-V2/V3) -------
    attention: str = "gqa"           # gqa | mla
    kv_lora_rank: int = 0            # width of the compressed KV latent
    qk_nope_head_dim: int = 0        # per-head query/key width without RoPE
    qk_rope_head_dim: int = 0        # decoupled RoPE key width (all heads)
    v_head_dim: int = 0              # per-head value width

    # moe ------------------------------------------------------------------
    num_experts: int = 0
    top_k: int = 0
    moe_dense_residual: bool = False       # arctic: dense FFN in parallel
    moe_dispatch: str = "gather"           # gather | scatter (§Perf iter D)
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.01        # load-balance loss weight
    router: str = "softmax"                # softmax | sigmoid (+ correction
                                           # bias used only to choose)
    routed_scale: float = 1.0              # routed output scale (sigmoid)
    router_bias_std: float = 0.0           # seeded correction bias (not
    router_bias_seed: int = 0              # trained, not federated)
    shared_experts: int = 0                # always-on experts beside routed
    dense_layers: int = 0                  # leading dense layers (MoE after)
    dense_d_ff: int = 0                    # their FFN width
    experts_held: int = 0                  # experts this chip computes (0:
    expert_offset: int = 0                 # all); held ids start here

    # ssm / hybrid -----------------------------------------------------------
    ssm_state: int = 0               # mamba state size (hymba) / 0
    rwkv_head_dim: int = 64          # RWKV6 WKV head size
    rwkv_lora_dim: int = 32          # ddlerp / decay LoRA rank

    # audio / vlm stubs ------------------------------------------------------
    encoder_layers: int = 0          # whisper encoder depth
    encoder_seq: int = 1500          # whisper: 30 s -> 1500 frames
    num_patches: int = 256           # vlm: stubbed patch embeddings

    # mlp detector -----------------------------------------------------------
    mlp_hidden: Tuple[int, ...] = ()
    num_features: int = 0
    num_classes: int = 0
    dropout: float = 0.0

    # numerics / training ------------------------------------------------------
    dtype: str = "bfloat16"
    remat: bool = True
    optimizer: str = "adamw"         # adamw | adafactor (large archs)

    # distribution ---------------------------------------------------------
    expert_parallel: bool = False    # shard expert dim over client ("data") axis
    client_axes: Tuple[str, ...] = ("pod", "data")  # mesh axes hosting FL clients

    @property
    def hd(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // max(self.num_heads, 1)

    @property
    def padded_vocab(self) -> int:
        """Vocab rounded up to a multiple of 256 (§Perf iteration D:
        unshardable vocab dims force a full-logits all-reduce — 12.9 GB/
        layer-use for granite-moe — so embed/lm_head use the padded size;
        labels never reference pad ids)."""
        v = self.vocab_size
        return v if v % 256 == 0 else (v // 256 + 1) * 256

    @property
    def q_groups(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def replace(self, **kw) -> "ArchConfig":
        return dataclasses.replace(self, **kw)

    # --- parameter counting (for roofline MODEL_FLOPS = 6 N D) -----------
    def param_count(self, active_only: bool = False) -> int:
        """Analytic parameter count; active_only counts top-k experts only."""
        d, ff, L, V = self.d_model, self.d_ff, self.num_layers, self.vocab_size
        hd, H, K = self.hd, self.num_heads, self.num_kv_heads
        if self.family == "mlp":
            dims = (self.num_features,) + tuple(self.mlp_hidden) + (self.num_classes,)
            return sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
        attn = d * H * hd + 2 * d * K * hd + H * hd * d
        if self.qkv_bias:
            attn += (H + 2 * K) * hd
        if self.mlp_act == "swiglu":
            ffn = 3 * d * ff
        else:
            ffn = 2 * d * ff + ff + d
        norms = 2 * d
        emb = V * d * (1 if self.tie_embeddings else 2)
        if self.family == "ssm":  # rwkv6
            heads = d // self.rwkv_head_dim
            lora = self.rwkv_lora_dim
            tmix = 4 * d * d + d  # r,k,v,g,o projections (g folded) approx
            tmix += 5 * (d * lora + lora * d) + 6 * d  # ddlerp loras + mus
            tmix += d * lora + lora * d + d + heads * self.rwkv_head_dim  # decay lora + u
            cmix = d * ff + ff * d + 2 * d
            per_layer = tmix + cmix + norms
            return L * per_layer + emb + d
        if self.family == "hybrid":
            dd = d  # mamba inner dim == d_model (parallel-heads design)
            mamba = d * 2 * dd + dd * (2 * self.ssm_state + dd // 16) \
                + dd * self.ssm_state + dd + dd * d + 4 * dd
            per_layer = attn + mamba + ffn + 3 * d
            return L * per_layer + emb + d
        if self.family == "moe" and self.attention == "mla":
            nope, rope, vd = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                              self.v_head_dim)
            r = self.kv_lora_rank
            mla = (d * H * (nope + rope) + d * (r + rope) + r
                   + r * H * (nope + vd) + H * vd * d)
            n_moe = L - self.dense_layers
            held = self.experts_held or self.num_experts
            experts = ((self.top_k if active_only else held)
                       + self.shared_experts) * 3 * d * ff
            dense = self.dense_layers * 3 * d * self.dense_d_ff
            return (L * (mla + norms) + dense
                    + n_moe * (experts + d * self.num_experts) + emb + d)
        if self.family in ("moe",):
            e_ffn = self.num_experts * 3 * d * ff
            a_ffn = (self.top_k if active_only else self.num_experts) * 3 * d * ff
            router = d * self.num_experts
            dense_res = 3 * d * ff if self.moe_dense_residual else 0
            per_layer = attn + (a_ffn if active_only else e_ffn) + router + dense_res + norms
            return L * per_layer + emb + d
        if self.family == "audio":
            enc = self.encoder_layers * (attn + ffn + norms)
            dec = L * (2 * attn + ffn + 3 * d)  # self + cross attention
            return enc + dec + emb + 2 * d
        if self.family == "vlm":
            return L * (attn + ffn + norms) + emb + d + self.d_model * d  # projector stub
        # dense
        return L * (attn + ffn + norms) + emb + d
