"""Shared neural-net layer primitives (pure functions over pytrees).

Conventions:
  * params are dicts of jnp arrays; per-layer params are STACKED over a
    leading layer dim and consumed via ``jax.lax.scan``.
  * activations default to the config compute dtype (bf16); softmax and
    normalization statistics run in fp32.
  * attention supports GQA (grouped einsum — KV heads are never repeated
    into H full heads), causal masks, sliding windows, and single-token
    decode against a (cyclic) KV cache.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp


# --------------------------------------------------------------------------
# initializers
# --------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: float = 1.0):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def rmsnorm(x, weight, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)).astype(x.dtype)


def layernorm(x, weight, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def apply_norm(cfg, x, p, prefix=""):
    if cfg.norm == "layernorm":
        return layernorm(x, p[prefix + "w"], p[prefix + "b"])
    return rmsnorm(x, p[prefix + "w"], cfg.norm_eps)


def norm_params(cfg, key, d, dtype):
    if cfg.norm == "layernorm":
        return {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}
    return {"w": jnp.ones((d,), dtype)}


# --------------------------------------------------------------------------
# rotary position embeddings (partial fraction supported)
# --------------------------------------------------------------------------

def rope_freqs(hd: int, fraction: float, theta: float):
    rot = int(hd * fraction)
    rot -= rot % 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv, rot


def apply_rope(x, positions, fraction: float, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    inv, rot = rope_freqs(hd, fraction, theta)
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    ang = positions[..., :, None, None].astype(jnp.float32) * inv  # (...,S,1,rot/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = xr[..., 0::2].astype(jnp.float32), xr[..., 1::2].astype(jnp.float32)
    o1 = x1 * cos - x2 * sin
    o2 = x1 * sin + x2 * cos
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([out, xp], axis=-1)


def sinusoidal_positions(seq: int, d: int):
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d)
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(ang))
    pe = pe.at[:, 1::2].set(jnp.cos(ang))
    return pe


def sinusoidal_position_at(pos, d: int):
    """Single-position sinusoidal embedding; pos may be a traced scalar."""
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)
    ang = jnp.asarray(pos, jnp.float32) / jnp.power(10000.0, dim / d)
    pe = jnp.zeros((d,), jnp.float32)
    pe = pe.at[0::2].set(jnp.sin(ang))
    pe = pe.at[1::2].set(jnp.cos(ang))
    return pe


# --------------------------------------------------------------------------
# attention (GQA, grouped einsum; full-sequence and decode paths)
# --------------------------------------------------------------------------

def attn_params(cfg, key, dtype, d=None):
    d = d or cfg.d_model
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H * hd), dtype),
        "wk": dense_init(ks[1], (d, K * hd), dtype),
        "wv": dense_init(ks[2], (d, K * hd), dtype),
        "wo": dense_init(ks[3], (H * hd, d), dtype, scale=1.0 / math.sqrt(2 * cfg.num_layers)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((K * hd,), dtype)
        p["bv"] = jnp.zeros((K * hd,), dtype)
    return p


def _project_qkv(cfg, p, x, xkv=None):
    B, S, _ = x.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    xkv = x if xkv is None else xkv
    q = x @ p["wq"]
    k = xkv @ p["wk"]
    v = xkv @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, xkv.shape[1], K, hd)
    v = v.reshape(B, xkv.shape[1], K, hd)
    return q, k, v


def _gqa_scores(q, k):
    """q: (B,Sq,H,hd) k: (B,Sk,K,hd) -> scores (B,K,G,Sq,Sk) fp32."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                   k.astype(jnp.float32))
    return s / math.sqrt(hd)


def _gqa_out(probs, v, dtype):
    """probs: (B,K,G,Sq,Sk) v: (B,Sk,K,hd) -> (B,Sq,H*hd)."""
    B, K, G, Sq, Sk = probs.shape
    hd = v.shape[-1]
    o = jnp.einsum("bkgqs,bskd->bqkgd", probs, v.astype(jnp.float32))
    return o.reshape(B, Sq, K * G * hd).astype(dtype)


# §Perf iteration B (EXPERIMENTS.md): blockwise attention in PURE XLA was
# tried as the S²-score fix and REFUTED — XLA spills the (m,l,acc) scan
# carries to HBM every KV block, so the memory term got WORSE (hymba
# train: 62.6s -> 182.9s). Flash attention only pays off with
# VMEM-resident accumulators -> the Pallas kernel in kernels/flash_attn.py
# (iteration C). blockwise_attention stays as the kernel's pure-jnp
# oracle and an opt-in (cfg.attention_impl="blockwise").
FLASH_BLOCK = 512


def full_attention(cfg, p, x, positions=None, causal=True, xkv=None,
                   sliding_window: Optional[int] = None, use_rope=True):
    """Full-sequence attention (training / prefill / encoder / cross)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(cfg, p, x, xkv)
    Sk = k.shape[1]
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if use_rope and xkv is None:
        q = apply_rope(q, positions, cfg.rope_fraction, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_fraction, cfg.rope_theta)
    if (getattr(cfg, "attention_impl", "full") == "blockwise"
            and S % FLASH_BLOCK == 0 and Sk % FLASH_BLOCK == 0):
        out = blockwise_attention(q, k, v, causal=(causal and xkv is None),
                                  sliding_window=sliding_window,
                                  out_dtype=x.dtype)
        return out @ p["wo"], (k, v)
    scores = _gqa_scores(q, k)                     # (B,K,G,S,Sk)
    if causal and xkv is None:
        i = jnp.arange(S)[:, None]
        j = jnp.arange(Sk)[None, :]
        mask = j <= i
        if sliding_window is not None:
            mask &= (i - j) < sliding_window
        scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, v, x.dtype)
    return out @ p["wo"], (k, v)


def blockwise_attention(q, k, v, *, causal: bool, sliding_window=None,
                        out_dtype, block: int = FLASH_BLOCK):
    """Flash-style online-softmax attention in pure JAX.

    Never materializes more than one (B,K,G,block,block) score tile at a
    time; running (max, sum, acc) statistics carry across KV blocks via
    ``lax.scan``. Memory per step: O(block²) vs O(S²). Causality is
    enforced per tile; fully-masked tiles still compute (branch-free SPMD)
    but their contribution multiplies to zero.
    """
    import math as _math
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    nq, nk = S // block, k.shape[1] // block
    scale = 1.0 / _math.sqrt(hd)
    qf = q.reshape(B, nq, block, K, G, hd).astype(jnp.float32)
    kf = k.reshape(B, nk, block, K, hd).astype(jnp.float32)
    vf = v.reshape(B, nk, block, K, hd).astype(jnp.float32)

    q_idx = jnp.arange(block)
    k_idx = jnp.arange(block)

    def q_block(qi, qb):
        # qb: (B, block, K, G, hd)
        def kv_step(carry, kv):
            m, l, acc = carry
            kj, kb, vb = kv                     # kb/vb: (B, block, K, hd)
            s = jnp.einsum("bqkgd,bskd->bkgqs", qb, kb) * scale
            if causal or sliding_window is not None:
                qi_abs = qi * block + q_idx[:, None]
                kj_abs = kj * block + k_idx[None, :]
                mask = jnp.ones((block, block), bool)
                if causal:
                    mask &= kj_abs <= qi_abs
                if sliding_window is not None:
                    mask &= (qi_abs - kj_abs) < sliding_window
                s = jnp.where(mask[None, None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(-1)
            acc = acc * corr[..., None] \
                + jnp.einsum("bkgqs,bskd->bkgqd", p, vb)
            return (m_new, l, acc), None

        m0 = jnp.full((B, K, G, block), -1e30, jnp.float32)
        l0 = jnp.zeros((B, K, G, block), jnp.float32)
        a0 = jnp.zeros((B, K, G, block, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0),
            (jnp.arange(nk), jnp.moveaxis(kf, 1, 0), jnp.moveaxis(vf, 1, 0)))
        out = acc / jnp.maximum(l[..., None], 1e-30)     # (B,K,G,block,hd)
        return jnp.moveaxis(out, 3, 1).reshape(B, block, K * G * hd)

    outs = jax.lax.map(lambda args: q_block(*args),
                       (jnp.arange(nq), jnp.moveaxis(qf, 1, 0)))
    # outs: (nq, B, block, H*hd)
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, H * hd).astype(out_dtype)


def decode_attention(cfg, p, x, cache_k, cache_v, step, *,
                     sliding_window: Optional[int] = None, cross=False,
                     use_rope: bool = True):
    """One-token decode. x: (B,1,d). cache_[kv]: (B,Scache,K,hd).

    For sliding-window archs the cache is cyclic with Scache == window and
    the new KV is written at ``step % window``. Returns (out, new_k, new_v).
    """
    B = x.shape[0]
    q, k_new, v_new = _project_qkv(cfg, p, x)
    Sc = cache_k.shape[1]
    if cross:
        # cross attention: cache holds pre-projected encoder KV, no update
        k, v = cache_k, cache_v
        scores = _gqa_scores(q, k)
        probs = jax.nn.softmax(scores, axis=-1)
        out = _gqa_out(probs, v, x.dtype)
        return out @ p["wo"], cache_k, cache_v
    if use_rope:
        pos = jnp.full((B, 1), step)
        q = apply_rope(q, pos, cfg.rope_fraction, cfg.rope_theta)
        k_new = apply_rope(k_new, pos, cfg.rope_fraction, cfg.rope_theta)
    slot = step % Sc if sliding_window is not None else step
    k = jax.lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype),
                                     (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype),
                                     (0, slot, 0, 0))
    scores = _gqa_scores(q, k)                     # (B,K,G,1,Sc)
    s_idx = jnp.arange(Sc)
    if sliding_window is not None:
        # slot s holds absolute position step - ((step - s) mod Sc)
        slot_pos = step - jnp.mod(step - s_idx, Sc)
        valid = (slot_pos >= 0) & (slot_pos <= step)
    else:
        valid = s_idx <= step
    scores = jnp.where(valid[None, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, v, x.dtype)
    return out @ p["wo"], k, v


# --------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2/V3, no q-LoRA; training only)
# --------------------------------------------------------------------------

def mla_params(cfg, key, dtype):
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], (d, H * (nope + rope)), dtype),
        "wkv_a": dense_init(ks[1], (d, r + rope), dtype),
        "kv_norm": {"w": jnp.ones((r,), dtype)},
        "wkv_b": dense_init(ks[2], (r, H * (nope + vd)), dtype),
        "wo": dense_init(ks[3], (H * vd, d), dtype),
    }


def mla_attention(cfg, p, x, positions):
    """x (B, S, d) -> (B, S, d). Queries: per head a no-RoPE part and a
    RoPE part; keys: the no-RoPE part up-projected per head from the
    RMS-normed ``kv_lora_rank`` latent, the RoPE part one projection
    shared by every head; values up-projected from the same latent. The
    core (``kernels/attention.py``) takes qk and v widths as they are."""
    from repro.kernels import attention as attn_kernel
    B, S, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    q = (x @ p["wq"]).reshape(B, S, H, nope + rope)
    kv_a = x @ p["wkv_a"]
    latent = rmsnorm(kv_a[..., :r], p["kv_norm"]["w"], cfg.norm_eps)
    k_pe = apply_rope(kv_a[..., None, r:], positions, 1.0, cfg.rope_theta)
    kv = (latent @ p["wkv_b"]).reshape(B, S, H, nope + vd)
    q = jnp.concatenate(
        [q[..., :nope], apply_rope(q[..., nope:], positions, 1.0,
                                   cfg.rope_theta)], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe, (B, S, H, rope))], axis=-1)
    with jax.named_scope("mla_attn"):
        out = attn_kernel.causal_attention(q, k, kv[..., nope:],
                                           scale=1.0 / math.sqrt(nope + rope))
    return out.reshape(B, S, H * vd).astype(x.dtype) @ p["wo"]


# --------------------------------------------------------------------------
# feed-forward
# --------------------------------------------------------------------------

def ffn_params(cfg, key, dtype, d=None, ff=None):
    d = d or cfg.d_model
    ff = ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_act == "swiglu":
        return {
            "wg": dense_init(ks[0], (d, ff), dtype),
            "wu": dense_init(ks[1], (d, ff), dtype),
            "wd": dense_init(ks[2], (ff, d), dtype, scale=1.0 / math.sqrt(2 * cfg.num_layers)),
        }
    return {
        "w1": dense_init(ks[0], (d, ff), dtype),
        "b1": jnp.zeros((ff,), dtype),
        "w2": dense_init(ks[1], (ff, d), dtype, scale=1.0 / math.sqrt(2 * cfg.num_layers)),
        "b2": jnp.zeros((d,), dtype),
    }


def ffn(cfg, p, x):
    if cfg.mlp_act == "swiglu":
        return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]
    return (jax.nn.gelu(x @ p["w1"] + p["b1"])) @ p["w2"] + p["b2"]


# --------------------------------------------------------------------------
# loss
# --------------------------------------------------------------------------

def softmax_xent(logits, labels, mask=None):
    """logits (..., V) fp-any; labels int (...,). Mean over valid tokens.

    GSPMD-friendly on a vocab-sharded V: the gold logit is extracted via a
    fused one-hot CONTRACTION (each vocab shard contributes its slice +
    tiny (B,S) psum), NOT take_along_axis — a gather over a sharded dim
    makes GSPMD all-gather the full fp32 logits (§Perf iteration D took a
    3x regression from exactly that before this rewrite)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    V = lf.shape[-1]
    idx = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    gold = jnp.sum(jnp.where(idx == labels[..., None], lf, 0.0), axis=-1)
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
