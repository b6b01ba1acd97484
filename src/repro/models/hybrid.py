"""Hymba-style hybrid layer: parallel attention + Mamba (SSM) heads.

Each layer runs a GQA attention branch and a selective-SSM (Mamba) branch
on the SAME normed input; branch outputs are each normalized and averaged
(the Hymba fusion, arXiv:2411.13676), followed by a SwiGLU FFN. The SSM
branch gives the layer O(1) decode state, so hymba runs ``long_500k``
natively (attention heads use a sliding window on that shape).

Mamba branch (inner dim == d_model, state n = cfg.ssm_state):
    xz = x @ Win ; x1, z = split
    x1 = silu(causal_conv4(x1))
    dt = softplus(x1 @ Wdt1 @ Wdt2 + dt_bias)
    h_t = exp(dt_t * A) h_{t-1} + (dt_t * x1_t) B_t ;  y_t = h_t · C_t + D x1_t
    out = (y * silu(z)) @ Wout
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L

_CONV_W = 4  # causal conv taps


def _dtr(cfg):
    return max(cfg.d_model // 16, 8)


def _mamba_params(cfg, key, dtype):
    d, n = cfg.d_model, cfg.ssm_state
    di, dtr = d, _dtr(cfg)
    ks = jax.random.split(key, 6)
    return {
        "Win": L.dense_init(ks[0], (d, 2 * di), dtype),
        "conv_w": L.dense_init(ks[1], (_CONV_W, di), dtype),
        "conv_b": jnp.zeros((di,), dtype),
        "Wdt1": L.dense_init(ks[2], (di, dtr), dtype),
        "Wdt2": L.dense_init(ks[3], (dtr, di), dtype),
        "dt_bias": jnp.full((di,), -4.6, jnp.float32),  # softplus -> ~0.01
        "WB": L.dense_init(ks[4], (di, n), dtype),
        "WC": L.dense_init(ks[5], (di, n), dtype),
        "A_log": jnp.log(jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32), (di, 1))),
        "D": jnp.ones((di,), jnp.float32),
        "Wout": L.dense_init(jax.random.fold_in(key, 7), (di, d), dtype),
    }


def _layer_init(cfg, key, dtype):
    ks = jax.random.split(key, 6)
    return {
        "ln1": L.norm_params(cfg, ks[0], cfg.d_model, dtype),
        "attn": L.attn_params(cfg, ks[1], dtype),
        "mamba": _mamba_params(cfg, ks[2], dtype),
        "attn_out_norm": {"w": jnp.ones((cfg.d_model,), dtype)},
        "ssm_out_norm": {"w": jnp.ones((cfg.d_model,), dtype)},
        "ln2": L.norm_params(cfg, ks[3], cfg.d_model, dtype),
        "ffn": L.ffn_params(cfg, ks[4], dtype),
    }


def init_params(rng, cfg):
    dtype = cfg.compute_dtype
    k_emb, k_layers, k_head = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    return {
        "embed": L.embed_init(k_emb, (cfg.padded_vocab, cfg.d_model), dtype),
        "layers": jax.vmap(lambda k: _layer_init(cfg, k, dtype))(layer_keys),
        "final_norm": L.norm_params(cfg, k_head, cfg.d_model, dtype),
        "lm_head": L.dense_init(k_head, (cfg.d_model, cfg.padded_vocab), dtype),
    }


# --------------------------------------------------------------------------
# mamba branch
# --------------------------------------------------------------------------

def _causal_conv(mp, x1):
    """x1: (B,T,di) — 4-tap depthwise causal conv via shifts."""
    out = x1 * mp["conv_w"][-1]
    for tap in range(1, _CONV_W):
        shifted = jnp.pad(x1, ((0, 0), (tap, 0), (0, 0)))[:, :-tap]
        out = out + shifted * mp["conv_w"][-1 - tap]
    return out + mp["conv_b"]


def _ssm_scan(mp, x1, dt, Bm, Cm, h0, unroll: int = 16):
    """h0: (B,di,n) fp32. Returns (y (B,T,di), h_T).

    §Perf iteration A: ``unroll`` amortizes the HBM round-trip of the
    (B,di,n) state across unrolled steps (see rwkv6._wkv_scan)."""
    A = -jnp.exp(mp["A_log"])                             # (di,n)

    def step(h, inp):
        x_t, dt_t, B_t, C_t = inp                         # (B,di),(B,di),(B,n),(B,n)
        dA = jnp.exp(dt_t[..., None] * A)                 # (B,di,n)
        h = dA * h + (dt_t * x_t)[..., None] * B_t[:, None, :]
        y = jnp.sum(h * C_t[:, None, :], axis=-1) + mp["D"] * x_t
        return h, y

    xs = (jnp.moveaxis(x1.astype(jnp.float32), 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(Bm, 1, 0), jnp.moveaxis(Cm, 1, 0))
    T = xs[0].shape[0]
    h_T, y = jax.lax.scan(step, h0, xs,
                          unroll=unroll if T % unroll == 0 else 1)
    return jnp.moveaxis(y, 0, 1), h_T


def _mamba_forward(mp, x, h0):
    """Returns (out, h_T, x1_raw_tail) — the tail is the PRE-conv x1 inputs
    (last CONV_W-1 steps) the decode path needs to resume the conv."""
    xz = x @ mp["Win"]
    x1_raw, z = jnp.split(xz, 2, axis=-1)
    x1 = jax.nn.silu(_causal_conv(mp, x1_raw))
    dt = jax.nn.softplus(
        ((x1 @ mp["Wdt1"]) @ mp["Wdt2"]).astype(jnp.float32) + mp["dt_bias"])
    Bm = (x1 @ mp["WB"]).astype(jnp.float32)
    Cm = (x1 @ mp["WC"]).astype(jnp.float32)
    y, h_T = _ssm_scan(mp, x1, dt, Bm, Cm, h0)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    tail = x1_raw[:, -(_CONV_W - 1):]
    return y @ mp["Wout"], h_T, tail


# --------------------------------------------------------------------------
# forward / loss / decode
# --------------------------------------------------------------------------

def forward(params, batch, cfg, *, return_cache: bool = False):
    x = params["embed"][batch["tokens"]]
    B, T, d = x.shape
    n = cfg.ssm_state
    h0 = jnp.zeros((B, d, n), jnp.float32)
    positions = jnp.arange(T)[None, :]

    def body(h, lp):
        z = L.apply_norm(cfg, h, lp["ln1"])
        a_out, (k, v) = L.full_attention(
            cfg, lp["attn"], z, positions=positions, causal=True,
            sliding_window=cfg.sliding_window)
        m_out, h_T, conv_tail = _mamba_forward(lp["mamba"], z, h0)
        fused = 0.5 * (L.rmsnorm(a_out, lp["attn_out_norm"]["w"])
                       + L.rmsnorm(m_out, lp["ssm_out_norm"]["w"]))
        h = h + fused
        z = L.apply_norm(cfg, h, lp["ln2"])
        h = h + L.ffn(cfg, lp["ffn"], z)
        ys = (k, v, h_T, conv_tail) if return_cache else None
        return h, ys

    if cfg.remat and not return_cache:
        body = jax.checkpoint(body, prevent_cse=False)
    x, caches = jax.lax.scan(body, x, params["layers"])
    x = L.apply_norm(cfg, x, params["final_norm"])
    logits = x @ params["lm_head"]
    cache = None
    if return_cache:
        # conv cache = last CONV_W-1 pre-conv x1 inputs per layer
        cache = {"k": caches[0], "v": caches[1], "h": caches[2],
                 "conv": caches[3], "step": jnp.asarray(T, jnp.int32)}
    return logits, cache, jnp.float32(0.0)


def loss_fn(params, batch, cfg):
    logits, _, _ = forward(params, batch, cfg)
    return L.softmax_xent(logits, batch["labels"])


def prefill(params, batch, cfg):
    logits, cache, _ = forward(params, batch, cfg, return_cache=True)
    return logits, cache


def init_cache(cfg, batch_size: int, seq_len: int, dtype=None):
    dtype = dtype or cfg.compute_dtype
    Sc = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    Lyr, d, n = cfg.num_layers, cfg.d_model, cfg.ssm_state
    kv = (Lyr, batch_size, Sc, cfg.num_kv_heads, cfg.hd)
    return {
        "k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
        "h": jnp.zeros((Lyr, batch_size, d, n), jnp.float32),
        "conv": jnp.zeros((Lyr, batch_size, _CONV_W - 1, d), dtype),
        "step": jnp.asarray(0, jnp.int32),
    }


def _mamba_decode(mp, x, h, conv_tail):
    """x: (B,1,d); conv_tail: (B,CONV_W-1,di) previous x1-inputs."""
    xz = x @ mp["Win"]
    x1_new, z = jnp.split(xz, 2, axis=-1)                 # (B,1,di)
    window = jnp.concatenate([conv_tail, x1_new], axis=1)  # (B,CONV_W,di)
    c = jnp.einsum("btd,td->bd", window, mp["conv_w"]) + mp["conv_b"]
    x1 = jax.nn.silu(c)[:, None, :]                       # (B,1,di)
    dt = jax.nn.softplus(
        ((x1 @ mp["Wdt1"]) @ mp["Wdt2"]).astype(jnp.float32) + mp["dt_bias"])
    Bm = (x1 @ mp["WB"]).astype(jnp.float32)
    Cm = (x1 @ mp["WC"]).astype(jnp.float32)
    y, h_n = _ssm_scan(mp, x1, dt, Bm, Cm, h)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    return y @ mp["Wout"], h_n, window[:, 1:]


def decode_step(params, cache, batch, cfg):
    x = params["embed"][batch["tokens"]]
    step = cache["step"]

    def body(h, lp_state):
        lp, ck, cv, hs, conv = lp_state
        z = L.apply_norm(cfg, h, lp["ln1"])
        a_out, nk, nv = L.decode_attention(
            cfg, lp["attn"], z, ck, cv, step,
            sliding_window=cfg.sliding_window)
        m_out, h_n, conv_n = _mamba_decode(lp["mamba"], z, hs, conv)
        fused = 0.5 * (L.rmsnorm(a_out, lp["attn_out_norm"]["w"])
                       + L.rmsnorm(m_out, lp["ssm_out_norm"]["w"]))
        h = h + fused
        z = L.apply_norm(cfg, h, lp["ln2"])
        h = h + L.ffn(cfg, lp["ffn"], z)
        return h, (nk, nv, h_n, conv_n)

    x, (nk, nv, nh, nconv) = jax.lax.scan(
        body, x, (params["layers"], cache["k"], cache["v"],
                  cache["h"], cache["conv"]))
    x = L.apply_norm(cfg, x, params["final_norm"])
    logits = x @ params["lm_head"]
    return logits, {"k": nk, "v": nv, "h": nh, "conv": nconv,
                    "step": step + 1}
