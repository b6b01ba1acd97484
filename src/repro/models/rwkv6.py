"""RWKV6 "Finch" — attention-free RNN with data-dependent decay.

Faithful-to-structure implementation of the RWKV6 block [arXiv:2404.05892]:
  * time-mix with ddlerp (data-dependent token-shift interpolation via a
    low-rank adapter over 5 targets w/k/v/r/g),
  * data-dependent per-channel decay  w_t = exp(-exp(w0 + lora(x_w))),
  * multi-head WKV linear-attention recurrence with bonus ``u``:
        S_t = diag(w_t) S_{t-1} + k_t v_t^T
        o_t = r_t · (S_{t-1} + diag(u) k_t v_t^T)
  * channel-mix with squared-ReLU.

Training runs the recurrence as a ``lax.scan`` over time inside a
``lax.scan`` over layers; decode carries (S, token-shift, channel-shift)
state — O(1) per token, which is why rwkv6 runs the ``long_500k`` shape
natively.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L


def _heads(cfg):
    return cfg.d_model // cfg.rwkv_head_dim


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _layer_init(cfg, key, dtype):
    d, ff, lora = cfg.d_model, cfg.d_ff, cfg.rwkv_lora_dim
    H, hd = _heads(cfg), cfg.rwkv_head_dim
    ks = jax.random.split(key, 12)
    tmix = {
        "mu_base": jnp.full((d,), 0.5, dtype),
        "mus": jnp.full((5, d), 0.5, dtype),
        "W1": L.dense_init(ks[0], (d, 5 * lora), dtype),
        "W2": L.dense_init(ks[1], (5, lora, d), dtype),
        "w0": jnp.full((d,), -6.0, jnp.float32),     # slow decay at init
        "dw1": L.dense_init(ks[2], (d, 2 * lora), dtype),
        "dw2": L.dense_init(ks[3], (2 * lora, d), dtype),
        "u": jnp.zeros((H, hd), jnp.float32),
        "Wr": L.dense_init(ks[4], (d, d), dtype),
        "Wk": L.dense_init(ks[5], (d, d), dtype),
        "Wv": L.dense_init(ks[6], (d, d), dtype),
        "Wg": L.dense_init(ks[7], (d, d), dtype),
        "Wo": L.dense_init(ks[8], (d, d), dtype),
        "gn_w": jnp.ones((d,), dtype),
        "gn_b": jnp.zeros((d,), dtype),
    }
    cmix = {
        "mu_k": jnp.full((d,), 0.5, dtype),
        "mu_r": jnp.full((d,), 0.5, dtype),
        "Wk": L.dense_init(ks[9], (d, ff), dtype),
        "Wv": L.dense_init(ks[10], (ff, d), dtype),
        "Wr": L.dense_init(ks[11], (d, d), dtype),
    }
    return {
        "ln1": {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)},
        "ln2": {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)},
        "tmix": tmix,
        "cmix": cmix,
    }


def init_params(rng, cfg):
    dtype = cfg.compute_dtype
    d = cfg.d_model
    k_emb, k_layers, k_head = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    return {
        "embed": L.embed_init(k_emb, (cfg.padded_vocab, d), dtype),
        "ln0": {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)},
        "layers": jax.vmap(lambda k: _layer_init(cfg, k, dtype))(layer_keys),
        "final_norm": {"w": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)},
        "lm_head": L.dense_init(k_head, (d, cfg.padded_vocab), dtype),
    }


# --------------------------------------------------------------------------
# block pieces
# --------------------------------------------------------------------------

def _ddlerp(tp, x, xx):
    """Data-dependent lerp -> (x_w, x_k, x_v, x_r, x_g), each (B,S,d)."""
    delta = xx - x
    base = x + delta * tp["mu_base"]
    lo = jnp.tanh(base @ tp["W1"])                      # (B,S,5*lora)
    B, S, _ = lo.shape
    lo = lo.reshape(B, S, 5, -1)
    off = jnp.einsum("bstl,tld->bstd", lo, tp["W2"])    # (B,S,5,d)
    mix = tp["mus"][None, None] + off
    outs = x[:, :, None, :] + delta[:, :, None, :] * mix
    return tuple(outs[:, :, i, :] for i in range(5))


def _decay(tp, x_w):
    """Data-dependent decay w_t in (0,1), fp32, shape of x_w."""
    ddd = jnp.tanh(x_w @ tp["dw1"]) @ tp["dw2"]
    return jnp.exp(-jnp.exp(tp["w0"] + ddd.astype(jnp.float32)))


def _wkv_scan(r, k, v, w, u, S0, unroll: int = 16):
    """r,k,v,w: (B,T,H,hd); u: (H,hd); S0: (B,H,hd,hd) fp32 -> (o, S_T).

    §Perf iteration A (EXPERIMENTS.md): ``unroll`` fuses consecutive steps
    into one loop body so the (B,H,hd,hd) state is materialized to HBM
    once per ``unroll`` steps instead of every step — the sequential-scan
    HBM-traffic term drops ~unroll×."""
    def step(S, inp):
        r_t, k_t, v_t, w_t = inp                                   # (B,H,hd)
        a = k_t[..., :, None] * v_t[..., None, :]                  # (B,H,hd,hd)
        o = jnp.sum((S + u[None, :, :, None] * a) * r_t[..., :, None], axis=-2)
        S = w_t[..., :, None] * S + a
        return S, o
    xs = tuple(jnp.moveaxis(t.astype(jnp.float32), 1, 0) for t in (r, k, v, w))
    T = xs[0].shape[0]
    S_T, o = jax.lax.scan(step, S0, xs,
                          unroll=unroll if T % unroll == 0 else 1)
    return jnp.moveaxis(o, 0, 1), S_T                              # (B,T,H,hd)


def _group_norm(x, w, b, H, eps=1e-5):
    """Per-head layernorm over hd. x: (..., d) viewed as (..., H, hd)."""
    shp = x.shape
    xf = x.astype(jnp.float32).reshape(shp[:-1] + (H, shp[-1] // H))
    mu = xf.mean(-1, keepdims=True)
    var = xf.var(-1, keepdims=True)
    xf = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (xf.reshape(shp) * w + b).astype(x.dtype)


def _time_mix(cfg, tp, x, xx, S0):
    """x: (B,T,d); xx: token-shifted x; S0: (B,H,hd,hd)."""
    B, T, d = x.shape
    H, hd = _heads(cfg), cfg.rwkv_head_dim
    x_w, x_k, x_v, x_r, x_g = _ddlerp(tp, x, xx)
    r = (x_r @ tp["Wr"]).reshape(B, T, H, hd)
    k = (x_k @ tp["Wk"]).reshape(B, T, H, hd)
    v = (x_v @ tp["Wv"]).reshape(B, T, H, hd)
    g = jax.nn.silu(x_g @ tp["Wg"])
    w = _decay(tp, x_w).reshape(B, T, H, hd)
    o, S_T = _wkv_scan(r, k, v, w, tp["u"], S0)
    o = o.reshape(B, T, d).astype(x.dtype)
    o = _group_norm(o, tp["gn_w"], tp["gn_b"], H)
    return (o * g) @ tp["Wo"], S_T


def _channel_mix(tp, x, xx):
    x_k = x + (xx - x) * tp["mu_k"]
    x_r = x + (xx - x) * tp["mu_r"]
    k = jnp.square(jax.nn.relu(x_k @ tp["Wk"]))
    return jax.nn.sigmoid(x_r @ tp["Wr"]) * (k @ tp["Wv"])


def _shift(x):
    """Token shift: previous token, zeros at t=0. x: (B,T,d)."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


# --------------------------------------------------------------------------
# forward / loss / decode
# --------------------------------------------------------------------------

def forward(params, batch, cfg, *, return_cache: bool = False):
    x = params["embed"][batch["tokens"]]
    x = L.layernorm(x, params["ln0"]["w"], params["ln0"]["b"])
    B, T, d = x.shape
    H, hd = _heads(cfg), cfg.rwkv_head_dim
    S0 = jnp.zeros((B, H, hd, hd), jnp.float32)

    def body(h, lp):
        z1 = L.layernorm(h, lp["ln1"]["w"], lp["ln1"]["b"])
        t_out, S_T = _time_mix(cfg, lp["tmix"], z1, _shift(z1), S0)
        h = h + t_out
        z2 = L.layernorm(h, lp["ln2"]["w"], lp["ln2"]["b"])
        h = h + _channel_mix(lp["cmix"], z2, _shift(z2))
        # decode resumes from the LAST TOKEN's normed inputs per sub-block
        ys = (S_T, z1[:, -1], z2[:, -1]) if return_cache else None
        return h, ys

    if cfg.remat and not return_cache:
        body = jax.checkpoint(body, prevent_cse=False)
    x, caches = jax.lax.scan(body, x, params["layers"])
    x = L.layernorm(x, params["final_norm"]["w"], params["final_norm"]["b"])
    logits = x @ params["lm_head"]
    cache = None
    if return_cache:
        cache = {"S": caches[0], "tshift": caches[1], "cshift": caches[2],
                 "step": jnp.asarray(T, jnp.int32)}
    return logits, cache, jnp.float32(0.0)


def loss_fn(params, batch, cfg):
    logits, _, _ = forward(params, batch, cfg)
    return L.softmax_xent(logits, batch["labels"])


def prefill(params, batch, cfg):
    logits, cache, _ = forward(params, batch, cfg, return_cache=True)
    return logits, cache


def init_cache(cfg, batch_size: int, seq_len: int, dtype=None):
    H, hd, d, Lyr = _heads(cfg), cfg.rwkv_head_dim, cfg.d_model, cfg.num_layers
    return {
        "S": jnp.zeros((Lyr, batch_size, H, hd, hd), jnp.float32),
        "tshift": jnp.zeros((Lyr, batch_size, d), cfg.compute_dtype),
        "cshift": jnp.zeros((Lyr, batch_size, d), cfg.compute_dtype),
        "step": jnp.asarray(0, jnp.int32),
    }


def decode_step(params, cache, batch, cfg):
    x = params["embed"][batch["tokens"]]                 # (B,1,d)
    x = L.layernorm(x, params["ln0"]["w"], params["ln0"]["b"])

    def body(h, lp_state):
        lp, S, tsh, csh = lp_state
        z = L.layernorm(h, lp["ln1"]["w"], lp["ln1"]["b"])
        xx = tsh[:, None, :].astype(z.dtype)             # previous token
        t_out, S_n = _time_mix(cfg, lp["tmix"], z, xx, S)
        new_tsh = z[:, 0]
        h = h + t_out
        z = L.layernorm(h, lp["ln2"]["w"], lp["ln2"]["b"])
        h = h + _channel_mix(lp["cmix"], z, csh[:, None, :].astype(z.dtype))
        return h, (S_n, new_tsh, z[:, 0])

    x, (S_n, tsh_n, csh_n) = jax.lax.scan(
        body, x, (params["layers"], cache["S"], cache["tshift"], cache["cshift"]))
    x = L.layernorm(x, params["final_norm"]["w"], params["final_norm"]["b"])
    logits = x @ params["lm_head"]
    return logits, {"S": S_n, "tshift": tsh_n, "cshift": csh_n,
                    "step": cache["step"] + 1}
