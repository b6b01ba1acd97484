"""Decoder-only transformer (dense, MoE, VLM families).

Per-layer parameters are stacked on a leading layer axis and consumed via
``jax.lax.scan``; the layer body is optionally wrapped in ``jax.checkpoint``
(remat) for training. The same stack serves:

  dense — llama-style (granite-34b, qwen2, stablelm, phi3)
  moe   — FFN replaced by top-k mixture of experts (granite-moe, arctic)
  vlm   — InternVL2: stubbed patch embeddings are projected and prepended
          to the token embeddings (internvl2-2b)
  moe with ``attention="mla"`` — DeepSeek-V3 (moonlight-16b-a3b): latent
          attention, a leading stack of ``dense_layers`` dense layers
          (FFN ``dense_d_ff``) and then sigmoid-routed MoE layers with
          shared experts, each its own scanned stack. Training and
          evaluation only: there is no prefill/decode with a latent cache.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import moe as moe_mod


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _layer_init(cfg, key, dtype, dense=False):
    ks = jax.random.split(key, 4)
    mla = cfg.attention == "mla"
    p = {
        "ln1": L.norm_params(cfg, ks[0], cfg.d_model, dtype),
        "attn": (L.mla_params(cfg, ks[1], dtype) if mla
                 else L.attn_params(cfg, ks[1], dtype)),
        "ln2": L.norm_params(cfg, ks[2], cfg.d_model, dtype),
    }
    if cfg.num_experts and not dense:
        p["moe"] = moe_mod.moe_params(cfg, ks[3], dtype)
    else:
        p["ffn"] = L.ffn_params(cfg, ks[3], dtype,
                                ff=cfg.dense_d_ff if dense else None)
    return p


def _init_mla(rng, cfg):
    """The latent-attention family's parameters: leaf i of the tree (dict
    keys sorted, as ``jax.tree`` orders them) is drawn from
    ``fold_in(rng, i)``: norm weights 1, the embedding N(0, 0.02²), every
    other matrix N(0, 1/fan_in) with fan_in its second-to-last axis."""
    dtype = cfg.compute_dtype
    n_moe = cfg.num_layers - cfg.dense_layers

    def stack(n, dense):
        return jax.vmap(lambda k: _layer_init(cfg, k, dtype, dense))(
            jax.random.split(rng, n))

    shapes = jax.eval_shape(lambda: {
        "embed": jnp.zeros((cfg.padded_vocab, cfg.d_model), dtype),
        "lm_head": jnp.zeros((cfg.d_model, cfg.padded_vocab), dtype),
        "final_norm": L.norm_params(cfg, rng, cfg.d_model, dtype),
        "dense_layers": stack(cfg.dense_layers, True),
        "layers": stack(n_moe, False)})
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = []
    for i, (path, sds) in enumerate(flat):
        name = jax.tree_util.keystr(path)
        if name.endswith("['w']"):
            leaves.append(jnp.ones(sds.shape, sds.dtype))
            continue
        std = 0.02 if name == "['embed']" else sds.shape[-2] ** -0.5
        leaves.append((std * jax.random.normal(
            jax.random.fold_in(rng, i), sds.shape, jnp.float32)
        ).astype(sds.dtype))
    return jax.tree.unflatten(treedef, leaves)


def init_params(rng, cfg):
    if cfg.attention == "mla":
        return _init_mla(rng, cfg)
    dtype = cfg.compute_dtype
    k_emb, k_layers, k_head, k_proj = jax.random.split(rng, 4)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    params = {
        "embed": L.embed_init(k_emb, (cfg.padded_vocab, cfg.d_model), dtype),
        "layers": jax.vmap(lambda k: _layer_init(cfg, k, dtype))(layer_keys),
        "final_norm": L.norm_params(cfg, k_head, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(k_head, (cfg.d_model, cfg.padded_vocab), dtype)
    if cfg.family == "vlm":
        params["patch_proj"] = L.dense_init(k_proj, (cfg.d_model, cfg.d_model), dtype)
    return params


# --------------------------------------------------------------------------
# forward (training / prefill)
# --------------------------------------------------------------------------

def _embed_inputs(params, cfg, batch):
    x = params["embed"][batch["tokens"]]
    if cfg.family == "vlm":
        patches = batch["patch_embeds"].astype(x.dtype) @ params["patch_proj"]
        x = jnp.concatenate([patches, x], axis=1)
    return x


def _forward_mla(params, batch, cfg):
    """Latent-attention family: the dense prefix's scan, then the MoE
    layers' scan. Returns (logits, None, stats), stats summed over the
    MoE layers (``moe.held_expert_ffn``)."""
    x = params["embed"][batch["tokens"]]
    positions = jnp.arange(x.shape[1])[None, :]

    def block(h, lp):
        with jax.named_scope("mla_block"):
            h = h + L.mla_attention(cfg, lp["attn"],
                                    L.apply_norm(cfg, h, lp["ln1"]),
                                    positions)
        return h, L.apply_norm(cfg, h, lp["ln2"])

    def dense_body(h, lp):
        h, f_in = block(h, lp)
        return h + L.ffn(cfg, lp["ffn"], f_in), None

    def moe_body(carry, lp_bias):
        h, stats = carry
        lp, bias = lp_bias
        h, f_in = block(h, lp)
        f_out, s = moe_mod.held_expert_ffn(cfg, lp["moe"], f_in, bias)
        return (h + f_out, jax.tree.map(jnp.add, stats, s)), None

    if cfg.remat:
        dense_body = jax.checkpoint(dense_body, prevent_cse=False)
        moe_body = jax.checkpoint(moe_body, prevent_cse=False)
    x, _ = jax.lax.scan(dense_body, x, params["dense_layers"])
    n_moe = cfg.num_layers - cfg.dense_layers
    zero = jnp.zeros((), jnp.int32)
    (x, stats), _ = jax.lax.scan(
        moe_body, (x, {"expert_rows": zero}),
        (params["layers"], moe_mod.router_bias(cfg, n_moe)))
    x = L.apply_norm(cfg, x, params["final_norm"])
    with jax.named_scope("lm_head"):
        logits = x @ params["lm_head"]
    return logits, None, stats


def forward(params, batch, cfg, *, return_cache: bool = False):
    """Returns (logits, cache_or_None, aux_loss)."""
    if cfg.attention == "mla":
        if return_cache:
            raise NotImplementedError(
                f"{cfg.name}: latent attention is trained and evaluated "
                "here; it has no prefill/decode cache")
        return _forward_mla(params, batch, cfg)
    x = _embed_inputs(params, cfg, batch)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]

    def body(carry, lp):
        h, aux = carry
        a_in = L.apply_norm(cfg, h, lp["ln1"])
        a_out, (k, v) = L.full_attention(
            cfg, lp["attn"], a_in, positions=positions, causal=True,
            sliding_window=cfg.sliding_window)
        h = h + a_out
        f_in = L.apply_norm(cfg, h, lp["ln2"])
        if cfg.num_experts:
            f_out, moe_aux = moe_mod.moe_ffn(cfg, lp["moe"], f_in)
            aux = aux + moe_aux
        else:
            f_out = L.ffn(cfg, lp["ffn"], f_in)
        h = h + f_out
        ys = (k, v) if return_cache else None
        return (h, aux), ys

    if cfg.remat and not return_cache:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), caches = jax.lax.scan(body, (x, jnp.float32(0.0)), params["layers"])
    x = L.apply_norm(cfg, x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    cache = None
    if return_cache:
        cache = {"k": caches[0], "v": caches[1],
                 "step": jnp.asarray(S, jnp.int32)}
    return logits, cache, aux


def loss_and_stats(params, batch, cfg):
    """(loss, stats): stats are the expert layer's counters (an empty
    dict for the families that have none)."""
    logits, _, aux = forward(params, batch, cfg)
    if cfg.family == "vlm":  # drop patch positions from the LM loss
        logits = logits[:, cfg.num_patches:]
    # labels are the next tokens, aligned with the logits
    # (data.synthetic.make_lm_tokens)
    xent = L.softmax_xent(logits, batch["labels"])
    if cfg.attention == "mla":   # no sequence aux loss; aux holds counters
        return xent, aux
    return xent + cfg.router_aux_weight * aux, {}


def loss_fn(params, batch, cfg):
    return loss_and_stats(params, batch, cfg)[0]


def prefill(params, batch, cfg):
    logits, cache, _ = forward(params, batch, cfg, return_cache=True)
    return logits, cache


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------

def init_cache(cfg, batch_size: int, seq_len: int, dtype=None):
    dtype = dtype or cfg.compute_dtype
    Sc = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    shape = (cfg.num_layers, batch_size, Sc, cfg.num_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "step": jnp.asarray(0, jnp.int32)}


def decode_step(params, cache, batch, cfg):
    """batch: {"tokens": (B,1)}. Returns (logits (B,1,V), new_cache)."""
    x = params["embed"][batch["tokens"]]
    step = cache["step"]

    def body(h, lp_and_cache):
        lp, ck, cv = lp_and_cache
        a_in = L.apply_norm(cfg, h, lp["ln1"])
        a_out, nk, nv = L.decode_attention(
            cfg, lp["attn"], a_in, ck, cv, step,
            sliding_window=cfg.sliding_window)
        h = h + a_out
        f_in = L.apply_norm(cfg, h, lp["ln2"])
        if cfg.num_experts:
            f_out, _ = moe_mod.moe_ffn(cfg, lp["moe"], f_in)
        else:
            f_out = L.ffn(cfg, lp["ffn"], f_in)
        return h + f_out, (nk, nv)

    x, (nk, nv) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    x = L.apply_norm(cfg, x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head
    return logits, {"k": nk, "v": nv, "step": step + 1}
