"""Top-k mixture-of-experts FFN with capacity-based dispatch.

Dispatch uses scatter/gather into a fixed (E, C, d) buffer (Switch/Mixtral
style) so compiled FLOPs are proportional to *active* experts — the einsum
one-hot dispatch tensor (T, E, C) is never materialized. Expert tensors are
laid out (E, d, ff) so the expert dim can be sharded for expert parallelism
(arctic-480b: E over the "data" axis, ff over "model").

Aux loss is the standard Switch load-balance term
``E * sum_e f_e * p_e`` (f_e = fraction of tokens routed to e, p_e = mean
router prob of e).

``held_expert_ffn`` is the DeepSeek-V3 expert layer (``router="sigmoid"``)
as one chip of an expert-parallel group runs it: it routes every token
over all ``num_experts`` (sigmoid scores; a correction bias that only
chooses; top-k weights renormalised and scaled), computes only the
``experts_held`` experts from ``expert_offset`` on, dropless, with a
grouped matmul over the rows routed to them, and adds the shared experts.
What the absent experts would add is left to the chips that hold them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.models import layers as L


def moe_params(cfg, key, dtype):
    E, d, ff = cfg.num_experts, cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 5)
    if cfg.router == "sigmoid":
        Eh = cfg.experts_held or E
        return {
            "router": L.dense_init(ks[0], (d, E), jnp.float32),
            "wg": L.dense_init(ks[1], (Eh, d, ff), dtype),
            "wu": L.dense_init(ks[2], (Eh, d, ff), dtype),
            "wd": L.dense_init(ks[3], (Eh, ff, d), dtype),
            "shared": L.ffn_params(cfg, ks[4], dtype,
                                   ff=ff * cfg.shared_experts),
        }
    p = {
        "router": L.dense_init(ks[0], (d, E), jnp.float32),
        "wg": L.dense_init(ks[1], (E, d, ff), dtype),
        "wu": L.dense_init(ks[2], (E, d, ff), dtype),
        "wd": L.dense_init(ks[3], (E, ff, d), dtype),
    }
    if cfg.moe_dense_residual:
        p["dense"] = L.ffn_params(cfg, ks[4], dtype)
    return p


def capacity(cfg, tokens: int) -> int:
    c = int(cfg.capacity_factor * tokens * cfg.top_k / cfg.num_experts)
    return max(4, min(c, tokens))


# --------------------------------------------------------------------------
# token<->slot permutations with custom VJPs: the BACKWARD of each gather
# is ALSO a gather through the inverse permutation. Plain AD of a gather
# emits a scatter into an unsharded zeros buffer, which GSPMD replicates
# and all-reduces (43 GB per layer on granite-moe before this).
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(k, xt, tok_for_slot, valid, slot_c, keep):
    """xt: (T, d) -> slot-major (E*C, d); bwd gathers via slot_c."""
    return xt[tok_for_slot] * valid[:, None].astype(xt.dtype)


def _dispatch_fwd(k, xt, tok_for_slot, valid, slot_c, keep):
    out = _dispatch(k, xt, tok_for_slot, valid, slot_c, keep)
    return out, (slot_c, keep)


def _dispatch_bwd(k, res, dxe):
    slot_c, keep = res
    d = dxe.shape[-1]
    dxt = dxe[slot_c] * keep[:, None].astype(dxe.dtype)        # (Tk, d)
    return (dxt.reshape(-1, k, d).sum(axis=1), None, None, None, None)


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine(k, ye, w, slot_c, choice_for_slot, valid):
    """ye: (E*C, d), w: (Tk,) -> (T, d); bwd gathers via choice_for_slot."""
    yt = ye[slot_c] * w[:, None].astype(ye.dtype)
    return yt.reshape(-1, k, ye.shape[-1]).sum(axis=1)


def _combine_fwd(k, ye, w, slot_c, choice_for_slot, valid):
    return _combine(k, ye, w, slot_c, choice_for_slot, valid), \
        (ye, w, slot_c, choice_for_slot, valid)


def _combine_bwd(k, res, dout):
    ye, w, slot_c, choice_for_slot, valid = res
    dyt = jnp.repeat(dout, k, axis=0)                           # (Tk, d)
    vmask = valid[:, None].astype(dyt.dtype)
    dye = (dyt[choice_for_slot] * vmask
           * w[choice_for_slot][:, None].astype(dyt.dtype))
    dw = jnp.sum(dyt.astype(jnp.float32)
                 * ye[slot_c].astype(jnp.float32), axis=-1)
    return dye.astype(ye.dtype), dw.astype(w.dtype), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_ffn(cfg, p, x):
    """x: (B, S, d) -> (out (B,S,d), aux_loss scalar fp32).

    §Perf iteration D (EXPERIMENTS.md): dispatch is GATHER-based. The
    original scatter of (E·C, d) token buffers had no sharding provenance
    (jnp.zeros) so GSPMD replicated it and ALL-REDUCED 43 GB per layer.
    Here only an int32/bool inverse-permutation of size E·C+1 is ever
    scattered; token payloads move through gathers (sharding follows the
    source), and the combine is a reshape-sum (tok_idx = repeat(arange)),
    no scatter at all.
    """
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    T = B * S
    C = capacity(cfg, T)
    xt = x.reshape(T, d)

    gates = jax.nn.softmax(xt.astype(jnp.float32) @ p["router"], axis=-1)  # (T,E)
    topv, topi = jax.lax.top_k(gates, k)                                   # (T,k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # position of each (token, choice) inside its expert's capacity buffer
    flat_e = topi.reshape(T * k)                                # (Tk,)
    mask = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)           # (Tk, E)
    pos = jnp.cumsum(mask, axis=0) - mask                       # (Tk, E)
    flat_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = flat_pos < C
    # overflow routes to a dump slot (index E*C) so it never collides
    slot = jnp.where(keep, flat_e * C + flat_pos, E * C)        # (Tk,)

    # inverse permutation: which token (choice) fills each capacity slot
    tok_idx = jnp.repeat(jnp.arange(T), k)
    tok_for_slot = jnp.zeros((E * C + 1,), jnp.int32).at[slot].set(
        tok_idx, mode="drop")
    choice_for_slot = jnp.zeros((E * C + 1,), jnp.int32).at[slot].set(
        jnp.arange(T * k), mode="drop")
    valid = jnp.zeros((E * C + 1,), jnp.bool_).at[slot].set(
        keep, mode="drop")
    slot_c = jnp.minimum(slot, E * C - 1)

    w = (topv.reshape(T * k) * keep).astype(x.dtype)            # (Tk,)
    if cfg.moe_dispatch == "gather":
        xe = _dispatch(k, xt, tok_for_slot[:E * C], valid[:E * C],
                       slot_c, keep).reshape(E, C, d)
    else:  # scatter path (measured alternative; see EXPERIMENTS §Perf D)
        tok_all = jnp.repeat(jnp.arange(T), k)
        xd = xt[tok_all] * keep[:, None].astype(x.dtype)
        xe = jnp.zeros((E * C, d), x.dtype).at[
            jnp.minimum(slot, E * C - 1)].add(
            xd * keep[:, None].astype(x.dtype)).reshape(E, C, d)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, p["wg"])) \
        * jnp.einsum("ecd,edf->ecf", xe, p["wu"])
    ye = jnp.einsum("ecf,efd->ecd", h, p["wd"]).reshape(E * C, d)

    if cfg.moe_dispatch == "gather":
        out = _combine(k, ye, w, slot_c, choice_for_slot[:E * C],
                       valid[:E * C])
    else:
        yt = ye[jnp.minimum(slot, E * C - 1)] * w[:, None]
        out = yt.reshape(T, k, d).sum(axis=1)

    if cfg.moe_dense_residual:
        out = out + L.ffn(cfg, p["dense"], xt)

    # load-balance aux
    f_e = jnp.mean(jax.nn.one_hot(topi, E, dtype=jnp.float32).sum(1), axis=0)  # (E,)
    p_e = jnp.mean(gates, axis=0)
    aux = E * jnp.sum(f_e / k * p_e)
    return out.reshape(B, S, d), aux


# --------------------------------------------------------------------------
# sigmoid-routed experts held by one chip, dropless, plus shared experts
# --------------------------------------------------------------------------

def router_bias(cfg, n_layers: int):
    """The routers' correction biases, (n_layers, num_experts): a seeded
    constant outside the trained parameters, so never federated."""
    return cfg.router_bias_std * jax.random.normal(
        jax.random.PRNGKey(cfg.router_bias_seed),
        (n_layers, cfg.num_experts), jnp.float32)


def sigmoid_route(cfg, router, xt, bias):
    """xt (T, d) -> (experts (T, k) int32, weights (T, k) f32): the top-k
    of sigmoid score + bias; weights are the chosen sigmoid scores,
    renormalised and times ``routed_scale``."""
    scores = jax.nn.sigmoid(xt.astype(jnp.float32) @ router)
    _, top = jax.lax.top_k(scores + bias, cfg.top_k)
    w = jnp.take_along_axis(scores, top, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return top, w * cfg.routed_scale


@jax.custom_vjp
def _permute(x, perm, inv):
    """x[perm] for a permutation; its backward is the gather by ``inv``."""
    return x[perm]


_permute.defvjp(lambda x, perm, inv: (x[perm], inv),
                lambda inv, g: (g[inv], None, None))


def held_expert_ffn(cfg, p, x, bias):
    """x (B, S, d) -> (out (B, S, d), stats). Every (token, choice) pair
    gets a row of the sorted buffer, so nothing is dropped whatever the
    routing; the grouped matmul multiplies only the held experts' rows.
    stats: ``expert_rows``, the rows routed to held experts."""
    from repro.kernels import grouped_matmul
    B, S, d = x.shape
    T, k, Eh = B * S, cfg.top_k, cfg.experts_held or cfg.num_experts
    xt = x.reshape(T, d)
    with jax.named_scope("moe_route"):
        top, w = sigmoid_route(cfg, p["router"], xt, bias)
        local = top.reshape(T * k) - cfg.expert_offset
        held = (local >= 0) & (local < Eh)
        group = jnp.where(held, local, Eh)
        order = jnp.argsort(group, stable=True).astype(jnp.int32)
        inv = jnp.zeros_like(order).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32))
        sizes = jnp.bincount(group, length=Eh + 1)[:Eh].astype(jnp.int32)
        xs = _permute(jnp.repeat(xt, k, axis=0), order, inv)
        ws = jnp.where(held, w.reshape(T * k), 0.0)[order]
    with jax.named_scope("moe_experts"):
        gmm = grouped_matmul.expert_matmul
        h = jax.nn.silu(gmm(xs, p["wg"], sizes)) * gmm(xs, p["wu"], sizes)
        ys = gmm(h, p["wd"], sizes) * ws[:, None]
        routed = _permute(ys, inv, order).reshape(T, k, d).sum(axis=1)
    with jax.named_scope("moe_shared"):
        shared = L.ffn(cfg, p["shared"], xt)
    stats = {"expert_rows": jnp.sum(sizes)}
    return (routed.astype(x.dtype) + shared).reshape(B, S, d), stats
