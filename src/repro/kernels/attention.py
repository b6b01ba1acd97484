"""Causal attention core with unequal query/key and value head widths
(multi-head latent attention: 192-wide queries and keys, 128-wide
values), forward and backward, without ``(S, S)`` scores for all heads.

Backends (``kernels/backend.py``): on TPU JAX's splash-attention Pallas
kernels (``splash_mha_fwd``, ``splash_mha_dq``, ``splash_mha_dkv``), which
take the two widths as they are (no padding of the values) and skip the
blocks above the diagonal; q, k and v go in as bf16 and the products
accumulate in f32, as XLA's default precision does on the MXU.
Elsewhere the jnp oracle computes the masked softmax outright (test
sizes only).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import backend as _backend

BLOCK = 512    # query and key block of the TPU kernels


def causal_attention_oracle(q, k, v, scale: float):
    """q, k (B, S, H, Dqk); v (B, S, H, Dv) -> (B, S, H, Dv) f32."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    S = q.shape[1]
    mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


def _splash_kernel(heads: int, seq: int):
    # built afresh in each trace: the kernel holds its block tables as
    # arrays, and a kernel kept from one trace would leak its values
    # into the next
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    blk = min(BLOCK, seq)
    mask = sm.MultiHeadMask([sm.CausalMask((seq, seq))] * heads)
    sizes = sk.BlockSizes(
        block_q=blk, block_kv=blk, block_kv_compute=blk,
        block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
        block_q_dq=blk, block_kv_dq=blk)
    return sk.make_splash_mha_single_device(mask, block_sizes=sizes)


def causal_attention(q, k, v, scale: float):
    """q, k (B, S, H, Dqk); v (B, S, H, Dv) -> (B, S, H, Dv) f32;
    softmax(q·kᵀ·scale) over keys at or before each query."""
    if _backend.resolve() != "tpu-pallas":
        return causal_attention_oracle(q, k, v, scale)
    B, S, H, _ = q.shape
    kernel = _splash_kernel(H, S)
    heads_first = lambda t: jnp.swapaxes(t, 1, 2).astype(jnp.bfloat16)  # noqa
    out = jax.vmap(kernel)(heads_first(q * scale), heads_first(k),
                           heads_first(v))
    return jnp.swapaxes(out, 1, 2).astype(jnp.float32)
