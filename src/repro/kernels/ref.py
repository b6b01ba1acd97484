"""Pure-jnp oracles for every Pallas kernel (the correctness reference).

Shapes follow the kernels' canonical layout: flat parameter vectors are
reshaped to (R, LANE) with LANE=1024 (8×128 VREG-aligned); client-stacked
updates are (C, R, LANE).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# f32 contractions everywhere: on a TPU the default precision would take
# one bf16 pass through the matrix unit, and the oracle would no longer
# be the f32 reference the kernels are held to
F32 = jax.lax.Precision.HIGHEST


def sign_align_counts(g, r):
    """g: (R, LANE) float; r: (R, LANE) int8 reference signs.
    Returns scalar count of positions where sign(g) == r."""
    s = jnp.sign(g.astype(jnp.float32)).astype(jnp.int8)
    return jnp.sum((s == r).astype(jnp.float32))


def per_client_sign_align(u, r):
    """u: (C, R, LANE); r: (R, LANE) int8 -> (C,) aligned counts."""
    s = jnp.sign(u.astype(jnp.float32)).astype(jnp.int8)
    eq = (s == r[None]).astype(jnp.float32)
    return eq.reshape(u.shape[0], -1).sum(axis=1)


def masked_agg(u, w):
    """u: (C, R, LANE); w: (C,) pre-normalized weights -> (R, LANE) f32."""
    return jnp.einsum("crl,c->rl", u.astype(jnp.float32), w.astype(jnp.float32),
                      precision=F32)


def fused_update(p, u, w_lr):
    """Fused selective-aggregate + SGD apply (beyond-paper, DESIGN.md §7).
    p: (R, LANE) params; u: (C, R, LANE) updates; w_lr: (C,) = lr·mask·w.
    Returns p - Σ_c w_lr[c]·u[c]."""
    agg = jnp.einsum("crl,c->rl", u.astype(jnp.float32), w_lr.astype(jnp.float32),
                     precision=F32)
    return (p.astype(jnp.float32) - agg).astype(p.dtype)


def cohort_gather(src, idx):
    """src: (N, R, LANE); idx: (K,) i32 -> (K, R, LANE) gathered rows —
    oracle of the one-hot matmul gather (exact: one nonzero per row)."""
    return jnp.take(src, idx, axis=0)


def quantize_q8(x):
    """Per-row symmetric int8 quantization. x: (R, LANE) float.
    Returns (q int8 (R, LANE), scale f32 (R, 1))."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_q8(q, scale):
    return q.astype(jnp.float32) * scale
