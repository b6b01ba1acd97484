"""Pallas TPU kernel: per-row symmetric int8 quantization of model updates
(beyond-paper: §VI names gradient compression as the complementary lever;
this gives an additional 4× on transmitted bytes on top of the θ filter).

Layout: x (R, LANE). Each grid step quantizes a (BR, LANE) tile: row scale
= max|x|/127 (fp32), q = clip(round(x/scale)). Dequant is the inverse
kernel. Both are single-pass VPU work with VMEM-resident tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 1024
BLOCK_R = 8


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    s_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("interpret", "block_r"))
def quantize_q8(x, *, interpret: bool, block_r: int = BLOCK_R):
    """x: (R, LANE) float -> (q int8 (R, LANE), scale f32 (R, 1))."""
    R = x.shape[0]
    grid = (pl.cdiv(R, block_r),)
    return pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_r, LANE), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_r, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, LANE), jnp.int8),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)


def _dequant_kernel(q_ref, s_ref, out_ref):
    out_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret", "block_r"))
def dequantize_q8(q, scale, *, interpret: bool, block_r: int = BLOCK_R):
    R = q.shape[0]
    grid = (pl.cdiv(R, block_r),)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_r, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, LANE), jnp.float32),
        interpret=interpret,
    )(q, scale)
