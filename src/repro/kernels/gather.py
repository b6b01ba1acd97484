"""Pallas TPU kernel: one-hot cohort gather on the parameter arena.

The device-resident control plane selects a fixed-width cohort of K
clients per scanned round; per-client arena buffers (the batched
error-feedback state, per-client delta slabs) must then be gathered by
the selected indices WITHOUT leaving the device. On TPU a dynamic
``jnp.take`` over the leading axis lowers to a serial DMA per row; the
MXU-friendly formulation is a one-hot matmul over the client axis:

    out[c] = Σ_n onehot[c, n] · src[n]          onehot: (K, N) f32

which is exact (each row has a single 1.0 coefficient) and reuses the
same (BR, LANE)-tiled sweep as ``masked_agg``. The grid sweeps the row
dimension; the full client axis is VMEM-resident per tile (N·BR·LANE·4 B
= 32 clients → 1 MiB at BR=8, comfortably inside ~16 MiB v5e VMEM).

CPU path: the pure-jnp oracle in ``kernels/ref.py`` (``jnp.take``) —
bit-matching because the one-hot sum has exactly one nonzero term.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 1024
BLOCK_R = 8


def _gather_kernel(oh_ref, src_ref, out_ref):
    oh = oh_ref[...].astype(jnp.float32)               # (K, N)
    src = src_ref[...].astype(jnp.float32)             # (N, BR, LANE)
    # f32 contraction on the matrix unit: a bf16 pass would round src
    out_ref[...] = jnp.einsum("kn,nrl->krl", oh, src,
                              precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.jit, static_argnames=("interpret", "block_r"))
def onehot_gather(src, onehot, *, interpret: bool,
                  block_r: int = BLOCK_R):
    """src: (N, R, LANE) f32; onehot: (K, N) f32 -> (K, R, LANE) f32."""
    N, R, _ = src.shape
    K = onehot.shape[0]
    grid = (pl.cdiv(R, block_r),)
    return pl.pallas_call(
        _gather_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, N), lambda i: (0, 0)),
            pl.BlockSpec((N, block_r, LANE), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((K, block_r, LANE), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((K, R, LANE), jnp.float32),
        interpret=interpret,
    )(onehot, src)
