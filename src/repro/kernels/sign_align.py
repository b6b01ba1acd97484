"""Pallas TPU kernel: gradient sign-alignment counting (paper Alg. 1,
CALCULATE-RELEVANCE — the O(C·M) hot spot of the technique).

TPU adaptation (DESIGN.md §7): where the paper's PyTorch loop issues one
tiny CUDA kernel per tensor per client (2.13M launches in its Table VI),
we flatten the parameter pytree ONCE into a (R, 1024) layout and sweep it
with a 1-D grid of VMEM-resident (BR, 1024) tiles. Each grid step folds
its tile's matches into a lane-dense (·, 128) partial — a block Mosaic
accepts, since its last two dims equal the output array's — and the jit'd
wrapper sums the partials. Elementwise compare + reduce → VPU-bound,
fully vectorized. Counts are small integers, so the f32 sums are exact.

The last tile is ragged whenever R is not a multiple of BR (54 rows for
the UNSW detector, 49 for the ROAD one): on the chip its out-of-range
rows hold whatever the buffer held, so the kernels mask them by row
index before counting.

Also provides the per-client variant: u (C, R, LANE) against a shared
reference sign tile — one pass produces all C counts (grid over R only;
the client dim stays resident in VMEM, C ≤ 64 for any realistic mesh).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 1024          # 8 sublanes × 128 lanes
BLOCK_R = 8          # rows per tile -> (8, 1024) f32 = 32 KiB VMEM per ref
PART = 128           # lane width of each tile's partial count


def _matches(x, r, n_rows, block_r):
    """(…, BR, LANE) f32: 1.0 where sign(x) == r on rows < n_rows.

    Compared in f32: sign() ∈ {-1, 0, 1} and r ∈ {-2, -1, 0, 1} are exact
    there, so this equals the int8 comparison of the oracle."""
    row = (pl.program_id(0) * block_r
           + jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 2))
    eq = jnp.sign(x.astype(jnp.float32)) == r.astype(jnp.float32)
    return jnp.where((row < n_rows) & eq, 1.0, 0.0).astype(jnp.float32)


def _fold_lanes(x):
    """Sum the LANE axis down to PART lanes with static aligned slices."""
    acc = x[..., :PART]
    for k in range(1, x.shape[-1] // PART):
        acc = acc + x[..., k * PART:(k + 1) * PART]
    return acc


def _count_kernel(g_ref, r_ref, out_ref, *, n_rows, block_r):
    eq = _matches(g_ref[...], r_ref[...], n_rows, block_r)   # (BR, LANE)
    out_ref[...] = _fold_lanes(jnp.sum(eq, axis=0, keepdims=True))


@functools.partial(jax.jit, static_argnames=("interpret", "block_r"))
def sign_align_counts(g, r, *, interpret: bool, block_r: int = BLOCK_R):
    """g: (R, LANE) float; r: (R, LANE) int8. Returns scalar f32 count."""
    R = g.shape[0]
    grid = (pl.cdiv(R, block_r),)
    partial = pl.pallas_call(
        functools.partial(_count_kernel, n_rows=R, block_r=block_r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, LANE), lambda i: (i, 0)),
            pl.BlockSpec((block_r, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, PART), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0], 1, PART), jnp.float32),
        interpret=interpret,
    )(g, r)
    return partial.sum()


def _per_client_kernel(u_ref, r_ref, out_ref, *, n_rows, block_r):
    eq = _matches(u_ref[...], r_ref[...][None], n_rows, block_r)
    out_ref[...] = _fold_lanes(jnp.sum(eq, axis=1))        # (C, PART)


@functools.partial(jax.jit, static_argnames=("interpret", "block_r"))
def per_client_sign_align(u, r, *, interpret: bool,
                          block_r: int = BLOCK_R):
    """u: (C, R, LANE); r: (R, LANE) int8 -> (C,) aligned counts (f32)."""
    C, R, _ = u.shape
    grid = (pl.cdiv(R, block_r),)
    partial = pl.pallas_call(
        functools.partial(_per_client_kernel, n_rows=R, block_r=block_r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((C, block_r, LANE), lambda i: (0, i, 0)),
            pl.BlockSpec((block_r, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((None, C, PART), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0], C, PART), jnp.float32),
        interpret=interpret,
    )(u, r)
    return partial.sum(axis=(0, 2))
