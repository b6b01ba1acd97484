"""Pallas TPU kernel: masked weighted aggregation over the client axis
(paper §IV-C: w_g = 1/|S| Σ_{i∈S} w_i — the server-side hot spot).

Layout: updates u (C, R, LANE); weights w (C,) already mask·weight
normalized by the jit'd wrapper (zero-safe). Grid sweeps R in (BR, LANE)
tiles; the full client dim is VMEM-resident per tile (C·BR·LANE·4 B =
16 clients → 512 KiB at BR=8 — comfortably inside the ~16 MiB v5e VMEM).
The weights arrive as a resident (C, 1, 1) block, and the reduction over
C is a VPU multiply-add of one (BR, LANE) tile per client into an f32
accumulator — Mosaic has no lowering for a dot that contracts the
leading axis of a 3-D tile. (SMEM scalars would be the natural home for
w, but under ``jax.vmap`` their batched block breaks the (8, 128) rule;
a (1, 1) trailing block equals the array's and stays legal.)

``fused_update`` additionally subtracts the aggregate from the parameter
tile in the same pass (aggregate+apply fusion — removes one full HBM
round-trip of the aggregated update; beyond-paper §Perf optimization).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANE = 1024
BLOCK_R = 8


def _weighted_sum(u_ref, w_ref):
    """Σ_c w[c]·u[c] over the resident (C, BR, LANE) tile, in f32."""
    def body(c, acc):
        return acc + w_ref[c] * u_ref[c].astype(jnp.float32)   # (1, 1) bcast
    return jax.lax.fori_loop(0, u_ref.shape[0], body,
                             jnp.zeros(u_ref.shape[1:], jnp.float32))


def _agg_kernel(u_ref, w_ref, out_ref):
    out_ref[...] = _weighted_sum(u_ref, w_ref)


def _specs(C, block_r):
    """Block specs of the client-stacked updates and the (C, 1, 1) weights."""
    return [pl.BlockSpec((C, block_r, LANE), lambda i: (0, i, 0)),
            pl.BlockSpec((C, 1, 1), lambda i: (0, 0, 0))]


@functools.partial(jax.jit, static_argnames=("interpret", "block_r"))
def masked_agg(u, w, *, interpret: bool, block_r: int = BLOCK_R):
    """u: (C, R, LANE); w: (C,) normalized weights -> (R, LANE) f32."""
    C, R, _ = u.shape
    grid = (pl.cdiv(R, block_r),)
    return pl.pallas_call(
        _agg_kernel,
        grid=grid,
        in_specs=_specs(C, block_r),
        out_specs=pl.BlockSpec((block_r, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, LANE), jnp.float32),
        interpret=interpret,
    )(u, w.astype(jnp.float32).reshape(C, 1, 1))


def _fused_kernel(p_ref, u_ref, w_ref, out_ref):
    agg = _weighted_sum(u_ref, w_ref)
    out_ref[...] = (p_ref[...].astype(jnp.float32) - agg).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_r"))
def fused_update(p, u, w_lr, *, interpret: bool, block_r: int = BLOCK_R):
    """p: (R, LANE); u: (C, R, LANE); w_lr: (C,) = lr·mask·weight.
    Returns p − Σ_c w_lr[c]·u[c] in p.dtype (aggregate+apply fused)."""
    C, R, _ = u.shape
    grid = (pl.cdiv(R, block_r),)
    return pl.pallas_call(
        _fused_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_r, LANE), lambda i: (i, 0)),
                  *_specs(C, block_r)],
        out_specs=pl.BlockSpec((block_r, LANE), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, LANE), p.dtype),
        interpret=interpret,
    )(p, u, w_lr.astype(jnp.float32).reshape(C, 1, 1))
