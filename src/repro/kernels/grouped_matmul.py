"""Grouped matmul over the experts one chip holds (the MoE layer's
expert FFN), forward and backward.

``lhs`` rows are sorted by expert: ``group_sizes[g]`` rows for held
expert ``g``, then the rows no held expert takes, which come out 0. Only
the held experts' rows are multiplied, so the work follows the tokens
routed here, not the buffer's worst-case size.

Backends (``kernels/backend.py``): on TPU the megablox Pallas kernels
(``gmm``, and ``tgmm`` for the weight gradient) from
``jax.experimental.pallas.ops.tpu.megablox``, with the operands rounded
to bf16 and products accumulated and returned in f32 — what XLA's default
precision does to every other f32 matmul of the model on the MXU — under
a custom VJP whose backward is two more grouped matmuls. Elsewhere the
jnp oracle multiplies every row by every held expert and keeps its own
(test sizes only; unlike ``jax.lax.ragged_dot`` it vmaps over a cohort).
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

from repro.kernels import backend as _backend

TM = 128       # row tile: a group's last tile is partly other rows' work
TKN = 512      # contraction / output tile where the width allows


def _tile(x: int) -> int:
    return TKN if x % TKN == 0 else x


def _tiling(m: int, k: int, n: int):
    return (TM, _tile(k), _tile(n))


def _megablox():
    # the package re-exports ``gmm`` the function under the module's name
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


@jax.custom_vjp
def _gmm_tpu(lhs, rhs, group_sizes):
    return _gmm_tpu_fwd(lhs, rhs, group_sizes)[0]


def _gmm_tpu_fwd(lhs, rhs, group_sizes):
    mb = _megablox()
    l16, r16 = lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16)
    out = mb.gmm(l16, r16, group_sizes, jnp.float32, _tiling)
    return out, (l16, r16, group_sizes)


def _gmm_tpu_bwd(res, g):
    mb = _megablox()
    l16, r16, group_sizes = res
    g16 = g.astype(jnp.bfloat16)
    d_lhs = mb.gmm(g16, r16, group_sizes, jnp.float32, _tiling,
                   transpose_rhs=True)
    d_rhs = mb.tgmm(l16.T, g16, group_sizes, jnp.float32, _tiling,
                    num_actual_groups=r16.shape[0])
    return d_lhs, d_rhs, None


_gmm_tpu.defvjp(_gmm_tpu_fwd, _gmm_tpu_bwd)


def expert_matmul_oracle(lhs, rhs, group_sizes):
    g = rhs.shape[0]
    row_group = jnp.searchsorted(jnp.cumsum(group_sizes),
                                 jnp.arange(lhs.shape[0]), side="right")
    every = jnp.einsum("mk,gkn->mgn", lhs.astype(jnp.float32),
                       rhs.astype(jnp.float32))
    own = jnp.take_along_axis(
        every, jnp.minimum(row_group, g - 1)[:, None, None], axis=1)[:, 0]
    return jnp.where((row_group < g)[:, None], own, 0.0)


def expert_matmul(lhs, rhs, group_sizes):
    """lhs (m, k) f32, rows sorted by group; rhs (g, k, n) f32;
    group_sizes (g,) int32 summing to at most m. Returns (m, n) f32 with
    the rows past the groups 0."""
    if _backend.resolve() != "tpu-pallas":
        return expert_matmul_oracle(lhs, rhs, group_sizes)
    m = lhs.shape[0]
    pad = -m % TM
    rest = m + pad - jnp.sum(group_sizes)
    sizes = jnp.concatenate([group_sizes, rest[None]]).astype(jnp.int32)
    out = _gmm_tpu(jnp.pad(lhs, ((0, pad), (0, 0))), rhs, sizes)
    return out[:m]
