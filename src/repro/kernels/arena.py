"""Flat parameter arena: the canonical (R, LANE) device layout shared by
the cohort megastep and the Pallas aggregation kernels.

The paper's profiled anti-pattern (Tables V-VI) is thousands of tiny
per-tensor kernels; the fix is to pack the whole parameter pytree ONCE
into a lane-aligned f32 matrix and run every hot-path reduction on that
single buffer:

  * per-client sign-alignment counts    (kernels/sign_align.py)
  * masked/weighted cohort aggregation  (kernels/masked_agg.py)
  * int8 wire quantization              (kernels/quantize.py)

``ParamArena`` owns the static layout metadata (treedef, shapes, dtypes,
offsets, row count) so ``pack``/``unpack`` are pure jnp reshapes that
trace away inside a jitted step — no per-leaf dispatches at run time.

Backend dispatch (one selector for every op, ``kernels.backend``): on
TPU the Mosaic-Pallas kernels run compiled (``interpret=False``), on GPU
the Triton-Pallas kernels from ``kernels/gpu.py`` run compiled, and
everywhere else the pure-jnp oracles from ``kernels/ref.py`` are used —
XLA-compiled, bit-matching the kernel semantics, and fast on CPU where
interpret-mode Pallas would be a correctness-only crawl. The resolved
backend is logged once per process and can be forced with
``REPRO_KERNEL_BACKEND={pallas,oracle,auto}`` (unknown values and
pallas-on-unsupported-platform raise — no silent fallback). Padding uses
value 0 for updates and a -2 sentinel for reference signs so padded
positions can never count as aligned (sign() ∈ {-1, 0, 1}).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import backend as _backend
from repro.kernels import gather as _gather
from repro.kernels import gpu as _gpu
from repro.kernels import masked_agg as _agg
from repro.kernels import quantize as _qz
from repro.kernels import ref as _ref
from repro.kernels import sign_align as _sa

LANE = _sa.LANE


def use_pallas() -> bool:
    """True when a compiled Pallas lowering (TPU Mosaic or GPU Triton)
    is the active kernel backend; False on the jnp-oracle path."""
    return _backend.resolve() != "oracle"


class ParamArena:
    """Static layout of one parameter pytree in the (rows, LANE) arena.

    Construct once from a template pytree (real arrays or
    ``jax.ShapeDtypeStruct``s — only shapes/dtypes are read); ``pack`` /
    ``unpack`` are then cheap pure functions usable inside jit.
    """

    def __init__(self, template, lane: int = LANE):
        leaves, treedef = jax.tree.flatten(template)
        self.treedef = treedef
        self.shapes = tuple(tuple(l.shape) for l in leaves)
        self.dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
        self.sizes = tuple(int(np.prod(s)) if s else 1 for s in self.shapes)
        self.n = int(sum(self.sizes))
        self.lane = int(lane)
        self.rows = max(-(-self.n // self.lane), 1)
        self.pad = self.rows * self.lane - self.n

    # ------------------------------------------------------------------
    # pack / unpack (pure jnp — trace away inside jit)
    # ------------------------------------------------------------------
    def pack(self, tree) -> jnp.ndarray:
        """pytree -> (rows, lane) f32, zero-padded."""
        leaves = [l.reshape(-1).astype(jnp.float32)
                  for l in jax.tree.leaves(tree)]
        flat = (jnp.concatenate(leaves) if leaves
                else jnp.zeros((0,), jnp.float32))
        flat = jnp.pad(flat, (0, self.rows * self.lane - self.n))
        return flat.reshape(self.rows, self.lane)

    def pack_cohort(self, tree) -> jnp.ndarray:
        """pytree with leading client dim C -> (C, rows, lane) f32."""
        leaves = jax.tree.leaves(tree)
        C = leaves[0].shape[0]
        flat = jnp.concatenate(
            [l.reshape(C, -1).astype(jnp.float32) for l in leaves], axis=1)
        flat = jnp.pad(flat, ((0, 0), (0, self.rows * self.lane - self.n)))
        return flat.reshape(C, self.rows, self.lane)

    def unpack(self, mat, dtype=None):
        """(rows, lane) -> pytree; leaves cast to the template dtypes
        (or a single override ``dtype``, e.g. f32 for gradient math)."""
        flat = mat.reshape(-1)
        out, off = [], 0
        for shape, dt, size in zip(self.shapes, self.dtypes, self.sizes):
            leaf = flat[off:off + size].reshape(shape)
            out.append(leaf.astype(dtype or dt))
            off += size
        return jax.tree.unflatten(self.treedef, out)

    def unpack_cohort(self, mat, dtype=None):
        """(C, rows, lane) -> pytree with leading client dim C."""
        C = mat.shape[0]
        flat = mat.reshape(C, -1)
        out, off = [], 0
        for shape, dt, size in zip(self.shapes, self.dtypes, self.sizes):
            leaf = flat[:, off:off + size].reshape((C,) + shape)
            out.append(leaf.astype(dtype or dt))
            off += size
        return jax.tree.unflatten(self.treedef, out)

    # ------------------------------------------------------------------
    # reference-sign helpers
    # ------------------------------------------------------------------
    def valid_mask(self) -> np.ndarray:
        """(rows, lane) bool host constant; True on real (unpadded) slots."""
        idx = np.arange(self.rows * self.lane)
        return (idx < self.n).reshape(self.rows, self.lane)

    def sign_ref(self, new_mat, old_mat) -> jnp.ndarray:
        """int8 sign of the global movement, -2 sentinel on padding."""
        sign = jnp.sign(new_mat - old_mat).astype(jnp.int8)
        return jnp.where(jnp.asarray(self.valid_mask()), sign,
                         jnp.int8(-2))

    def pack_signs(self, sign_tree) -> jnp.ndarray:
        """int8 sign pytree -> (rows, lane) with -2 padding sentinel."""
        leaves = [l.reshape(-1) for l in jax.tree.leaves(sign_tree)]
        flat = jnp.concatenate(leaves).astype(jnp.int8)
        return jnp.pad(flat, (0, self.rows * self.lane - self.n),
                       constant_values=-2).reshape(self.rows, self.lane)


# ---------------------------------------------------------------------------
# backend-dispatched cohort ops (TPU Mosaic / GPU Triton / jnp oracle)
# ---------------------------------------------------------------------------

def cohort_sign_align(u, r) -> jnp.ndarray:
    """u: (C, rows, lane) f32 updates; r: (rows, lane) int8 reference.
    Returns (C,) aligned counts (divide by the arena's true n for ratios)."""
    b = _backend.resolve()
    if b == "tpu-pallas":
        return _sa.per_client_sign_align(u, r, interpret=False)
    if b == "gpu-pallas":
        return _gpu.per_client_sign_align(u, r)
    return _ref.per_client_sign_align(u, r)


def weighted_sum(u, w, compute_dtype=jnp.float32) -> jnp.ndarray:
    """Σ_c w[c]·u[c] over the client axis -> (rows, lane) f32.

    ``compute_dtype`` selects the cross-client reduction precision for
    the jnp oracle (bf16 halves all-reduce bytes on the production mesh);
    the Pallas kernels always reduce in f32.
    """
    b = _backend.resolve()
    if b == "tpu-pallas":
        return _agg.masked_agg(u, w, interpret=False)
    if b == "gpu-pallas":
        return _gpu.masked_agg(u, w)
    out = jnp.einsum("crl,c->rl", u.astype(compute_dtype),
                     w.astype(compute_dtype), precision=_ref.F32)
    return out.astype(jnp.float32)


def fused_apply(p, u, w_lr) -> jnp.ndarray:
    """p − Σ_c w_lr[c]·u[c] (aggregate+apply fused, p.dtype preserved)."""
    b = _backend.resolve()
    if b == "tpu-pallas":
        return _agg.fused_update(p, u, w_lr, interpret=False)
    if b == "gpu-pallas":
        return _gpu.fused_update(p, u, w_lr)
    return _ref.fused_update(p, u, w_lr)


def cohort_gather(src, idx) -> jnp.ndarray:
    """Gather per-client arena slabs by cohort index: src (N, rows, lane)
    f32, idx (K,) i32 -> (K, rows, lane). The device control plane's
    top-k selection feeds this (EF buffers, per-client state slabs); on
    TPU/GPU it runs as a one-hot matmul sweep (matrix-unit friendly, no
    serial DMA per row), on CPU as the bit-identical ``jnp.take``
    oracle."""
    b = _backend.resolve()
    if b == "oracle":
        return _ref.cohort_gather(src, idx)
    onehot = (idx[:, None] == jnp.arange(src.shape[0])[None, :]
              ).astype(jnp.float32)
    if b == "gpu-pallas":
        return _gpu.onehot_gather(src, onehot)
    return _gather.onehot_gather(src, onehot, interpret=False)


def quantize_rows(x):
    """x: (R, lane) f32 -> (q int8 (R, lane), scales f32 (R, 1))."""
    b = _backend.resolve()
    if b == "tpu-pallas":
        return _qz.quantize_q8(x, interpret=False)
    if b == "gpu-pallas":
        return _gpu.quantize_q8(x)
    return _ref.quantize_q8(x)


def dequantize_rows(q, s) -> jnp.ndarray:
    b = _backend.resolve()
    if b == "tpu-pallas":
        return _qz.dequantize_q8(q, s, interpret=False)
    if b == "gpu-pallas":
        return _gpu.dequantize_q8(q, s)
    return _ref.dequantize_q8(q, s)
