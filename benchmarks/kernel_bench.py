"""Kernel microbenchmarks: Pallas (interpret) vs pure-jnp oracle vs the
fused selective-update path. On CPU the interesting number is the ORACLE
row (XLA-compiled jnp) — interpret-mode Pallas measures correctness, not
speed; on TPU the same harness times the real kernels. Prints
``name,us_per_call,derived`` CSV per the harness contract.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import masked_agg as ma
from repro.kernels import ops, ref
from repro.kernels import quantize as qz
from repro.kernels import sign_align as sa


def _time(fn, *args, iters=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


def run():
    key = jax.random.PRNGKey(0)
    R, C = 128, 16                      # ~131k-param update, 16 clients
    g = jax.random.normal(key, (R, ops.LANE))
    r = jnp.sign(jax.random.normal(jax.random.fold_in(key, 1),
                                   (R, ops.LANE))).astype(jnp.int8)
    u = jax.random.normal(jax.random.fold_in(key, 2), (C, R, ops.LANE))
    w = jnp.full((C,), 1.0 / C)
    p = jax.random.normal(jax.random.fold_in(key, 3), (R, ops.LANE))

    jit_ref_align = jax.jit(ref.per_client_sign_align)
    jit_ref_agg = jax.jit(ref.masked_agg)
    jit_ref_fused = jax.jit(ref.fused_update)
    jit_ref_q = jax.jit(ref.quantize_q8)

    # compiled Mosaic kernels on a TPU; the interpreter anywhere else
    interp = jax.default_backend() != "tpu"
    mode, note = (("interp", "correctness mode") if interp
                  else ("mosaic", "compiled"))
    rows = [
        ["oracle_per_client_align", _time(jit_ref_align, u, r),
         f"C={C},R={R}"],
        ["oracle_masked_agg", _time(jit_ref_agg, u, w), f"C={C},R={R}"],
        ["oracle_fused_update", _time(jit_ref_fused, p, u, w),
         "agg+apply fused"],
        ["oracle_quantize_q8", _time(jit_ref_q, g), "4x bytes saved"],
        [f"pallas_{mode}_align", _time(
            lambda: sa.per_client_sign_align(u, r, interpret=interp)), note],
        [f"pallas_{mode}_agg", _time(
            lambda: ma.masked_agg(u, w, interpret=interp)), note],
        [f"pallas_{mode}_quant", _time(
            lambda: qz.quantize_q8(g, interpret=interp)), note],
    ]
    # two-pass (align then agg) vs fused single pass, oracle timing
    def two_pass(p, u, w):
        agg = ref.masked_agg(u, w)
        return (p - 0.01 * agg).astype(p.dtype)
    rows.append(["oracle_two_pass_update", _time(jax.jit(two_pass), p, u, w),
                 "unfused baseline"])
    print("name,us_per_call,derived")
    for n, t, d in rows:
        print(f"{n},{t:.1f},{d}")
    return rows


if __name__ == "__main__":
    run()
