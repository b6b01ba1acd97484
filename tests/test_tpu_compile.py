"""Ahead-of-time compiles of the Mosaic kernels for a described TPU v5e.

No chip is attached: the TPU compiler builds each kernel for a v5e that
is only described, at the detector arenas' real sizes (54 rows for the
UNSW detector, 49 for the ROAD one — both with a ragged last tile) and a
32-client cohort. Interpret-mode tests cannot see what this catches:
block shapes Mosaic refuses and contractions it cannot lower.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, so describing it while the module is
collected would make test workers disagree on what exists.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import gather as ga
from repro.kernels import masked_agg as ma
from repro.kernels import quantize as qz
from repro.kernels import sign_align as sa

LANE = sa.LANE
C = 32
ROWS = [54, 49]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()
    if prev_log is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = prev_log


def _kernel_cases(R):
    """name -> (call with interpret=False, argument shapes)."""
    f32, i8 = jnp.float32, jnp.int8
    return {
        "sign_align_counts": (
            lambda g, r: sa.sign_align_counts(g, r, interpret=False),
            [((R, LANE), f32), ((R, LANE), i8)]),
        "per_client_sign_align": (
            lambda u, r: sa.per_client_sign_align(u, r, interpret=False),
            [((C, R, LANE), f32), ((R, LANE), i8)]),
        "masked_agg": (
            lambda u, w: ma.masked_agg(u, w, interpret=False),
            [((C, R, LANE), f32), ((C,), f32)]),
        "fused_update": (
            lambda p, u, w: ma.fused_update(p, u, w, interpret=False),
            [((R, LANE), f32), ((C, R, LANE), f32), ((C,), f32)]),
        "quantize_q8": (
            lambda x: qz.quantize_q8(x, interpret=False),
            [((R, LANE), f32)]),
        "dequantize_q8": (
            lambda q, s: qz.dequantize_q8(q, s, interpret=False),
            [((R, LANE), i8), ((R, 1), f32)]),
        "onehot_gather": (
            lambda src, oh: ga.onehot_gather(src, oh, interpret=False),
            [((C, R, LANE), f32), ((C, C), f32)]),
    }


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("R", ROWS)
@pytest.mark.parametrize("name", list(_kernel_cases(8)))
def test_kernel_compiles_for_v5e(one_chip, name, R):
    fn, shapes = _kernel_cases(R)[name]
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["masked_agg", "per_client_sign_align"])
def test_vmapped_kernel_compiles_for_v5e(one_chip, name):
    """The vmapped seed batch (api/runner.run_scanned_seed_batch) batches
    every pallas_call: each block gains a leading squeezed dim, which
    must still satisfy Mosaic's tiling rule."""
    fn, shapes = _kernel_cases(54)[name]
    batched = [((2,) + s, d) for s, d in shapes]
    compiled = _compile(jax.vmap(fn), batched, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
