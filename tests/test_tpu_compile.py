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


# moonlight-16b-a3b-ep8: one MoE layer's buffer of (token, choice) rows at
# 4,096 tokens × top-6, its 8 held experts, and the latent attention's
# 16 heads of 192-wide queries/keys and 128-wide values; its arena is
# 555,161 rows and its cohort 2
MOONLIGHT_ROWS = 555_161


@pytest.fixture
def tpu_backend(monkeypatch):
    from repro.kernels import backend
    monkeypatch.setattr(backend, "resolve", lambda: "tpu-pallas")


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)])
def test_expert_matmul_compiles_for_v5e(one_chip, tpu_backend, k, n):
    from repro.kernels import grouped_matmul as gm

    def loss(lhs, rhs, sizes):
        return jnp.sum(gm.expert_matmul(lhs, rhs, sizes))
    compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1)),
                        [((4096 * 6, k), jnp.float32),
                         ((8, k, n), jnp.float32), ((8,), jnp.int32)],
                        one_chip)
    text = compiled.as_text()
    assert text.count("gmm") > text.count("tgmm") > 0


def test_causal_attention_compiles_for_v5e(one_chip, tpu_backend):
    from repro.kernels import attention

    def loss(q, k, v):
        return jnp.sum(attention.causal_attention(q, k, v, scale=0.07))
    compiled = _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                        [((1, 4096, 16, 192), jnp.float32),
                         ((1, 4096, 16, 192), jnp.float32),
                         ((1, 4096, 16, 128), jnp.float32)], one_chip)
    text = compiled.as_text()
    for kernel in ("splash_mha_fwd", "splash_mha_dq", "splash_mha_dkv"):
        assert kernel in text


@pytest.mark.parametrize("name", ["masked_agg", "per_client_sign_align"])
def test_arena_kernel_compiles_at_moonlight_rows(one_chip, name):
    fn, shapes = _kernel_cases(MOONLIGHT_ROWS)[name]
    shapes = [((2,) + s[1:], d) if s[0] == C else (s, d) for s, d in shapes]
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_packed_record_gather_compiles_for_v5e(one_chip):
    """Silo's cohort batch (10 silos × 16 steps × 256 rows = 40,960
    indices) from its packed record table (175,341 records of 49 f32
    features and an s32 label, and the zero row): one gather of whole
    rows under ``fl_gather``, and no gather of labels one by one."""
    import re

    import numpy as np

    from repro.core import records
    leaves = (records.Leaf("x", np.dtype(np.float32), (49,), 0, 49),
              records.Leaf("y", np.dtype(np.int32), (), 49, 1))

    def batch_loss(table, offsets, cohort, idx, w):
        with jax.named_scope("fl_gather"):
            batch = records.RecordTable(table, offsets, leaves).take(
                cohort, idx)
        # local training's use of them: features into a matmul, labels
        # picking a logit
        logits = batch["x"] @ w
        return jnp.sum(jnp.take_along_axis(logits, batch["y"][..., None],
                                           axis=-1))

    compiled = _compile(batch_loss, [((175_342, 50), jnp.uint32),
                                     ((10,), jnp.int32), ((10,), jnp.int32),
                                     ((10, 16, 256), jnp.int32),
                                     ((49, 10), jnp.float32)], one_chip)
    gathers = [line for line in compiled.as_text().splitlines()
               if " gather(" in line and "/fl_gather/" in line]
    rows = [g for g in gathers if re.search(r"= \w+\[40960[,\]]", g)]
    assert len(rows) == 1, rows
    assert "u32[40960,50]" in rows[0]
    assert not any("s32[40960]" in g for g in gathers)
