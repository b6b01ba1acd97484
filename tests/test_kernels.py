"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs the
ref.py pure-jnp oracles (interpret mode executes kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import aggregation, alignment
from repro.kernels import arena
from repro.kernels import backend as kbackend
from repro.kernels import gather as ga
from repro.kernels import gpu
from repro.kernels import masked_agg as ma
from repro.kernels import ops, ref
from repro.kernels import quantize as qz
from repro.kernels import sign_align as sa

SHAPES = [(8, ops.LANE), (16, ops.LANE), (40, ops.LANE), (49, ops.LANE)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_sign_align_counts(shape, dtype):
    key = jax.random.PRNGKey(0)
    g = _rand(key, shape, dtype)
    r = jnp.sign(_rand(jax.random.fold_in(key, 1), shape, jnp.float32)) \
        .astype(jnp.int8)
    np.testing.assert_allclose(
        np.asarray(sa.sign_align_counts(g, r, interpret=True)),
        np.asarray(ref.sign_align_counts(g, r)), rtol=1e-6)


@pytest.mark.parametrize("C", [1, 4, 16])
@pytest.mark.parametrize("dtype", DTYPES)
def test_per_client_sign_align(C, dtype):
    key = jax.random.PRNGKey(1)
    u = _rand(key, (C, 16, ops.LANE), dtype)
    r = jnp.sign(_rand(jax.random.fold_in(key, 2), (16, ops.LANE),
                       jnp.float32)).astype(jnp.int8)
    np.testing.assert_allclose(
        np.asarray(sa.per_client_sign_align(u, r, interpret=True)),
        np.asarray(ref.per_client_sign_align(u, r)), rtol=1e-6)


@pytest.mark.parametrize("C", [2, 8])
@pytest.mark.parametrize("shape", SHAPES)
def test_masked_agg(C, shape):
    key = jax.random.PRNGKey(2)
    u = _rand(key, (C,) + shape, jnp.float32)
    w = jax.nn.softmax(_rand(jax.random.fold_in(key, 3), (C,), jnp.float32))
    np.testing.assert_allclose(np.asarray(ma.masked_agg(u, w, interpret=True)),
                               np.asarray(ref.masked_agg(u, w)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("N,K", [(4, 2), (8, 8), (6, 1)])
def test_onehot_cohort_gather(N, K):
    """One-hot matmul gather == jnp.take oracle (exact: single 1.0
    coefficient per output row) — the scanned control plane's cohort
    fetch (kernels/gather.py)."""
    key = jax.random.PRNGKey(5)
    src = _rand(key, (N, 8, ops.LANE), jnp.float32)
    idx = jax.random.randint(jax.random.fold_in(key, 1), (K,), 0, N)
    onehot = (idx[:, None] == jnp.arange(N)[None, :]).astype(jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(ga.onehot_gather(src, onehot, interpret=True)),
        np.asarray(ref.cohort_gather(src, idx)))


@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_update(dtype):
    key = jax.random.PRNGKey(3)
    p = _rand(key, (16, ops.LANE), dtype)
    u = _rand(jax.random.fold_in(key, 4), (4, 16, ops.LANE), jnp.float32)
    w = jnp.array([0.3, 0.0, 0.5, 0.2]) * 0.01
    got = ma.fused_update(p, u, w, interpret=True)
    want = ref.fused_update(p, u, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-6)


@pytest.mark.parametrize("shape", SHAPES)
def test_quantize_roundtrip(shape):
    key = jax.random.PRNGKey(4)
    x = _rand(key, shape, jnp.float32) * 3.0
    q, s = qz.quantize_q8(x, interpret=True)
    q2, s2 = ref.quantize_q8(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s2), rtol=1e-6)
    back = qz.dequantize_q8(q, s, interpret=True)
    # quantization error bounded by scale/2 per element
    err = np.abs(np.asarray(back) - np.asarray(x))
    assert np.all(err <= np.asarray(s) * 0.51 + 1e-9)


def test_quantize_zero_row_safe():
    x = jnp.zeros((8, ops.LANE), jnp.float32)
    q, s = qz.quantize_q8(x, interpret=True)
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.isfinite(np.asarray(s)))


# ---------------------------------------------------------------------------
# tree-level ops vs the pure-jnp core (hypothesis property sweeps)
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(1, 6), st.integers(1, 400), st.integers(0, 2 ** 31 - 1))
def test_ops_ratio_matches_core(n_leaves, leaf_size, seed):
    key = jax.random.PRNGKey(seed)
    tree = {f"p{i}": jax.random.normal(jax.random.fold_in(key, i),
                                       (leaf_size + i,))
            for i in range(n_leaves)}
    refsign = alignment.tree_sign(
        jax.tree.map(lambda x: x * 0.7 + 0.05, tree))
    np.testing.assert_allclose(
        np.asarray(ops.sign_align_ratio(tree, refsign)),
        np.asarray(alignment.alignment_ratio(tree, refsign)), rtol=1e-6)


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 6), st.integers(0, 2 ** 31 - 1))
def test_ops_masked_agg_matches_core(C, seed):
    key = jax.random.PRNGKey(seed)
    tree = {"a": jax.random.normal(key, (37,)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (5, 11))}
    stacked = jax.tree.map(
        lambda x: jnp.stack([x * (i + 1) for i in range(C)]), tree)
    mask = (jax.random.uniform(jax.random.fold_in(key, 2), (C,)) > 0.4) \
        .astype(jnp.float32)
    if float(mask.sum()) == 0:
        mask = mask.at[0].set(1.0)
    got = ops.masked_aggregate(stacked, mask)
    want = aggregation.masked_mean(stacked, mask)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# GPU Triton-Pallas variants vs the same oracles
#
# Interpret mode executes the Triton-constrained kernel bodies (pow2
# block padding, broadcast-multiply reductions) on any backend; the
# compiled variant requires an actual GPU and SKIPS with an explicit
# reason elsewhere — never a silent fallback.
# ---------------------------------------------------------------------------

_GPU_MODES = [
    pytest.param(True, id="interpret"),
    pytest.param(False, id="compiled", marks=pytest.mark.skipif(
        jax.default_backend() != "gpu",
        reason="Triton lowering requires jax.default_backend() == 'gpu' "
               f"(got {jax.default_backend()!r}); interpret-mode variant "
               "covers the kernel bodies here")),
]
# deliberately non-power-of-2 client/population sizes to exercise padding
_GPU_CS = [1, 3, 5, 8]


@pytest.mark.parametrize("interpret", _GPU_MODES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_gpu_sign_align_counts(interpret, dtype):
    key = jax.random.PRNGKey(10)
    g = _rand(key, (13, ops.LANE), dtype)   # 13 rows: exercises R padding
    r = jnp.sign(_rand(jax.random.fold_in(key, 1), (13, ops.LANE),
                       jnp.float32)).astype(jnp.int8)
    np.testing.assert_allclose(
        np.asarray(gpu.sign_align_counts(g, r, interpret=interpret)),
        np.asarray(ref.sign_align_counts(g, r)), rtol=1e-6)


@pytest.mark.parametrize("interpret", _GPU_MODES)
@pytest.mark.parametrize("C", _GPU_CS)
def test_gpu_per_client_sign_align(interpret, C):
    key = jax.random.PRNGKey(11)
    u = _rand(key, (C, 16, ops.LANE), jnp.float32)
    r = jnp.sign(_rand(jax.random.fold_in(key, 2), (16, ops.LANE),
                       jnp.float32)).astype(jnp.int8)
    np.testing.assert_allclose(
        np.asarray(gpu.per_client_sign_align(u, r, interpret=interpret)),
        np.asarray(ref.per_client_sign_align(u, r)), rtol=1e-6)


@pytest.mark.parametrize("interpret", _GPU_MODES)
@pytest.mark.parametrize("C", _GPU_CS)
def test_gpu_masked_agg(interpret, C):
    key = jax.random.PRNGKey(12)
    u = _rand(key, (C, 16, ops.LANE), jnp.float32)
    w = jax.nn.softmax(_rand(jax.random.fold_in(key, 3), (C,), jnp.float32))
    np.testing.assert_allclose(
        np.asarray(gpu.masked_agg(u, w, interpret=interpret)),
        np.asarray(ref.masked_agg(u, w)), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("interpret", _GPU_MODES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_gpu_fused_update(interpret, dtype):
    key = jax.random.PRNGKey(13)
    p = _rand(key, (16, ops.LANE), dtype)
    u = _rand(jax.random.fold_in(key, 4), (3, 16, ops.LANE), jnp.float32)
    w = jnp.array([0.3, 0.5, 0.2]) * 0.01
    got = gpu.fused_update(p, u, w, interpret=interpret)
    want = ref.fused_update(p, u, w)
    assert got.dtype == p.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-6)


@pytest.mark.parametrize("interpret", _GPU_MODES)
@pytest.mark.parametrize("N,K", [(4, 2), (6, 1), (10, 7)])
def test_gpu_onehot_gather(interpret, N, K):
    key = jax.random.PRNGKey(14)
    src = _rand(key, (N, 13, ops.LANE), jnp.float32)
    idx = jax.random.randint(jax.random.fold_in(key, 1), (K,), 0, N)
    onehot = (idx[:, None] == jnp.arange(N)[None, :]).astype(jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(gpu.onehot_gather(src, onehot, interpret=interpret)),
        np.asarray(ref.cohort_gather(src, idx)))


@pytest.mark.parametrize("interpret", _GPU_MODES)
def test_gpu_quantize_roundtrip(interpret):
    key = jax.random.PRNGKey(15)
    x = _rand(key, (13, ops.LANE), jnp.float32) * 3.0
    q, s = gpu.quantize_q8(x, interpret=interpret)
    q2, s2 = ref.quantize_q8(x)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q2))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s2), rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(gpu.dequantize_q8(q, s, interpret=interpret)),
        np.asarray(ref.dequantize_q8(q2, s2)), rtol=1e-6)


# ---------------------------------------------------------------------------
# backend selector: REPRO_KERNEL_BACKEND override semantics
# ---------------------------------------------------------------------------

def test_backend_auto_matches_platform(monkeypatch):
    monkeypatch.delenv(kbackend.ENV_VAR, raising=False)
    expected = {"tpu": "tpu-pallas", "gpu": "gpu-pallas"}.get(
        jax.default_backend(), "oracle")
    assert kbackend.resolve() == expected


def test_backend_forced_oracle(monkeypatch):
    monkeypatch.setenv(kbackend.ENV_VAR, "oracle")
    assert kbackend.resolve() == "oracle"
    assert not arena.use_pallas()
    assert ops.default_interpret()


def test_backend_unknown_forced_value_errors(monkeypatch):
    """An unknown override must raise, not degrade to a default."""
    monkeypatch.setenv(kbackend.ENV_VAR, "tensor-cores")
    with pytest.raises(ValueError, match="REPRO_KERNEL_BACKEND"):
        kbackend.resolve()


def test_backend_forced_pallas_requires_lowering(monkeypatch):
    """Forcing pallas on a platform without a Pallas lowering is an
    error (the silent-fallback bug this selector replaces)."""
    monkeypatch.setenv(kbackend.ENV_VAR, "pallas")
    if jax.default_backend() in ("tpu", "gpu"):
        assert kbackend.resolve().endswith("-pallas")
    else:
        with pytest.raises(RuntimeError, match="no Pallas lowering"):
            kbackend.resolve()


def test_backend_announces_once(monkeypatch, caplog):
    monkeypatch.setenv(kbackend.ENV_VAR, "oracle")
    monkeypatch.setattr(kbackend, "_announced", set())
    with caplog.at_level("INFO", logger="repro.kernels"):
        kbackend.resolve()
        kbackend.resolve()
    hits = [r for r in caplog.records
            if "active kernel backend" in r.getMessage()]
    assert len(hits) == 1
    assert "oracle" in hits[0].getMessage()


def test_ops_route_through_selector(monkeypatch):
    """Forced-oracle and auto must agree numerically on the pytree ops
    (interpret-mode kernels and jnp oracles are bit-matching)."""
    key = jax.random.PRNGKey(16)
    tree = {"w": jax.random.normal(key, (300,)),
            "b": jax.random.normal(jax.random.fold_in(key, 1), (7,))}
    refsign = alignment.tree_sign(tree)
    monkeypatch.setenv(kbackend.ENV_VAR, "oracle")
    forced = np.asarray(ops.sign_align_ratio(tree, refsign))
    monkeypatch.delenv(kbackend.ENV_VAR)
    auto = np.asarray(ops.sign_align_ratio(tree, refsign))
    np.testing.assert_allclose(forced, auto, rtol=1e-6)
