"""Loop-aware HLO analyzer: trip-count detection, dot flops, collectives."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.roofline import hlo_census


def _compiled(fn, *specs):
    return jax.jit(fn).lower(*specs).compile()


def test_flat_dot_flops():
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    c = _compiled(lambda x, y: x @ y, a, b)
    res = hlo_census.analyze(c.as_text())
    assert res["flops"] == 2 * 64 * 128 * 32


def test_scan_multiplies_by_trip_count():
    L, D = 7, 32

    def f(ws, x):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, ws)
        return h

    ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32)
    x = jax.ShapeDtypeStruct((4, D), jnp.float32)
    c = _compiled(f, ws, x)
    res = hlo_census.analyze(c.as_text())
    expected = L * 2 * 4 * D * D
    assert abs(res["flops"] - expected) / expected < 0.01, \
        (res["flops"], expected, res["while_trips"])
    assert L in res["while_trips"].values()
    # XLA's own cost analysis counts the body once -> analyzer must exceed it
    xla_flops = float(c.cost_analysis().get("flops", 0.0))
    assert res["flops"] > xla_flops


def test_nested_scan_trips_multiply():
    Lo, Li, D = 3, 5, 16

    def f(ws, x):
        def outer(h, w):
            def inner(hh, _):
                return jnp.tanh(hh @ w), None
            h2, _ = jax.lax.scan(inner, h, None, length=Li)
            return h2, None
        h, _ = jax.lax.scan(outer, x, ws)
        return h

    ws = jax.ShapeDtypeStruct((Lo, D, D), jnp.float32)
    x = jax.ShapeDtypeStruct((2, D), jnp.float32)
    c = _compiled(f, ws, x)
    res = hlo_census.analyze(c.as_text())
    expected = Lo * Li * 2 * 2 * D * D
    assert abs(res["flops"] - expected) / expected < 0.02, \
        (res["flops"], expected, res["while_trips"])


def test_traffic_positive_and_bounded():
    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    c = _compiled(lambda x: (x @ x).sum(), a)
    res = hlo_census.analyze(c.as_text())
    assert res["traffic_bytes"] >= 256 * 256 * 4       # at least the input
    assert res["traffic_bytes"] < 100 * 256 * 256 * 4  # sane upper bound


def test_shape_bytes_parser():
    from repro.roofline.hlo_census import _shape_elems_bytes
    e, b = _shape_elems_bytes("f32[128,1024]{1,0}")
    assert e == 128 * 1024 and b == 4 * e
    e, b = _shape_elems_bytes("(bf16[8,2], s32[])")
    assert b == 8 * 2 * 2 + 4
    e, b = _shape_elems_bytes("pred[]")
    assert e == 1 and b == 1
