"""The lower-precision control — the reference itself, put in the
program's place, computed in bfloat16 (training) or with fp8 matmul
inputs (scoring) — fails a limit of its cell, at a size a test run can
hold."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testkit import small_cell

from bench import checks, gen, reference  # noqa: E402


@pytest.mark.parametrize("cell", ["unsw.silo", "road.fleet"])
@pytest.mark.parametrize("seed", [3, 2147483659])
def test_training_control_fails(cell, seed):
    _, _, config, traffic = small_cell(cell)
    world = reference.build_world(config, traffic, seed)
    n = traffic["rounds_per_dispatch"]
    ref, p0, p_ref = reference.federated_rounds(
        world, config, traffic, seed, rounds=n, eval_marks={n - 1})
    low, _, p_low = reference.federated_rounds(
        world, config, traffic, seed, rounds=n, eval_marks={n - 1},
        dtype=jnp.bfloat16)
    got = checks.train_numbers(low, p0, p_low, ref, p_ref)
    lim = traffic["limits"]
    assert any(got[k] > lim[k] for k in lim), (got, lim)


@pytest.mark.parametrize("cell", ["unsw.replay"])
def test_scoring_control_fails(cell):
    _, _, config, traffic = small_cell(cell)
    params = reference.init_from_key(jax.random.PRNGKey(5),
                                     tuple(reference.layer_dims(config)),
                                     bias_scale=0.1)
    x, _ = gen.make_split(config["dataset"], 5, 4096, config)
    want = [np.asarray(a) for a in reference.probs_and_scores(
        params, jnp.asarray(x))]
    low = [np.asarray(a) for a in reference.probs_and_scores(
        params, jnp.asarray(x), matmul_dtype=jnp.float8_e4m3fn)]
    got = checks.serve_numbers(*low, *want)
    lim = traffic["limits"]
    assert any(got[k] > lim[k] for k in lim), (got, lim)
