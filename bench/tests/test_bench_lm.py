"""The language-model cell at a CPU size: the driver's set-up, window and
check pass against ``bench/ref_moonlight.py``; a planted fault and the
bf16 control break a limit; the cell's readers give sane numbers on a
synthetic trace and nothing where there is nothing to read."""
from __future__ import annotations

import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_testkit import run
from bench import checks, flops, lmflops, ref_moonlight

# moonlight-16b-a3b-ep8-smoke, as the reference reads it
SMALL_CONFIG = dict(
    program_model="moonlight-16b-a3b-ep8-smoke", hidden_size=64,
    num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, moe_intermediate_size=32,
    intermediate_size=96, n_routed_experts=2, router_experts=16,
    num_experts_per_tok=4, n_shared_experts=2, num_hidden_layers=3,
    first_k_dense_replace=1, vocab_size=256, arena_rows=151)
SMALL_TRAFFIC = dict(records=16, eval_records=2, seq_len=32)


def small_cell():
    bench, cell, config, traffic = run.load_cell("moonlight.silo")
    return bench, cell, dict(config, **SMALL_CONFIG), dict(traffic,
                                                           **SMALL_TRAFFIC)


def run_small(monkeypatch, seed=11, seconds=1.0):
    monkeypatch.setattr(run, "check_device", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    bench, cell, config, traffic = small_cell()
    return run.run_cell(bench, cell, config, traffic, seed, seconds, 0,
                        t_start=time.perf_counter())


def _broken(out):
    return not out["correct"] and any(
        c["value"] > c["limit"] for c in out["checks"].values())


def test_small_config_matches_program():
    from repro.configs import moonlight_16b_a3b
    from repro.kernels.arena import ParamArena
    from repro.models import api
    cfg = moonlight_16b_a3b.SMOKE
    _, _, config, _ = small_cell()
    shapes = jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    assert ParamArena(shapes).rows == config["arena_rows"]
    ours = ref_moonlight.init_params(3, config)
    theirs = api.init_params(jax.random.PRNGKey(3), cfg)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_sound_run_is_correct(monkeypatch):
    out = run_small(monkeypatch)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compiles_in_window"] == 0


def test_half_sequence_fault_breaks_a_limit(monkeypatch):
    from repro.core import megastep
    plain = megastep._train_cohort

    def half(cfg, opt, arena, params_mat, batches, lr_scale):
        s = batches["tokens"].shape[-1] // 2
        return plain(cfg, opt, arena, params_mat,
                     {k: v[..., :s] for k, v in batches.items()}, lr_scale)
    monkeypatch.setattr(megastep, "_train_cohort", half)
    assert _broken(run_small(monkeypatch))


def test_bf16_control_breaks_a_limit(monkeypatch):
    seed = 11
    _, _, config, traffic = small_cell()
    from bench.drivers import lmtrain
    rounds, marks = lmtrain.compared_rounds(traffic)
    world = ref_moonlight.build_world(config, traffic, seed)
    sound = ref_moonlight.federated_rounds(world, config, traffic, seed,
                                           rounds, marks)
    control = ref_moonlight.federated_rounds(world, config, traffic, seed,
                                             rounds, marks,
                                             dtype=jnp.bfloat16)
    got = checks.train_numbers(control[0], sound[1], control[2], sound[0],
                               sound[2])
    limits = traffic["limits"]
    assert any(got[n] > limits[n] for n in limits if n in got), got


def test_device_change_norms_give_the_checks_numbers():
    from bench.drivers import lmtrain
    rng = np.random.default_rng(0)
    shapes = {"a": (3, 40), "b": (7,), "c": (2, 5, 6)}
    p0, p1, p2 = ({n: rng.normal(size=s).astype(np.float32)
                   for n, s in shapes.items()} for _ in range(3))
    recs = [dict(loss=1.0 + i, accuracy=-2.0 + i, bytes_sent=8.0 * (i + 1),
                 sim_time=0.5 * i) for i in range(3)]
    other = [dict(r, loss=r["loss"] * 1.01) for r in recs]
    got = lmtrain.numbers(recs, p0, p1, other, p2)
    want = checks.train_numbers(recs, p0, p1, other, p2)
    assert got.keys() == want.keys()
    for n in want:
        assert got[n] == pytest.approx(want[n], rel=1e-5), n


def _ctx(kernels, raw=None):
    _, _, config, traffic = run.load_cell("moonlight.silo")
    raw = dict(rounds=8, seconds=4.0, cohort=2, evals=2,
               expert_rows=8 * 2 * 2 * 4 * 3072, dispatches=4,
               **(raw or {}))
    return types.SimpleNamespace(
        config=config, traffic=traffic, raw=raw, seconds=4.0,
        trace={"kernels": kernels, "busy_s": 3.0, "window_s": 4.0},
        peaks=flops.peaks("TPU v5 lite"))


@pytest.mark.parametrize("metric,kernels", [
    ("expert_gmm_roofline", {"expert_gmm": {"seconds": 1.2,
                                            "calls": 1140}}),
    ("mla_attention_roofline", {
        "mla_fwd_train": {"seconds": 0.5, "calls": 160},
        "mla_fwd_eval": {"seconds": 0.05, "calls": 10},
        "mla_dq": {"seconds": 0.4, "calls": 80},
        "mla_dkv": {"seconds": 0.6, "calls": 80}}),
    ("lm_train_mfu", {}),
])
def test_readers_give_shares(metric, kernels):
    value = run.load_reader(metric)(_ctx(kernels))
    assert 0.0 < value < 100.0


@pytest.mark.parametrize("metric", ["expert_gmm_roofline",
                                    "mla_attention_roofline",
                                    "lm_train_mfu"])
def test_readers_find_nothing_without_their_inputs(metric):
    m = _ctx({})
    m.raw.pop("expert_rows")
    assert run.load_reader(metric)(m) is None


def test_flops_follow_the_shapes():
    _, _, config, _ = run.load_cell("moonlight.silo")
    one = lmflops.forward_flops(config, 1, 4096, 0)
    assert lmflops.forward_flops(config, 2, 4096, 0) == pytest.approx(2 * one)
    rows = lmflops.forward_flops(config, 1, 4096, 1000) - one
    assert rows == pytest.approx(2 * 1000 * 3 * 2048 * 1408)
    fwd = lmflops.mla_attention(config, "fwd", 1, 4096)
    assert fwd["ops"] == pytest.approx(2 * 16 * 4096 * 4097 / 2 * 320)
