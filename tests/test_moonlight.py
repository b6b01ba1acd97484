"""Moonlight-16B-A3B's block (moonlight-16b-a3b-ep8) against its plain
reference (bench/ref_moonlight.py) at the CPU test size, f32, highest
precision: logits, loss and gradients; the expert-parallel share; the
dropless expert layer; and the engine's cohort training, slot by slot
for a large model and vmapped for the detectors."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import ref_moonlight
from repro.configs import anomaly_mlp, moonlight_16b_a3b, registry
from repro.core import megastep
from repro.kernels.arena import ParamArena
from repro.models import api, moe
from repro.optim.adamw import sgd

CFG = moonlight_16b_a3b.SMOKE
# the same block in the reference's (the published config's) terms
REF = dict(hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
           qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
           moe_intermediate_size=32, intermediate_size=96,
           n_routed_experts=2, router_experts=16, num_experts_per_tok=4,
           n_shared_experts=2, num_hidden_layers=3, first_k_dense_replace=1,
           vocab_size=256, rms_norm_eps=1e-5, rope_theta=50000.0,
           routed_scaling_factor=2.446, router_bias_std=0.02,
           router_bias_seed=0)
RTOL = 1e-5     # f32 on both sides; the program's sums run in another order


def _batch(seed=0, b=2, s=16):
    rng = np.random.default_rng(seed)
    return {"tokens": jnp.asarray(rng.integers(0, 256, (b, s)), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, 256, (b, s)), jnp.int32)}


def _close(a, b, rtol=RTOL):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.max(np.abs(b)), 1e-30)
    assert np.max(np.abs(a - b)) <= rtol * scale, np.max(np.abs(a - b)) / scale


def test_registered_with_its_smoke_config():
    from repro.api import ExperimentSpec
    assert registry.get_config("moonlight-16b-a3b-ep8") is \
        moonlight_16b_a3b.CONFIG
    assert registry.get_config("moonlight-16b-a3b-ep8", smoke=True) is CFG
    assert ExperimentSpec(model="moonlight-16b-a3b-ep8-smoke") \
        .resolve_model() is CFG
    full = moonlight_16b_a3b.CONFIG
    shapes = jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0),
                                                    full))
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == full.param_count() == 568_484_352
    assert ParamArena(shapes).rows == 555_161


def test_init_follows_the_reference_rule():
    ours = api.init_params(jax.random.PRNGKey(5), CFG)
    theirs = ref_moonlight.init_params(5, REF)
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_logits_loss_and_gradients_match_reference():
    params = api.init_params(jax.random.PRNGKey(1), CFG)
    batch = _batch()
    with jax.default_matmul_precision("highest"):
        logits = api.module_for(CFG).forward(params, batch, CFG)[0]
        want = ref_moonlight.forward(REF, params, batch["tokens"])
        (loss, stats), grads = jax.value_and_grad(
            lambda p: api.loss_and_stats(p, batch, CFG), has_aux=True)(params)
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: ref_moonlight.loss(REF, p, batch["tokens"],
                                         batch["labels"]))(params)
    _close(logits, want)
    _close(loss, ref_loss)
    for g, rg in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        _close(g, rg)
    assert 0 < int(stats["expert_rows"]) <= 2 * 16 * CFG.top_k * 2


def _moe_layer(seed=2, tokens=24, held_from=0, held=None):
    """One MoE layer's parameters holding ``held`` experts from
    ``held_from`` (all 16 when None), sliced from one full layer."""
    full = CFG.replace(experts_held=CFG.num_experts)
    p = moe.moe_params(full, jax.random.PRNGKey(seed), jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, tokens, 64))
    bias = moe.router_bias(CFG, 1)[0]
    if held is None:
        return full, p, x, bias
    cut = dict(p, **{k: p[k][held_from:held_from + held]
                     for k in ("wg", "wu", "wd")})
    return CFG.replace(expert_offset=held_from, experts_held=held), cut, x, \
        bias


def test_expert_shares_sum_to_the_uncut_layer():
    full, p, x, bias = _moe_layer()
    with jax.default_matmul_precision("highest"):
        want = ref_moonlight.experts(dict(REF, n_routed_experts=16), p,
                                     x[0], bias)
        shared = ref_moonlight._ffn(p["shared"], x[0])
        parts = []
        for s in range(8):
            cfg, cut, _, _ = _moe_layer(held_from=2 * s, held=2)
            out, _ = moe.held_expert_ffn(cfg, cut, x, bias)
            parts.append(out[0] - shared)
            _close(out[0], ref_moonlight.experts(REF, cut, x[0], bias,
                                                 offset=2 * s))
    _close(sum(parts) + shared, want)


def test_every_token_to_one_expert_drops_nothing():
    cfg, p, x, _ = _moe_layer(held_from=0, held=2)
    bias = jnp.zeros((16,)).at[1].set(100.0)     # expert 1 always chosen
    with jax.default_matmul_precision("highest"):
        out, stats = moe.held_expert_ffn(cfg, p, x, bias)
        want = ref_moonlight.experts(REF, p, x[0], bias)
    tokens = x.shape[1]
    assert int(stats["expert_rows"]) >= tokens   # capacity 1.25 would drop
    _close(out[0], want)


def _cohort_inputs(cfg, k=2, steps=2, seq=16):
    params = api.init_params(jax.random.PRNGKey(4), cfg)
    arena = ParamArena(params)
    rng = np.random.default_rng(4)
    batches = {n: jnp.asarray(rng.integers(0, cfg.vocab_size,
                                           (k, steps, 1, seq)), jnp.int32)
               for n in ("tokens", "labels")}
    return arena, arena.pack(params), batches, jnp.asarray([1.0, 0.5])


def test_sequential_and_vmapped_cohorts_agree(monkeypatch):
    arena, mat, batches, scale = _cohort_inputs(CFG)
    assert not megastep.cohort_is_sequential(arena, 2)
    run = lambda: megastep._train_cohort(  # noqa: E731
        CFG, sgd(1e-3), arena, mat, batches, scale)
    vmapped = run()
    monkeypatch.setattr(megastep, "VMAP_COHORT_BYTES", 0)
    assert megastep.cohort_is_sequential(arena, 2)
    sequential = run()
    for a, b in zip(jax.tree.leaves(vmapped), jax.tree.leaves(sequential)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-9)


@pytest.mark.parametrize("cfg,cohort,sequential", [
    (anomaly_mlp.CONFIG, 10, False),          # unsw.silo
    (anomaly_mlp.ROAD_CONFIG, 100, False),    # road.fleet
    (moonlight_16b_a3b.CONFIG, 2, True),      # moonlight.silo
])
def test_only_large_models_train_slot_by_slot(cfg, cohort, sequential):
    shapes = jax.eval_shape(lambda: api.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    assert megastep.cohort_is_sequential(ParamArena(shapes),
                                         cohort) is sequential


def test_session_trains_on_the_scanned_engine_with_fused_eval():
    from repro.api import (DataSpec, ExperimentSession, ExperimentSpec,
                           WorldSpec)
    spec = ExperimentSpec(
        model="moonlight-16b-a3b-ep8-smoke",
        data=DataSpec(dataset="lm", partition="iid", seq_len=16,
                      n_samples=32, eval_samples=2),
        world=WorldSpec(num_clients=8), strategy="ours",
        strategy_kwargs=dict(batch_size=1, max_samples_per_round=2,
                             dynamic_batch=False, select_fraction=0.25,
                             theta=0.55, lr=1e-3),
        rounds_per_dispatch=2, fused_eval=True, eval_every=4, seed=3)
    session = ExperimentSession.open(spec)
    records = session.run(4)
    assert session.dispatches == 2
    assert all(r.expert_rows > 0 for r in records)
    assert session.expert_rows == sum(r.expert_rows for r in records)
    assert np.isfinite([r.loss for r in records]).all()
    assert np.isfinite(records[-1].accuracy)
