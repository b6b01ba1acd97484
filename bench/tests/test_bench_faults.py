"""A run whose timed path is broken underneath comes out not correct,
for each fault the cell can have; a sound run comes out correct."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench_testkit import run_small


def _failed(out):
    return (not out["correct"]) and any(
        c["value"] > c["limit"] for c in out["checks"].values()) \
        or out["failed"] > 0


@pytest.mark.parametrize("cell", ["unsw.silo", "road.fleet", "unsw.replay"])
def test_sound_run_is_correct(monkeypatch, cell):
    out = run_small(monkeypatch, cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["compiles_in_window"] == 0


@pytest.mark.parametrize("cell", ["unsw.silo", "road.fleet"])
def test_unchanged_state_fails(monkeypatch, cell):
    from repro.core import megastep

    def no_update(u, w, *a, **k):
        return jnp.zeros(u.shape[1:], jnp.float32)
    monkeypatch.setattr(megastep.arena_ops, "weighted_sum", no_update)
    out = run_small(monkeypatch, cell)
    assert _failed(out), out["checks"]


@pytest.mark.parametrize("cell", ["unsw.silo", "road.fleet"])
def test_half_batch_fails(monkeypatch, cell):
    from repro.core import megastep
    plain = megastep._train_cohort

    def half(cfg, opt, arena, params, batches, lr_scale):
        b = next(iter(batches.values())).shape[2] // 2
        return plain(cfg, opt, arena, params,
                     {k: v[:, :, :b] for k, v in batches.items()}, lr_scale)
    monkeypatch.setattr(megastep, "_train_cohort", half)
    out = run_small(monkeypatch, cell)
    assert _failed(out), out["checks"]


@pytest.mark.parametrize("cell", ["unsw.replay"])
def test_altered_answer_fails(monkeypatch, cell):
    from repro.models import mlp_detector
    plain = mlp_detector.predict

    def altered(params, x, cfg):
        p = plain(params, x, cfg)
        return p.at[::8].set(jnp.roll(p[::8], 1, axis=-1))
    monkeypatch.setattr(mlp_detector, "predict", altered)
    out = run_small(monkeypatch, cell)
    assert _failed(out), out["checks"]


@pytest.mark.parametrize("cell", ["unsw.replay"])
def test_half_batch_left_out_fails(monkeypatch, cell):
    from repro.models import mlp_detector
    plain = mlp_detector.predict

    def half(params, x, cfg):
        n = max(1, x.shape[0] // 2)
        p = plain(params, x[:n], cfg)
        rest = jnp.broadcast_to(p.mean(0), (x.shape[0] - n, p.shape[1]))
        return jnp.concatenate([p, rest])
    monkeypatch.setattr(mlp_detector, "predict", half)
    out = run_small(monkeypatch, cell)
    assert _failed(out), out["checks"]
