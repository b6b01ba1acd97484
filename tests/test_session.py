"""ExperimentSession: streaming, callbacks, and bit-exact
checkpoint/resume on both engines (including the scanned
rounds_per_dispatch path and quantized error-feedback state)."""
import dataclasses
import os

import jax
import numpy as np
import pytest

from repro.api import (CheckpointMismatchError, DataSpec, ExperimentSession,
                       ExperimentSpec, StrategyConfig, WorldSpec,
                       get_strategy, run_experiment)
from repro.api import session as session_mod
from repro.checkpoint.io import CheckpointCorruptError

SMALL = dict(model="anomaly-mlp-smoke",
             data=DataSpec(n_samples=1500, eval_samples=300),
             rounds=6, seed=0)


def _sim_spec(**kw):
    """Full-feature sim spec: selection + dropout + θ + dynamic batch +
    checkpointing — every piece of engine state a resume must restore."""
    base = dict(SMALL,
                world=WorldSpec(num_clients=5, profile="heterogeneous",
                                dropout_p=0.25),
                strategy=get_strategy("ours").build(batch_size=32,
                                                    select_fraction=0.8))
    return ExperimentSpec(**{**base, **kw})


def _spmd_spec(**kw):
    st = StrategyConfig(mode="sync", theta=0.65, selection=True,
                        select_fraction=0.5, dynamic_batch=False,
                        checkpointing=False, batch_size=32, lr=3e-2,
                        max_samples_per_round=64)
    base = dict(SMALL, engine="spmd", strategy=st,
                world=WorldSpec(num_clients=4, profile="heterogeneous",
                                dropout_p=0.2))
    return ExperimentSpec(**{**base, **kw})


def _assert_records_equal(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        fx, fy = dataclasses.astuple(x), dataclasses.astuple(y)
        # exact equality, NaN-tolerant (pre-first-eval scanned rounds)
        np.testing.assert_equal(fx, fy)


def _assert_params_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def _resume_case(spec, k, tmp_path, total=None):
    total = total or spec.rounds
    full = ExperimentSession.open(spec)
    full.run(total)

    part = ExperimentSession.open(spec)
    part.run(k)
    path = str(tmp_path / "session.ckpt")
    part.checkpoint(path)

    resumed = ExperimentSession.restore(path)
    assert resumed.rounds_done == k
    resumed.run(total - k)
    _assert_records_equal(full.records, resumed.records)
    _assert_params_equal(full.result().params, resumed.result().params)


# ---------------------------------------------------------------------------
# bit-exact resume: engine x execution path
# ---------------------------------------------------------------------------

def test_resume_bit_exact_sim_megastep(tmp_path):
    _resume_case(_sim_spec(), k=3, tmp_path=tmp_path)


def test_resume_bit_exact_sim_loop(tmp_path):
    _resume_case(_sim_spec(megastep=False), k=3, tmp_path=tmp_path)


def test_resume_bit_exact_sim_scanned_r4(tmp_path):
    # checkpoint at a dispatch boundary: records (incl. the amortized
    # per-dispatch accuracy samples) match the uninterrupted run exactly
    _resume_case(_sim_spec(rounds_per_dispatch=4, rounds=8), k=4,
                 tmp_path=tmp_path)


def test_resume_bit_exact_sim_quantized(tmp_path):
    # int8 + error-feedback arenas are part of the serialized state
    _resume_case(ExperimentSpec(
        **SMALL, world=WorldSpec(num_clients=4, profile="uniform"),
        strategy=get_strategy("ours").build(batch_size=32,
                                            dynamic_batch=False,
                                            quantize_updates=True)),
        k=3, tmp_path=tmp_path)


def test_resume_bit_exact_spmd(tmp_path):
    _resume_case(_spmd_spec(), k=3, tmp_path=tmp_path)


# ---------------------------------------------------------------------------
# dynamic-world scenarios: checkpoint/restore mid-drift (WorldState is
# part of the serialized engine state on every path)
# ---------------------------------------------------------------------------

def test_resume_bit_exact_sim_scenario_megastep(tmp_path):
    # checkpoint at k=3 of 6: the drift amplitude is mid-ramp, the link
    # walk mid-trajectory and the churn roster mid-rotation — a resumed
    # run must replay the identical world
    _resume_case(_sim_spec(scenario="dynamic"), k=3, tmp_path=tmp_path)


def test_resume_bit_exact_sim_scenario_loop(tmp_path):
    _resume_case(_sim_spec(scenario="dynamic", megastep=False), k=3,
                 tmp_path=tmp_path)


def test_resume_bit_exact_sim_scenario_scanned_r4(tmp_path):
    # WorldState rides in the lax.scan carry; a dispatch-boundary
    # checkpoint must hand the exact carry back to the next dispatch
    _resume_case(_sim_spec(scenario="dynamic", rounds_per_dispatch=4,
                           rounds=8), k=4, tmp_path=tmp_path)


def test_resume_bit_exact_spmd_scenario(tmp_path):
    # FLState.world serializes through the driver state_dict
    _resume_case(_spmd_spec(scenario="dynamic"), k=3, tmp_path=tmp_path)


def test_restore_scenario_mismatch_raises(tmp_path):
    spec = _sim_spec(scenario="dynamic", rounds=2)
    s = ExperimentSession.open(spec)
    s.run(2)
    path = str(tmp_path / "scn.ckpt")
    s.checkpoint(path)
    with pytest.raises(CheckpointMismatchError, match="scenario"):
        ExperimentSession.restore(
            path, dataclasses.replace(spec, scenario="drift"))
    with pytest.raises(CheckpointMismatchError, match="scenario"):
        ExperimentSession.restore(
            path, dataclasses.replace(spec, scenario=None))


def test_resume_scanned_midchunk_trajectory(tmp_path):
    """Checkpointing INSIDE a dispatch group (k not a multiple of R):
    the trajectory — every scan-computed field and the final params —
    is still bit-identical (per-round keys fold from the absolute round
    index); only the accuracy SAMPLING points may shift, because eval
    is amortized once per dispatch."""
    spec = _sim_spec(rounds_per_dispatch=4, rounds=8)
    full = ExperimentSession.open(spec)
    full.run(8)
    part = ExperimentSession.open(spec)
    part.run(3)                                   # mid-dispatch
    path = str(tmp_path / "mid.ckpt")
    part.checkpoint(path)
    resumed = ExperimentSession.restore(path)
    resumed.run(5)
    for a, b in zip(full.records, resumed.records):
        for f in ("round", "sim_time", "comm_time", "idle_time",
                  "bytes_sent", "updates_applied", "accept_rate", "loss"):
            assert getattr(a, f) == getattr(b, f), f
    _assert_params_equal(full.result().params, resumed.result().params)


# ---------------------------------------------------------------------------
# restore validation
# ---------------------------------------------------------------------------

def test_restore_mismatched_spec_raises(tmp_path):
    spec = _sim_spec(rounds=2)
    s = ExperimentSession.open(spec)
    s.run(2)
    path = str(tmp_path / "m.ckpt")
    s.checkpoint(path)
    with pytest.raises(CheckpointMismatchError, match="seed"):
        ExperimentSession.restore(path, dataclasses.replace(spec, seed=7))
    with pytest.raises(CheckpointMismatchError, match="engine"):
        ExperimentSession.restore(
            path, _spmd_spec(rounds=2, seed=0))
    # a different round BUDGET is not a mismatch (sessions extend runs)
    resumed = ExperimentSession.restore(
        path, dataclasses.replace(spec, rounds=5))
    resumed.run(3)
    assert resumed.rounds_done == 5


def test_checkpoint_is_atomic_and_restorable_without_spec(tmp_path):
    spec = _sim_spec(rounds=2)
    s = ExperimentSession.open(spec)
    s.run(1)
    path = str(tmp_path / "a.ckpt")
    s.checkpoint(path)
    assert os.path.exists(path) and not os.path.exists(path + ".tmp")
    # plain specs are embedded: restore() needs no spec argument
    assert ExperimentSession.restore(path).rounds_done == 1


# ---------------------------------------------------------------------------
# corruption detection + verified fallback (ISSUE 7 satellite)
# ---------------------------------------------------------------------------

def _corruption_case(tmp_path, spec):
    """Two checkpoints, newest corrupted: restore must refuse it by
    name and ``fallback=True`` must recover the older verified one
    bit-identically."""
    s = ExperimentSession.open(spec)
    s.run(1)
    old = str(tmp_path / "old.ckpt")
    s.checkpoint(old)
    params_at_1 = jax.tree.map(np.asarray, s.result().params)
    s.run(1)
    new = str(tmp_path / "new.ckpt")
    s.checkpoint(new)

    with open(new, "r+b") as f:               # bit-flip the newest
        f.seek(100)
        c = f.read(1)
        f.seek(100)
        f.write(bytes([c[0] ^ 0xFF]))
    with pytest.raises(CheckpointCorruptError, match="new.ckpt"):
        ExperimentSession.restore(new)
    assert session_mod.latest_good_checkpoint(str(tmp_path)) == old

    resumed = ExperimentSession.restore(new, fallback=True)
    assert resumed.rounds_done == 1           # recovered from old.ckpt
    for x, y in zip(jax.tree.leaves(params_at_1),
                    jax.tree.leaves(resumed.result().params)):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


def test_corrupt_restore_falls_back_bit_identical_sim(tmp_path):
    _corruption_case(tmp_path, _sim_spec(rounds=2))


def test_corrupt_restore_falls_back_bit_identical_spmd(tmp_path):
    _corruption_case(tmp_path, _spmd_spec(rounds=2))


def test_corrupt_modes_all_named(tmp_path):
    """Truncation, sidecar stripping and a stale sidecar digest each
    raise ``CheckpointCorruptError`` pointing at the artifact — pickle
    never sees untrusted bytes."""
    import json
    import shutil

    spec = _sim_spec(rounds=1)
    s = ExperimentSession.open(spec)
    s.run(1)
    path = str(tmp_path / "base.ckpt")
    s.checkpoint(path)
    meta = session_mod.read_sidecar(path)
    assert meta["sha256"] and meta["payload_bytes"] == \
        os.path.getsize(path)

    trunc = str(tmp_path / "trunc.ckpt")
    shutil.copyfile(path, trunc)
    shutil.copyfile(session_mod.sidecar_path(path),
                    session_mod.sidecar_path(trunc))
    with open(trunc, "r+b") as f:
        f.truncate(os.path.getsize(trunc) // 2)
    with pytest.raises(CheckpointCorruptError, match="trunc.ckpt"):
        ExperimentSession.restore(trunc)

    orphan = str(tmp_path / "orphan.ckpt")
    shutil.copyfile(path, orphan)             # no sidecar copied
    with pytest.raises(CheckpointCorruptError, match="sidecar"):
        ExperimentSession.restore(orphan)

    stale = str(tmp_path / "stale.ckpt")
    shutil.copyfile(path, stale)
    bad = dict(meta, sha256="0" * 64)
    with open(session_mod.sidecar_path(stale), "w") as f:
        json.dump(bad, f)
    with pytest.raises(CheckpointCorruptError, match="digest mismatch"):
        ExperimentSession.restore(stale)

    # the intact original still restores (and the corrupt variants are
    # exactly what latest_good_checkpoint must skip)
    assert session_mod.latest_good_checkpoint(str(tmp_path)) == path
    assert ExperimentSession.restore(path).rounds_done == 1


# ---------------------------------------------------------------------------
# streaming + callbacks
# ---------------------------------------------------------------------------

def test_stream_yields_rounds_in_order():
    s = ExperimentSession.open(_sim_spec(rounds=4))
    rounds = [r.round for r in s.stream(4)]
    assert rounds == [0, 1, 2, 3]
    assert s.rounds_done == 4


def test_iter_runs_spec_budget():
    s = ExperimentSession.open(_sim_spec(rounds=3))
    assert len(list(s)) == 3


def test_callback_early_stop():
    s = ExperimentSession.open(_sim_spec(rounds=6))
    seen = []

    def stop_after_two(rec):
        seen.append(rec.round)
        if rec.round >= 1:
            return False                      # early-stop hook

    s.add_callback(stop_after_two)
    list(s.stream(6))
    assert s.stopped
    assert s.rounds_done == 2 and seen == [0, 1]
    assert s.run(4) == []                     # stopped sessions stay put


def test_run_then_more_rounds_continues_numbering():
    s = ExperimentSession.open(_sim_spec(rounds=4))
    s.run(2)
    more = s.run(2)
    assert [r.round for r in more] == [2, 3]
    assert [r.round for r in s.records] == [0, 1, 2, 3]


def _assert_trees_bitwise_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


@pytest.mark.parametrize("kw,rounds", [
    (dict(fused_eval=True), 24),                       # async quorum + θ
    (dict(fused_eval=True, scenario="dynamic"), 24),   # churn/links/drift
    (dict(fused_eval=False), 24),                      # host eval
    (dict(fused_eval=True), 20),                       # partial last
], ids=["quorum-theta", "dynamic", "host-eval", "partial"])
def test_run_look_ahead_matches_stream_bitwise(kw, rounds):
    # run() enqueues each dispatch before reading the previous one's
    # metrics back; stream() advances one dispatch a chunk with no
    # look-ahead and forces the same end-of-run eval. Evals every 5th
    # round, so dispatches start on carried-forward accuracy.
    spec = _sim_spec(rounds_per_dispatch=8, rounds=rounds, eval_every=5,
                     **kw)
    assert spec.strategy.selection and spec.strategy.dynamic_batch
    ran = ExperimentSession.open(spec)
    ran.run(rounds)
    streamed = ExperimentSession.open(spec)
    list(streamed.stream(rounds))
    a, b = ran._driver.sim, streamed._driver.sim
    assert a.overlapped_dispatches == -(-rounds // 8) - 1   # all but one
    assert b.overlapped_dispatches == 0
    _assert_records_equal(a.history, b.history)
    _assert_records_equal(ran.records, streamed.records)
    assert a.failure_log == b.failure_log
    assert a.server_step == b.server_step
    for name in ("_params_mat", "_ref_mat", "_scan_ref_valid", "_scan_ctl",
                 "_world_state"):
        _assert_trees_bitwise_equal(getattr(a, name), getattr(b, name))
    _assert_trees_bitwise_equal(a.state_dict()["scan"],
                                b.state_dict()["scan"])


def test_run_experiment_is_session_wrapper():
    spec = _sim_spec(rounds=3)
    res = run_experiment(spec)
    sess = ExperimentSession.open(spec)
    sess.run(3)
    _assert_records_equal(res.records, sess.result().records)
    _assert_params_equal(res.params, sess.result().params)
