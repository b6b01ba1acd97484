"""The scanned round program's packed record table (core/records.py).

One row gather at ``offsets[cohort] + idx`` must return, bit for bit, the
batch that a per-leaf gather ``leaf[cohort, idx]`` from client stacks
padded to the largest client gives, for the same key; the seed-stacked
sweep pads each seed's table with rows to the longest; and the compiled
round program reads its records through ONE gather a round, whatever
the number of leaves.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (DataSpec, ExperimentSession, ExperimentSpec,
                       WorldSpec, get_strategy, run_experiment,
                       run_scanned_seed_batch)
from repro.api import runner
from repro.core import records


def _f32(rng, n, *shape):
    x = rng.standard_normal((n,) + shape).astype(np.float32)
    if x.size:                        # bit patterns a lossy path would lose
        x.flat[0], x.flat[-1] = np.float32(-0.0), np.float32(np.nan)
    return x


def _features_labels(sizes, features, classes=10):
    def make(rng, n):
        return {"x": _f32(rng, n, features),
                "y": rng.integers(0, classes, n).astype(np.int32)}
    return make


def _tokens(sizes, seq=16):
    def make(rng, n):
        return {"tokens": rng.integers(0, 50_000, (n, seq), dtype=np.int32),
                "labels": rng.integers(0, 50_000, (n, seq), dtype=np.int32)}
    return make


def _narrow(sizes):
    def make(rng, n):
        return {"h": _f32(rng, n, 3).astype(jnp.bfloat16),
                "b": rng.integers(0, 256, (n, 5)).astype(np.uint8),
                "m": rng.random((n, 2)) < 0.5,
                "s": rng.integers(-2**15, 2**15, n).astype(np.int16)}
    return make


# name -> (client sizes, cohort, leaf maker)
CASES = {
    "unsw_widths": ([300, 517, 41, 1200], [3, 0, 1, 2], _features_labels,
                    dict(features=49)),
    "tokens_labels": ([32, 32, 32], [2, 0], _tokens, {}),
    "unequal_sizes": ([1, 2000, 37, 5, 640], [0, 1, 2, 3, 4],
                      _features_labels, dict(features=7)),
    "empty_client": ([0, 33, 0, 12], [0, 1, 2, 3], _features_labels,
                     dict(features=4)),
    "repeated_client": ([50, 9, 70], [2, 2, 0, 2], _features_labels,
                        dict(features=6)),
    "narrow_dtypes": ([20, 3, 11], [1, 2, 0], _narrow, {}),
}


def _padded_take(clients, cohort, idx):
    """The per-leaf gather from client stacks padded to the largest
    client: what the round program read before the table."""
    cap = max(len(next(iter(c.values()))) for c in clients)
    out = {}
    for k in clients[0]:
        stack = np.stack([np.concatenate(
            [c[k], np.zeros((cap - len(c[k]),) + c[k].shape[1:],
                            c[k].dtype)]) for c in clients])
        out[k] = jnp.asarray(stack)[cohort[:, None, None], idx]
    return out


@pytest.mark.parametrize("case", list(CASES))
def test_packed_gather_matches_padded_gather(case):
    sizes, cohort, maker, kw = CASES[case]
    rng = np.random.default_rng(7)
    make = maker(sizes, **kw)
    clients = [make(rng, n) for n in sizes]
    table = records.pack(clients)
    assert table.table.shape[0] == sum(sizes) + 1      # no padding rows
    cohort = jnp.asarray(cohort, jnp.int32)
    sz = jnp.asarray(sizes, jnp.int32)[cohort]
    # the round program's own draw: (K, steps, B) rows under each size
    idx = jax.random.randint(jax.random.PRNGKey(3), (len(cohort), 3, 8),
                             0, sz[:, None, None])
    got = jax.jit(lambda t, c, i: t.take(c, i))(table, cohort, idx)
    want = _padded_take(clients, cohort, idx)
    assert sorted(got) == sorted(want)
    for k in want:
        a, b = np.asarray(got[k]), np.asarray(want[k])
        assert a.dtype == b.dtype and a.shape == b.shape, k
        assert a.tobytes() == b.tobytes(), k


def _batch_spec():
    return dataclasses.replace(
        ExperimentSpec(
            model="anomaly-mlp-smoke",
            data=DataSpec(n_samples=1200, eval_samples=300,
                          partition="iid"),
            world=WorldSpec(num_clients=5, profile="heterogeneous"),
            rounds=5, seed=0, rounds_per_dispatch=3, fused_eval=True),
        eval_every=2)


def test_seed_batch_pads_rows_to_the_longest_table(monkeypatch):
    # seed 1 loses 40 of its last client's records: its table is shorter,
    # its trace shape the same (8 steps of 32 either way)
    build_world = ExperimentSpec.build_world

    def trimmed(spec):
        world = build_world(spec)
        if spec.seed == 1:
            world.client_arrays[-1] = {
                k: v[:200] for k, v in world.client_arrays[-1].items()}
        return world

    monkeypatch.setattr(ExperimentSpec, "build_world", trimmed)
    spec = _batch_spec()
    seeds = [0, 1]
    rows = []
    for s in seeds:
        sim = runner.build_simulation(dataclasses.replace(spec, seed=s))
        sim._scan_setup()
        rows.append(sim._scan_world[0].table.shape[0])
    assert rows == [1201, 1161]
    batch = run_scanned_seed_batch(spec, seeds)
    for s, res in zip(seeds, batch):
        solo = run_experiment(dataclasses.replace(spec, seed=s))
        assert len(res.records) == len(solo.records) == spec.rounds
        for a, b in zip(res.records, solo.records):
            # tests/test_fused_eval.py's vmap-vs-solo tolerances
            assert a.updates_applied == b.updates_applied
            np.testing.assert_allclose(a.sim_time, b.sim_time, rtol=1e-9)
            np.testing.assert_allclose(a.accuracy, b.accuracy, atol=1e-5)
            np.testing.assert_allclose(a.loss, b.loss, rtol=1e-4)


class _Captured(Exception):
    pass


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _eqns(sub)


def test_round_program_gathers_record_data_once():
    # silo's shape: UNSW widths (49 features and a label), every client
    # in every round, several local steps
    n_records = 2000
    spec = ExperimentSpec(
        model="anomaly-mlp",
        data=DataSpec(n_samples=n_records, eval_samples=200, alpha=1.25),
        world=WorldSpec(num_clients=10, profile="heterogeneous"),
        strategy=get_strategy("ours").build(
            batch_size=16, max_samples_per_round=64, dynamic_batch=False,
            select_fraction=1.0),
        rounds_per_dispatch=2, fused_eval=True, eval_every=2, seed=0)
    session = ExperimentSession.open(spec)
    sim = session._driver.sim
    got = []

    def capture(*args):
        got.append(args)
        raise _Captured

    sim._scan_fns[2] = capture           # the arguments of a real dispatch
    with pytest.raises(_Captured):
        session.run(2)
    del sim._scan_fns[2]
    jaxpr = jax.make_jaxpr(sim._scan_fn(2))(*got[0])
    reads = [e for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "gather"
             and "fl_gather" in str(e.source_info.name_stack)
             and e.invars[0].aval.size >= n_records]
    assert len(reads) == 1, [e.invars[0].aval for e in reads]
    assert reads[0].invars[0].aval.shape == (n_records + 1, 49 + 1)
