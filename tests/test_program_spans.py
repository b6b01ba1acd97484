"""The program's own trace marks: host spans of the engine's loop and of
the scorer, recorded under the profiler once per call, dispatch or pump
and nested as ``repro/obs.py`` lists them; the round program's
``fl_*`` named scopes in its compiled op metadata; and the session's
public dispatch counter."""
from __future__ import annotations

import collections
import pathlib
import sys

import jax
import numpy as np
import pytest

from repro.api import (DataSpec, ExperimentSession, ExperimentSpec,
                       StrategyConfig, WorldSpec)
from repro.api.strategies import get_strategy
from repro.configs import anomaly_mlp
from repro.models import api as model_api
from repro.serve import ModelSlot, ServeEngine

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spans as bench_spans  # noqa: E402

FL_SCOPES = ["fl_" + x for x in bench_spans.FL_SCOPES]


def _spec(**kw):
    return ExperimentSpec(**{**dict(
        model="anomaly-mlp-smoke",
        data=DataSpec(n_samples=1200, eval_samples=200),
        world=WorldSpec(num_clients=5, profile="heterogeneous",
                        dropout_p=0.2),
        strategy=get_strategy("ours").build(batch_size=32,
                                            select_fraction=0.8),
        rounds_per_dispatch=2, fused_eval=True), **kw})


def _traced(tmp_path, fn):
    """Runs ``fn`` under the profiler; the program's spans it recorded,
    as (name, start, end) in start order."""
    with jax.profiler.trace(str(tmp_path)):
        fn()
    events = bench_spans.load_events(str(tmp_path))
    return sorted(((e.name, e.start_ns, e.start_ns + e.dur_ns)
                   for e in events if e.name.startswith("repro.")),
                  key=lambda s: s[1])


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("how", ["run", "stream"])
def test_engine_loop_spans_once_per_call_and_dispatch(tmp_path, how):
    session = ExperimentSession.open(_spec())
    session.run(2)                                  # compile outside
    if how == "run":
        got = _traced(tmp_path, lambda: session.run(4))
        calls = 1
    else:
        got = _traced(tmp_path, lambda: list(session.stream(4)))
        calls = 2                                   # one chunk a dispatch
    counts = collections.Counter(n for n, _, _ in got)
    # 2 dispatches; the call's closing θ-reference check reads back with
    # its last dispatch's metrics
    assert counts == {"repro.fl.run": calls,
                      "repro.fl.session_records": calls,
                      "repro.fl.args": 2, "repro.fl.launch": 2,
                      "repro.fl.readback": 2, "repro.fl.records": 2}
    runs = [s for s in got if s[0] == "repro.fl.run"]
    for s in got:
        assert any(_inside(s, r) for r in runs), s
    steps = [n for n, _, _ in got if n != "repro.fl.run"]
    launch = ["repro.fl.args", "repro.fl.launch"]
    record = ["repro.fl.readback", "repro.fl.records"]
    if how == "run":
        # look-ahead: dispatch 2 is launched before dispatch 1's metrics
        # are waited for
        assert steps == (launch * 2 + record * 2
                         + ["repro.fl.session_records"])
    else:
        assert steps == (launch + record
                         + ["repro.fl.session_records"]) * 2


def test_serve_spans_once_per_submit_and_pump(tmp_path):
    cfg = anomaly_mlp.SMOKE
    eng = ServeEngine(ModelSlot(model_api.init_params(
        jax.random.PRNGKey(0), cfg)), cfg, max_batch=8)
    flows = np.random.default_rng(0).normal(
        size=(16, cfg.num_features)).astype(np.float32)
    eng.submit_many(flows[:8])
    eng.drain()                                     # compile outside
    out = []

    def serve():
        eng.submit_many(flows)
        out.extend(eng.pump())
        out.extend(eng.pump())

    got = _traced(tmp_path, serve)
    assert len(out) == 16 and eng.pending == 0
    pump = ["repro.serve.take", "repro.serve.pad", "repro.serve.score",
            "repro.serve.respond"]
    assert [n for n, _, _ in got] == ["repro.serve.submit"] + pump * 2
    for a, b in zip(got, got[1:]):
        assert a[2] <= b[1], (a, b)                 # one after another


def test_round_program_carries_every_phase_scope():
    session = ExperimentSession.open(_spec())
    sim = session._driver.sim
    session.run(2)
    fn = sim._scan_fns[2]
    seen = []
    sim._scan_fns[2] = lambda *a: seen.append(a) or fn(*a)
    session.run(2)
    # the compiled program's op metadata, which the device trace carries
    text = fn.lower(*seen[0]).compile().as_text()
    for scope in FL_SCOPES:
        assert f"/{scope}/" in text, scope


def test_session_counts_its_dispatches():
    session = ExperimentSession.open(_spec())
    assert session.dispatches == 0
    session.run(4)
    assert session.dispatches == 2 == session._driver.sim.dispatches
    assert session.overlapped_dispatches == 1
    list(session.stream(2))
    assert session.dispatches == 3
    assert session.overlapped_dispatches == 1       # stream: no look-ahead
    spmd = ExperimentSession.open(ExperimentSpec(
        model="anomaly-mlp-smoke", engine="spmd",
        data=DataSpec(n_samples=1200, eval_samples=200),
        world=WorldSpec(num_clients=4),
        strategy=StrategyConfig(mode="sync", dynamic_batch=False,
                                batch_size=32)))
    with pytest.raises(AttributeError, match="spmd"):
        spmd.dispatches
    with pytest.raises(AttributeError, match="spmd"):
        spmd.overlapped_dispatches


@pytest.mark.parametrize("calls", [(24, 24, 8), (8, 16, 24)])
def test_run_overlaps_dispatches_on_one_cached_program(calls):
    session = ExperimentSession.open(_spec(rounds_per_dispatch=8))
    sim = session._driver.sim
    for n in calls:
        session.run(n)
    assert session.overlapped_dispatches == session.dispatches - len(calls)
    # the first dispatch of a call takes its accounting and accuracy from
    # the host, the others from the carry: one signature all the same
    assert sim._scan_fns[8]._cache_size() == 1
    before = session.overlapped_dispatches
    list(session.stream(16))
    assert session.overlapped_dispatches == before
    assert sim._scan_fns[8]._cache_size() == 1
