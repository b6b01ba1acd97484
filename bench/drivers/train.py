"""Federated training traffic: an ``ExperimentSession`` on the fused
scanned engine, driven round batch after round batch.

Set-up builds the session from the traffic file and the seed (data from
the benchmark's own generators through ``DataSpec(factory=...)``), checks
the scan's physical (steps, batch) against the file, and makes the
window's own call ``check_calls`` times: ``session.run(rounds_per_call)``,
which compiles the one scan program the window uses and chains its
dispatches through the engine's host loop (parameters, θ reference,
control and world state, key and accounting handed from one dispatch to
the next). Those rounds' records and the parameters after them are what
``check`` compares with the reference. The window then makes the same
call on the same session until the time is up.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench import checks, gen, reference


def _spec(ctx):
    from repro.api import DataSpec, ExperimentSpec, WorldSpec
    cfg, tr = ctx.config, ctx.traffic
    return ExperimentSpec(
        model=cfg["program_model"],
        data=DataSpec(n_samples=tr["records"],
                      eval_samples=tr["eval_records"], alpha=tr["alpha"],
                      factory=lambda s, n: gen.make_split(
                          cfg["dataset"], s, n, cfg)),
        world=WorldSpec(num_clients=tr["clients"], profile="heterogeneous",
                        dropout_p=tr["dropout_p"]),
        strategy=tr["strategy"], strategy_kwargs=dict(tr["strategy_kwargs"]),
        scenario=tr.get("scenario"),
        rounds_per_dispatch=tr["rounds_per_dispatch"], fused_eval=True,
        eval_every=tr["eval_every"], seed=ctx.seed)


def setup(ctx):
    from repro.api import ExperimentSession
    tr = ctx.traffic
    session = ExperimentSession.open(_spec(ctx))
    sim = session._driver.sim
    k, steps, batch = sim._scan_shapes()
    print(f"bench: scan physical shape (cohort, steps, batch) = "
          f"({k}, {steps}, {batch})", flush=True)
    if [steps, batch] != tr["physical_steps_batch"]:
        raise RuntimeError(
            f"the scan's physical (steps, batch) is ({steps}, {batch}), "
            f"not {tuple(tr['physical_steps_batch'])}: the seed's "
            "partition left a client too small")
    records = []
    for _ in range(tr["check_calls"]):
        records += session.run(tr["rounds_per_call"])
    params = {n: np.asarray(v, np.float32)
              for n, v in session.result().params.items()}
    return {"ctx": ctx, "session": session, "sim": sim, "cohort": k,
            "first": [dataclasses.asdict(r) for r in records], "params": params}


def compared_rounds(traffic):
    """(rounds set-up drives and the check compares, the rounds whose
    eval the end of a ``session.run`` call forces)."""
    per, calls = traffic["rounds_per_call"], traffic["check_calls"]
    return per * calls, {per * (i + 1) - 1 for i in range(calls)}


def window(state, seconds):
    session, sim = state["session"], state["sim"]
    per_call = state["ctx"].traffic["rounds_per_call"]
    every = state["ctx"].traffic["eval_every"]
    d0 = sim.dispatches
    r0 = session.rounds_done
    t0 = time.perf_counter()
    calls = 0
    while True:
        with state["ctx"].annotate("bench.session_run"):
            session.run(per_call)
        calls += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    rounds = session.rounds_done - r0
    evals = sum(1 for r in range(r0, r0 + rounds)
                if r % every == 0 or (r - r0) % per_call == per_call - 1)
    return {"attempted": rounds, "rounds": rounds, "seconds": elapsed, "calls": calls,
            "dispatches": sim.dispatches - d0, "evals": evals,
            "cohort": state["cohort"]}


def end_to_end(raw):
    return {"rounds_per_s": raw["rounds"] / raw["seconds"]}


def check(state):
    """Frees the session, then runs the reference over the rounds
    set-up drove and compares."""
    ctx = state["ctx"]
    state.pop("session")
    state.pop("sim")
    gc.collect()
    rounds, marks = compared_rounds(ctx.traffic)
    world = reference.build_world(ctx.config, ctx.traffic, ctx.seed)
    ref_records, p0, p_ref = reference.federated_rounds(
        world, ctx.config, ctx.traffic, ctx.seed, rounds=rounds,
        eval_marks=marks)
    got = checks.train_numbers(state["first"], p0, state["params"],
                               ref_records, p_ref)
    limits = ctx.traffic["limits"]
    return [(n, got[n], limits[n]) for n in limits], 0
