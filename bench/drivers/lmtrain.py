"""Federated training of a language model: an ``ExperimentSession`` on
the fused scanned engine over token sequences, driven round batch after
round batch (the MLP driver's loop, ``bench/drivers/train.py``).

Set-up builds the session from the configuration and traffic files and
the seed (``DataSpec(dataset="lm", partition="iid")``: the program makes
the tokens, the reference makes the same ones from the seed), checks the
scan's physical (steps, batch) against the file, and makes the window's
own call ``check_calls`` times. Those rounds' records and the parameters
after them are what ``check`` compares with ``bench/ref_moonlight.py``.
The window also counts the rows routed to the held experts, for the
per-layer readers.
"""
from __future__ import annotations

import dataclasses
import gc
import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench import checks, ref_moonlight
from bench.drivers import train


def _spec(ctx):
    from repro.api import DataSpec, ExperimentSpec, WorldSpec
    cfg, tr = ctx.config, ctx.traffic
    return ExperimentSpec(
        model=cfg["program_model"],
        data=DataSpec(dataset="lm", partition="iid", seq_len=tr["seq_len"],
                      n_samples=tr["records"],
                      eval_samples=tr["eval_records"]),
        world=WorldSpec(num_clients=tr["clients"], profile="heterogeneous",
                        dropout_p=tr["dropout_p"]),
        strategy=tr["strategy"], strategy_kwargs=dict(tr["strategy_kwargs"]),
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
            f"not {tuple(tr['physical_steps_batch'])}")
    records = []
    for _ in range(tr["check_calls"]):
        records += session.run(tr["rounds_per_call"])
    params = ref_moonlight.flat_numpy(session.result().params)
    return {"ctx": ctx, "session": session, "sim": sim, "cohort": k,
            "first": [dataclasses.asdict(r) for r in records],
            "params": params}


compared_rounds = train.compared_rounds
end_to_end = train.end_to_end


@jax.jit
def _norms(p0, p):
    return {n: jnp.linalg.norm(p[n] - p0[n]) for n in p0}


def _leaf_norms(p0, p):
    """{leaf: ‖p − p0‖}, on the device (f32), each as a 1-vector."""
    return {n: np.asarray([v], np.float64)
            for n, v in jax.device_get(_norms(p0, p)).items()}


def numbers(recs_prog, p0, p_prog, recs_ref, p_ref) -> dict:
    """``checks.train_numbers`` with the leaves' changes measured on the
    device: change_gap reads only their norms, and at 568M parameters
    the host's float64 pass over them takes ~20 s."""
    zero = {n: np.zeros(1) for n in p0}
    return checks.train_numbers(recs_prog, zero, _leaf_norms(p0, p_prog),
                                recs_ref, _leaf_norms(p0, p_ref))


def window(state, seconds):
    session = state["session"]
    rows0 = session.expert_rows
    raw = train.window(state, seconds)
    raw["expert_rows"] = session.expert_rows - rows0
    return raw


def check(state):
    """Frees the session, then runs the reference over the rounds
    set-up drove and compares."""
    ctx = state["ctx"]
    state.pop("session")
    state.pop("sim")
    gc.collect()
    rounds, marks = compared_rounds(ctx.traffic)
    world = ref_moonlight.build_world(ctx.config, ctx.traffic, ctx.seed)
    ref_records, p0, p_ref = ref_moonlight.federated_rounds(
        world, ctx.config, ctx.traffic, ctx.seed, rounds=rounds,
        eval_marks=marks)
    for a, b in zip(state["first"], ref_records):
        print(f"bench: round {a['round']}: loss {a['loss']!r} / "
              f"{b['loss']!r}, applied {a['updates_applied']} / "
              f"{b['updates_applied']}, θ ratios {b['ratios']}",
              file=sys.stderr)
    got = numbers(state["first"], p0, state["params"], ref_records, p_ref)
    limits = ctx.traffic["limits"]
    return [(n, got[n], limits[n]) for n in limits], 0
