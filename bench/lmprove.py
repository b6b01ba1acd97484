#!/usr/bin/env python3
"""Readings that ``bench/traffic/lmsilo.json``'s limits are set from.

    python bench/lmprove.py --seeds <a,b,...>

For each seed, in one process: the program's numbers (as a run's check
computes them) and the bf16 control's (``bench/ref_moonlight.py`` with
parameters, activations and updates in bfloat16, put in the program's
place), each beside the f32 reference at ``precision="highest"``. One
JSON line per seed on standard output, with the per-round records and
the θ ratios of the reference's clients. Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types

import run  # noqa: F401  (puts the checkout root and src/ on the path)

import jax.numpy as jnp  # noqa: E402

from bench import ref_moonlight  # noqa: E402

CELL = "moonlight.silo"


def readings(ctx, driver):
    state = driver.setup(ctx)
    state.pop("session")
    state.pop("sim")
    rounds, marks = driver.compared_rounds(ctx.traffic)
    world = ref_moonlight.build_world(ctx.config, ctx.traffic, ctx.seed)
    kw = dict(rounds=rounds, eval_marks=marks)
    ref, p0, p_ref = ref_moonlight.federated_rounds(
        world, ctx.config, ctx.traffic, ctx.seed, **kw)
    out = {"program": driver.numbers(state["first"], p0, state["params"],
                                     ref, p_ref),
           "theta_margin": min(r["theta_margin"] for r in ref)}
    del state["params"]
    ctl, _, p_ctl = ref_moonlight.federated_rounds(
        world, ctx.config, ctx.traffic, ctx.seed, dtype=jnp.bfloat16, **kw)
    out["control_bf16"] = driver.numbers(ctl, p0, p_ctl, ref, p_ref)
    runs = {"program": state["first"], "reference": ref, "control_bf16": ctl}
    out["rounds"] = {name: {k: [r.get(k) for r in recs] for k in (
        "loss", "accuracy", "bytes_sent", "updates_applied", "ratios")}
        for name, recs in runs.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bench, cell, config, traffic = run.load_cell(CELL)
    run.check_device(cell["chips"])
    run.enable_compile_cache()
    driver = run.load_driver(traffic["driver"])
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = types.SimpleNamespace(cell=CELL, config=config,
                                    traffic=traffic, seed=seed, seconds=0.0,
                                    annotate=run.annotate)
        t0 = time.perf_counter()
        out = readings(ctx, driver)
        out.update(seed=seed, seconds=time.perf_counter() - t0)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
