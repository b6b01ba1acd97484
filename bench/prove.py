#!/usr/bin/env python3
"""Readings that the limits in ``bench/traffic/*.json`` are set from.

    python bench/prove.py --workload <cell> --seeds <a,b,...> [--seconds s]

For each seed, in one process: the program's numbers (as a run's check
computes them), the lower-precision control's (the reference put in the
program's place: in bfloat16 for training, with fp8 matmul inputs for
scoring, and in bfloat16 too for the record), and the faults' (training:
the reference training on half of each batch, the mean taken over it;
scoring: each sampled answer moved to its neighbour's id). One JSON
line per seed on standard output, with each training run's per-round
records beside its numbers. A state left unchanged needs no run: it
reads change_gap = 1. Needs the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types

import run  # noqa: F401  (puts the checkout root and src/ on the path)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from bench import checks, reference, serving  # noqa: E402


def _half_batch(fn):
    def half(params, xb, yb, lr, momentum):
        b = xb.shape[2] // 2
        return fn(params, xb[:, :, :b], yb[:, :, :b], lr=lr,
                  momentum=momentum)
    return half


def train_readings(ctx, driver, faults: bool):
    state = driver.setup(ctx)
    state.pop("session")
    state.pop("sim")
    rounds, marks = driver.compared_rounds(ctx.traffic)
    world = reference.build_world(ctx.config, ctx.traffic, ctx.seed)
    kw = dict(rounds=rounds, eval_marks=marks)
    ref, p0, p_ref = reference.federated_rounds(
        world, ctx.config, ctx.traffic, ctx.seed, **kw)
    out = {"program": checks.train_numbers(state["first"], p0,
                                           state["params"], ref, p_ref),
           "theta_margin": min(r["theta_margin"] for r in ref)}
    ctl, _, p_ctl = reference.federated_rounds(
        world, ctx.config, ctx.traffic, ctx.seed, dtype=jnp.bfloat16, **kw)
    out["control_bf16"] = checks.train_numbers(ctl, p0, p_ctl, ref, p_ref)
    runs = {"program": state["first"], "reference": ref,
            "control_bf16": ctl}
    if faults:
        plain = reference.local_training
        reference.local_training = _half_batch(plain)
        try:
            hb, _, p_hb = reference.federated_rounds(
                world, ctx.config, ctx.traffic, ctx.seed, **kw)
        finally:
            reference.local_training = plain
        out["fault_half_batch"] = checks.train_numbers(hb, p0, p_hb, ref,
                                                       p_ref)
        runs["fault_half_batch"] = hb
    out["rounds"] = {name: {k: [r[k] for r in recs] for k in (
        "loss", "accuracy", "bytes_sent", "sim_time", "updates_applied")}
        for name, recs in runs.items()}
    return out


def serve_readings(ctx, driver, seconds):
    state = driver.setup(ctx)
    driver.window(state, seconds)
    rec = state["rec"]
    params = jax.device_get(state["params"])
    rids, got_p, got_s = rec.sample()
    rows = (rids - state["rid0"]) % len(state["flows"])
    x = jnp.asarray(state["flows"][rows])
    want = reference.probs_and_scores(
        {n: jnp.asarray(v) for n, v in params.items()}, x)
    dev = {n: jnp.asarray(v) for n, v in params.items()}
    bf16 = reference.probs_and_scores(
        {n: v.astype(jnp.bfloat16) for n, v in dev.items()}, x)
    fp8 = reference.probs_and_scores(dev, x,
                                     matmul_dtype=jnp.float8_e4m3fn)
    want = [np.asarray(a) for a in want]
    return {"program": checks.serve_numbers(got_p, got_s, *want),
            "control_fp8": checks.serve_numbers(
                *[np.asarray(a) for a in fp8], *want),
            "control_bf16": checks.serve_numbers(
                *[np.asarray(a) for a in bf16], *want),
            "fault_answer_moved": checks.serve_numbers(
                np.roll(got_p, 1, axis=0), np.roll(got_s, 1), *want),
            "sampled": int(len(rows))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--faults", type=int, default=3,
                    help="seeds (the first ones) that also read the faults")
    args = ap.parse_args(argv)
    bench, cell, config, traffic = run.load_cell(args.workload)
    run.check_device(cell["chips"])
    run.enable_compile_cache()
    driver = run.load_driver(traffic["driver"])
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = types.SimpleNamespace(cell=cell["name"], config=config,
                                    traffic=traffic, seed=seed,
                                    seconds=args.seconds,
                                    annotate=run.annotate)
        t0 = time.perf_counter()
        if traffic["driver"] == "train":
            out = train_readings(ctx, driver, faults=i < args.faults)
        else:
            out = serve_readings(ctx, driver, args.seconds)
        out.update(seed=seed, seconds=time.perf_counter() - t0)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
