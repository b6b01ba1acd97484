#!/usr/bin/env python3
"""Smoke run of the main path on one TPU chip.

Drives the federated round and the scorer once, through the entry points
a user calls, at the full width of the paper's detector (``anomaly-mlp``:
54,602 parameters, 54 arena rows). Phases, in order:

  device   the chip is a TPU and the Mosaic-Pallas kernels are selected
  kernels  the seven Mosaic kernels at 54 and 49 arena rows, 32 clients,
           each against its ``kernels/ref.py`` oracle on the same chip
  train    the BENCH_sim configuration through the fused scanned engine,
           once with the Pallas kernels and once with the oracles; then
           quantized updates, a vmapped 2-seed batch, and the spmd engine
  serve    the trained model behind a ServeEngine, buckets 64 and 256

Any failure ends the run with a non-zero exit; no phase carries on past
one. The last line of standard output is one JSON object naming the
device. Times printed on the way are wall seconds of a smoke run,
compilation included: they are not measurements.

``--four-chips`` runs only the population plane sharded over four chips
(``core/population.round_update_sharded`` at 10^6 clients), compared
bitwise with the single-device ``round_update``.

Usage: python chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.launch import compile_cache  # noqa: E402  (needs the path above)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ROWS = (54, 49)          # arena rows: UNSW detector, ROAD detector
COHORT = 32              # clients per cohort (the BENCH_sim world)
LANE = 1024


def _say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _timed(phase: str, t0: float) -> None:
    _say(phase, f"ok — smoke run, {time.perf_counter() - t0:.1f} s wall "
                "including compilation (not a measurement)")


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def device_phase(expect_count: int) -> dict:
    from repro.kernels import backend
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU — JAX's first device is on "
                         f"platform {d.platform!r}")
    if len(devs) < expect_count:
        raise SystemExit(f"chip_smoke: need {expect_count} TPU devices, "
                         f"JAX sees {len(devs)}")
    kb = backend.resolve()
    if kb != "tpu-pallas":
        raise SystemExit(f"chip_smoke: kernel backend resolved to {kb!r}, "
                         f"not 'tpu-pallas' ({backend.ENV_VAR}="
                         f"{os.environ.get(backend.ENV_VAR)!r})")
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    _say("device", f"{d.device_kind} x{len(devs)}, kernel backend {kb}")
    return info


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _close(name, got, want, rtol, atol=0.0):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=rtol, atol=atol, err_msg=name)


def _equal(name, got, want):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                  err_msg=name)


def kernel_phase() -> None:
    """Every Mosaic kernel against its oracle, at the tolerance
    tests/test_kernels.py pins for it in interpret mode."""
    from repro.kernels import gather as ga
    from repro.kernels import masked_agg as ma
    from repro.kernels import quantize as qz
    from repro.kernels import ref
    from repro.kernels import sign_align as sa

    t0 = time.perf_counter()
    key = jax.random.PRNGKey(0)
    C = COHORT
    for R in ROWS:
        ks = jax.random.split(jax.random.fold_in(key, R), 6)
        u = jax.random.normal(ks[0], (C, R, LANE), jnp.float32)
        r = jnp.sign(jax.random.normal(ks[1], (R, LANE))).astype(jnp.int8)
        r = r.at[-1, 300:].set(-2)            # the arena's padding sentinel
        w = jax.nn.softmax(jax.random.normal(ks[2], (C,)))
        w_lr = w * (jax.random.uniform(ks[3], (C,)) > 0.3) * 0.01
        x = jax.random.normal(ks[4], (R, LANE)) * 3.0
        idx = jax.random.randint(ks[5], (C,), 0, C)
        onehot = (idx[:, None] == jnp.arange(C)[None, :]).astype(jnp.float32)
        at = f"R={R} C={C}"

        for dt in (jnp.float32, jnp.bfloat16):
            tag = f"{at} {jnp.dtype(dt).name}"
            _close(f"sign_align_counts {tag}",
                   sa.sign_align_counts(u[0].astype(dt), r, interpret=False),
                   ref.sign_align_counts(u[0].astype(dt), r), 1e-6)
            _close(f"per_client_sign_align {tag}",
                   sa.per_client_sign_align(u.astype(dt), r, interpret=False),
                   ref.per_client_sign_align(u.astype(dt), r), 1e-6)
            p = x.astype(dt)
            tol = 1e-2 if dt == jnp.bfloat16 else 1e-5
            got = ma.fused_update(p, u, w_lr, interpret=False)
            assert got.dtype == p.dtype, (got.dtype, p.dtype)
            _close(f"fused_update {tag}", got, ref.fused_update(p, u, w_lr),
                   tol, 1e-2 if dt == jnp.bfloat16 else 1e-6)
        _close(f"masked_agg {at}", ma.masked_agg(u, w, interpret=False),
               ref.masked_agg(u, w), 1e-5, 1e-6)
        _equal(f"onehot_gather {at}",
               ga.onehot_gather(u, onehot, interpret=False),
               ref.cohort_gather(u, idx))
        q, s = qz.quantize_q8(x, interpret=False)
        q2, s2 = ref.quantize_q8(x)
        _equal(f"quantize_q8 codes {at}", q, q2)
        _close(f"quantize_q8 scales {at}", s, s2, 1e-6)
        _close(f"dequantize_q8 {at}", qz.dequantize_q8(q, s, interpret=False),
               ref.dequantize_q8(q2, s2), 1e-6)
        _say("kernels", f"{at}: 7 kernels match their oracles")
    _timed("kernels", t0)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def bench_sim_spec(**overrides):
    """The BENCH_sim configuration (benchmarks/run.py ``bench_sim``) on
    the fused scanned engine: 8 rounds, 4 per compiled dispatch."""
    from repro.api import DataSpec, ExperimentSpec, WorldSpec
    kw = dict(model="anomaly-mlp",
              data=DataSpec(n_samples=20000, eval_samples=2000),
              world=WorldSpec(num_clients=COHORT, profile="heterogeneous"),
              strategy="ours",
              strategy_kwargs=dict(batch_size=64, dynamic_batch=False,
                                   max_samples_per_round=64),
              rounds=8, rounds_per_dispatch=4, fused_eval=True, seed=0)
    kw.update(overrides)
    return ExperimentSpec(**kw)


def run_scanned(spec):
    """``run_experiment``'s own steps (open a session, run, collect),
    keeping the session so the compiled dispatch count and the lowered
    scan are visible. Returns (result, dispatches, lowered text,
    compile seconds)."""
    from repro.api import ExperimentSession
    session = ExperimentSession.open(spec)
    sim = session._driver.sim
    R = spec.rounds_per_dispatch
    scan = sim._scan_fn(R)
    built = {}

    def recording(*args):
        if not built:
            t0 = time.perf_counter()
            lowered = scan.lower(*args)
            built["exe"] = lowered.compile()
            built["s"] = time.perf_counter() - t0
            built["text"] = lowered.as_text()
        return built["exe"](*args)
    sim._scan_fns[R] = recording
    session.run(spec.rounds)
    dispatches = sim.dispatches       # before result() unpacks the arena
    return session.result(), dispatches, built["text"], built["s"]


def _check_accuracy(name, result, chance):
    acc = result.final.accuracy
    if not (math.isfinite(acc) and acc > chance):
        raise AssertionError(f"{name}: final accuracy {acc} is not finite "
                             f"and above chance {chance:.3f}")
    if not all(math.isfinite(r.loss) for r in result.records):
        raise AssertionError(f"{name}: non-finite loss in "
                             f"{[r.loss for r in result.records]}")
    return acc


def train_phase():
    from repro.api import get_strategy, run_experiment, run_scanned_seed_batch
    from repro.kernels import backend

    t0 = time.perf_counter()
    spec = bench_sim_spec()
    chance = 1.0 / spec.resolve_model().num_classes
    res_p, disp_p, text_p, comp_p = run_scanned(spec)
    n_kernels = text_p.count("tpu_custom_call")
    if n_kernels == 0:
        raise AssertionError("the lowered scan holds no tpu_custom_call: "
                             "the Pallas kernels did not run")
    acc_p = _check_accuracy("pallas", res_p, chance)

    os.environ[backend.ENV_VAR] = "oracle"
    try:
        res_o, disp_o, text_o, comp_o = run_scanned(spec)
    finally:
        del os.environ[backend.ENV_VAR]
    assert backend.resolve() == "tpu-pallas"
    if "tpu_custom_call" in text_o:
        raise AssertionError("the oracle run lowered Pallas kernels")
    acc_o = _check_accuracy("oracle", res_o, chance)
    expect = -(-spec.rounds // spec.rounds_per_dispatch)
    if not disp_p == disp_o == expect:
        raise AssertionError(f"compiled dispatches: pallas {disp_p}, "
                             f"oracle {disp_o}, fused contract {expect}")
    if abs(acc_p - acc_o) >= 0.01:
        raise AssertionError(f"final accuracy pallas {acc_p:.4f} vs oracle "
                             f"{acc_o:.4f}: more than 1 point apart")
    _say("train", f"fused scan: {n_kernels} tpu_custom_call in the lowered "
                  f"program; {disp_p} dispatches for {spec.rounds} rounds "
                  f"with either backend; final accuracy pallas "
                  f"{acc_p:.4f}, oracle {acc_o:.4f}; scan compile "
                  f"{comp_p:.1f} s / {comp_o:.1f} s (smoke run, not a "
                  f"measurement)")

    q_spec = bench_sim_spec(strategy_kwargs=dict(
        batch_size=64, dynamic_batch=False, max_samples_per_round=64,
        quantize_updates=True))
    acc_q = _check_accuracy("quantized", run_experiment(q_spec), chance)
    _say("train", f"quantized updates: final accuracy {acc_q:.4f}")

    seeds = run_scanned_seed_batch(bench_sim_spec(), (0, 1))
    accs = [_check_accuracy(f"seed {r.seed}", r, chance) for r in seeds]
    _say("train", f"vmapped 2-seed batch: final accuracy "
                  f"{', '.join(f'{a:.4f}' for a in accs)}")

    spmd_spec = bench_sim_spec(
        engine="spmd", rounds=4, rounds_per_dispatch=None, fused_eval=False,
        strategy=get_strategy("cmfl").build(batch_size=64, theta=0.65,
                                            max_samples_per_round=64),
        strategy_kwargs={})
    acc_s = _check_accuracy("spmd cmfl", run_experiment(spmd_spec), chance)
    _say("train", f"spmd engine, cmfl θ=0.65: final accuracy {acc_s:.4f}")
    _timed("train", t0)
    return res_p


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def _reference_probs(params, x, cfg):
    """float64 host forward pass of the detector (mlp_detector.forward)."""
    h = np.asarray(x, np.float64)
    n = len(cfg.mlp_hidden) + 1
    for i in range(n):
        h = h @ np.asarray(params[f"w{i}"], np.float64) \
            + np.asarray(params[f"b{i}"], np.float64)
        if i < n - 1:
            h = np.maximum(h, 0.0)
    e = np.exp(h - h.max(axis=-1, keepdims=True))
    return e / e.sum(axis=-1, keepdims=True)


def serve_phase(result) -> None:
    from repro.data import synthetic
    from repro.serve import ModelSlot, ServeEngine

    t0 = time.perf_counter()
    cfg = result.cfg
    engine = ServeEngine(ModelSlot(result.params, model=cfg.name), cfg,
                         max_batch=256)
    X, _ = synthetic.make_unsw_like(7, 256 + 64 + 37, cfg.num_features,
                                    cfg.num_classes)
    responses, ids = [], []
    for lo, hi in ((0, 256), (256, 320), (320, len(X))):
        ids += engine.submit_many(X[lo:hi])
        responses += engine.pump()
    stats = engine.shutdown()
    if stats.errors:
        raise AssertionError(f"{stats.errors} scoring dispatches failed")
    got = {r.request_id: r for r in responses}
    if sorted(got) != sorted(ids) or stats.dropped or stats.pending:
        raise AssertionError(f"answered {len(got)} of {len(ids)} requests "
                             f"(dropped {stats.dropped})")
    if not {64, 256} <= set(stats.by_bucket):
        raise AssertionError(f"buckets dispatched: {sorted(stats.by_bucket)}")
    probs = np.stack([got[i].probs for i in ids])
    scores = np.array([got[i].score for i in ids])
    if not (np.isfinite(probs).all() and np.isfinite(scores).all()):
        raise AssertionError("non-finite scores")
    want = _reference_probs(result.params, X, cfg)
    # default-precision matmuls on the chip take bf16 passes: ~1e-2 on a
    # probability, far below what a wrong model would give
    err = float(np.abs(probs - want).max())
    if err > 5e-2:
        raise AssertionError(f"scores differ from the float64 host "
                             f"reference by up to {err:.4f}")
    np.testing.assert_allclose(scores, 1.0 - probs[:, 0], rtol=1e-6)
    _say("serve", f"{len(ids)} requests answered, buckets "
                  f"{sorted(stats.by_bucket)}, errors 0, max |p - ref| "
                  f"{err:.2e}")
    _timed("serve", t0)


# ---------------------------------------------------------------------------
# four chips: the sharded population plane
# ---------------------------------------------------------------------------

def four_chip_phase() -> None:
    from repro.core import control, population
    from repro.launch import mesh as mesh_mod
    from repro.launch import sharding

    t0 = time.perf_counter()
    n, k = 1_000_000, 1024
    rng = np.random.default_rng(0)
    state = control.init_control(n)._replace(
        avail=rng.uniform(0.2, 1.0, n).astype(np.float32),
        pass_rate=rng.uniform(0.5, 1.0, n).astype(np.float32),
        round_time=rng.uniform(0.5, 2.0, n).astype(np.float32))
    mesh = mesh_mod.make_population_mesh()
    shrd = sharding.shard_population(state, mesh, n)
    glob = jax.device_put(state, jax.devices()[0])
    for r in range(4):
        cohort = rng.choice(n, size=k, replace=False).astype(np.int32)
        failed = rng.random(k) < 0.2
        active = ~failed
        obs = dict(failed=failed, active=active,
                   passed=(rng.random(k) < 0.8) & active,
                   round_time=rng.uniform(0.2, 3.0, k).astype(np.float32),
                   sent=active,
                   norms=rng.uniform(0.05, 2.5, k).astype(np.float32))
        obs = {key: jnp.asarray(v) for key, v in obs.items()}
        glob = population.round_update(glob, jnp.asarray(cohort), **obs)
        shrd = population.round_update_sharded(shrd, jnp.asarray(cohort),
                                               mesh=mesh, **obs)
        for f in population._FIELDS:
            spread = len(getattr(shrd, f).sharding.device_set)
            if spread != 4:
                raise AssertionError(f"{f} spans {spread} devices after "
                                     f"round {r}, not 4")
            _equal(f"{f} round {r}", getattr(shrd, f), getattr(glob, f))
    _say("four-chips", f"{n} clients sharded over {mesh.shape['data']} "
                       f"devices; 4 rounds bitwise equal to one device")
    _timed("four-chips", t0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the population plane over four chips")
    args = ap.parse_args(argv)
    _say("cache", compile_cache.enable())
    device = device_phase(4 if args.four_chips else 1)
    if args.four_chips:
        four_chip_phase()
    else:
        kernel_phase()
        serve_phase(train_phase())
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
