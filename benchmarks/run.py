"""Benchmark driver: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # everything (quick)
  PYTHONPATH=src python -m benchmarks.run --only table3_comm_opt
  PYTHONPATH=src python -m benchmarks.run --full     # paper-scale repeats
  PYTHONPATH=src python -m benchmarks.run --list     # strategy smoke mode
  PYTHONPATH=src python -m benchmarks.run --bench-json BENCH_sim.json --sweep
                                                     # sim-engine perf run

Each module prints a CSV block headed by its paper-table provenance; the
roofline table (deliverable g) is rendered from the dry-run JSONL by
``roofline_report``. ``--list`` instantiates every registered strategy
(no training) — a cheap registry/CI smoke check. ``--bench-json`` times
the fixed 32-client heterogeneous sim config on both execution paths
(reference per-client loop vs compiled cohort megastep) and writes
rounds/sec + dispatches/round so the perf trajectory is tracked in CI.
``--sweep`` adds the multi-seed sweep benchmark: the serial per-seed
spmd loop vs run_sweep's ONE vmapped seed-stacked state.
"""
from __future__ import annotations

import argparse
import importlib
import time
import traceback

from repro.launch import compile_cache

MODULES = [
    "table1_baseline_grid",
    "table2_sota",
    "table3_comm_opt",
    "table4_threshold",
    "table56_profiling",
    "fig3_scaling",
    "fig4_fault_tolerance",
    "table7_mannwhitney",
    "ablation_components",
    "roofline_report",
    "kernel_bench",
]


def list_strategies() -> None:
    """Smoke mode: build every registered strategy without training."""
    import csv
    import sys

    from repro.api import STRATEGY_REGISTRY

    w = csv.writer(sys.stdout)
    w.writerow(["name", "mode", "theta", "selection", "dynamic_batch",
                "checkpointing", "description"])
    for name in sorted(STRATEGY_REGISTRY):
        strat = STRATEGY_REGISTRY[name]
        cfg = strat.build()                    # must not raise
        w.writerow([name, cfg.mode, cfg.theta, cfg.selection,
                    cfg.dynamic_batch, cfg.checkpointing,
                    (strat.description or "").split("\n")[0]])
    print(f"# {len(STRATEGY_REGISTRY)} strategies instantiated OK")


SCAN_R = 8          # rounds per dispatch on the scanned control plane
SCENARIO_PRESET = "dynamic"   # the scenario config timed on the scanned
                              # path (drift + churn + link walks + dropout
                              # regimes — core/scenario.py); the world
                              # transitions run INSIDE the lax.scan, so
                              # their overhead must stay <10% of the
                              # static scanned path (ISSUE 5 acceptance)

# multi-seed sweep protocol (--sweep): the Table VII regime — MANY small
# repeated runs — where per-seed dispatch overhead dominates and folding
# the seed axis into the cohort dispatch pays the most
SWEEP_SEEDS = 16
SWEEP_CLIENTS = 4
SWEEP_BATCH = 32
SWEEP_ROUNDS = 50
SWEEP_REPS = 3      # best-of-N timing: the windows are short (dispatch-
                    # bound micro-runs), min over reps kills scheduler noise


def bench_sweep(rounds: int = SWEEP_ROUNDS, seeds: int = SWEEP_SEEDS,
                clients: int = SWEEP_CLIENTS,
                batch_size: int = SWEEP_BATCH,
                reps: int = SWEEP_REPS) -> dict:
    """Multi-seed spmd sweep throughput: the serial per-seed loop vs ONE
    vmapped seed-stacked state (``run_sweep``'s vectorized path,
    ``fl_step.build_seed_batched_step``). Fixed cohort batches reused
    every round (the ``_bench_spmd_engine`` idiom) isolate dispatch +
    compute from host sampling; rounds/sec counts seeds x rounds
    simulated rounds. Both sides share one compiled step build; the
    serial loop still pays one dispatch per seed per round, the vmapped
    path exactly one per round for ALL seeds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import anomaly_mlp
    from repro.core import fl_step
    from repro.optim import adamw as optim_mod

    cfg = anomaly_mlp.SMOKE
    opt = optim_mod.sgd(5e-3, momentum=0.0)
    rng = np.random.default_rng(0)
    batches = [{"x": jnp.asarray(rng.normal(
                    size=(clients, batch_size, cfg.num_features))
                    .astype(np.float32)),
                "y": jnp.asarray(rng.integers(
                    0, cfg.num_classes, (clients, batch_size)))}
               for _ in range(seeds)]

    step = fl_step.build_fl_train_step(cfg, opt, theta=0.65, donate=False)
    states = [fl_step.init_state(jax.random.PRNGKey(s), cfg, opt)
              for s in range(seeds)]
    for i in range(seeds):                              # compile + warm
        states[i], m = step(states[i], batches[i])
    jax.block_until_ready(m)
    dt_serial = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(rounds):
            for i in range(seeds):
                states[i], m = step(states[i], batches[i])
        jax.block_until_ready(m)
        dt_serial = min(dt_serial, time.perf_counter() - t0)

    vstep = fl_step.build_seed_batched_step(cfg, opt, theta=0.65)
    vstate = fl_step.init_seed_batched_state(range(seeds), cfg, opt)
    vbatch = {k: jnp.stack([b[k] for b in batches]) for k in batches[0]}
    vstate, m = vstep(vstate, vbatch)                   # compile + warm
    jax.block_until_ready(m)
    dt_vmap = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(rounds):
            vstate, m = vstep(vstate, vbatch)
        jax.block_until_ready(m)
        dt_vmap = min(dt_vmap, time.perf_counter() - t0)

    total = seeds * rounds
    out = {
        "serial": {"seconds": round(dt_serial, 3),
                   "rounds_per_sec": round(total / dt_serial, 3),
                   "dispatches_per_round": float(seeds)},
        "vmapped": {"seconds": round(dt_vmap, 3),
                    "rounds_per_sec": round(total / dt_vmap, 3),
                    "dispatches_per_round": 1.0},
        "speedup": round(dt_serial / dt_vmap, 2),
    }
    print(f"# sweep bench ({seeds} seeds x {rounds} rounds, "
          f"{clients} clients, batch {batch_size}): vmapped "
          f"{out['speedup']}x serial rounds/sec")
    return out


def bench_sim(json_path: str, rounds: int = 20, clients: int = 32,
              warmup: int = 2, check_against: str = None,
              sweep: bool = False) -> dict:
    """Sim-engine perf benchmark (ISSUE 2/3 acceptance metric): the fixed
    ``clients``-client heterogeneous config, timed on every execution
    path. Reports rounds/sec and compiled dispatches/round: the
    reference loop pays O(clients) dispatches/round, the per-round
    megastep O(1), the scanned device-control-plane path O(1/R)
    (amortized BELOW one), the fused path (eval folded into the scan
    carry, ``fused_eval=True``) EXACTLY ceil(rounds/R)/rounds — no eval
    dispatches at all — and the compiled spmd engine exactly one
    training dispatch per round.

    The config is the communication-centric FedSGD setting the paper's
    Tables V-VI profile (one local step per client per round,
    ``max_samples_per_round == batch_size``), where per-client dispatch /
    transfer / sync overhead dominates — the effect this benchmark
    exists to track. Compute-bound configs (16 local steps) still gain
    ~2.3x from batched cohort math; see README "Performance". Warmup
    rounds per path absorb jit compiles (round 1 re-specializes the
    megastep on ``has_ref``).

    ``check_against``: path to a committed BENCH JSON — fails (exit 1)
    if any shared path's rounds/sec regresses more than 30% after
    normalizing out machine speed via the reference loop's ratio (CI
    runners and dev boxes differ in absolute speed; the loop path is the
    uncompiled-control baseline both sides measure)."""
    import json

    from repro.api import DataSpec, ExperimentSpec, WorldSpec, get_strategy
    from repro.core import async_engine as ae

    spec = ExperimentSpec(
        model="anomaly-mlp",
        data=DataSpec(n_samples=20000, eval_samples=2000),
        world=WorldSpec(num_clients=clients, profile="heterogeneous"),
        strategy=get_strategy("ours").build(batch_size=64,
                                            dynamic_batch=False,
                                            max_samples_per_round=64),
        seed=0)
    cfg = spec.resolve_model()
    world = spec.build_world()

    out = {"config": {"model": "anomaly-mlp", "clients": clients,
                      "rounds": rounds, "strategy": "ours",
                      "batch_size": 64, "max_samples_per_round": 64,
                      "local_steps": 1, "profile": "heterogeneous",
                      "scan_rounds_per_dispatch": SCAN_R,
                      "scenario": SCENARIO_PRESET,
                      "fused_eval_every": SCAN_R}}
    for name, kwargs in (("loop", dict(megastep=False)),
                         ("megastep", dict(megastep=True)),
                         ("scanned", dict(megastep=True,
                                          rounds_per_dispatch=SCAN_R)),
                         ("scanned_scenario",
                          dict(megastep=True, rounds_per_dispatch=SCAN_R,
                               scenario=SCENARIO_PRESET)),
                         # whole-experiment fusion: eval joins the scan
                         # carry, so the ONLY dispatches are the scans
                         # themselves (no per-chunk host eval readback);
                         # eval_every=SCAN_R matches the post-hoc row's
                         # effective chunk-end cadence — same number of
                         # eval computations, zero extra dispatches
                         ("fused", dict(megastep=True,
                                        rounds_per_dispatch=SCAN_R,
                                        fused_eval=True,
                                        eval_every=SCAN_R))):
        sim = ae.FederatedSimulation(cfg, world.client_arrays,
                                     world.eval_arrays,
                                     spec.resolve_strategy(), world.profiles,
                                     seed=0, **kwargs)
        if kwargs.get("rounds_per_dispatch"):
            # warmup compiles BOTH trace lengths the timed run will use
            # (full R-dispatches plus the remainder-length scan, if any)
            sim.run(SCAN_R + rounds % SCAN_R)
            d0 = sim.dispatches
            t0 = time.perf_counter()
            sim.run(rounds)
            dt = time.perf_counter() - t0
        else:
            for r in range(warmup):
                sim.run_round(r)
            d0 = sim.dispatches
            t0 = time.perf_counter()
            for r in range(rounds):
                sim.run_round(warmup + r)
            dt = time.perf_counter() - t0
        out[name] = {"seconds": round(dt, 3),
                     "rounds_per_sec": round(rounds / dt, 3),
                     "dispatches_per_round": (sim.dispatches - d0) / rounds}

    out["spmd"] = _bench_spmd_engine(rounds, clients)
    if sweep:
        out["config"].update({"sweep_seeds": SWEEP_SEEDS,
                              "sweep_clients": SWEEP_CLIENTS,
                              "sweep_batch": SWEEP_BATCH,
                              "sweep_rounds": SWEEP_ROUNDS})
        out["sweep"] = bench_sweep()
    out["speedup"] = round(out["megastep"]["rounds_per_sec"]
                           / out["loop"]["rounds_per_sec"], 2)
    out["scan_speedup"] = round(out["scanned"]["rounds_per_sec"]
                                / out["loop"]["rounds_per_sec"], 2)
    out["fused_speedup"] = round(out["fused"]["rounds_per_sec"]
                                 / out["loop"]["rounds_per_sec"], 2)
    # dynamic-world cost on the scanned path: static/scenario rounds-per-
    # sec ratio (>1 means the scenario is slower; acceptance bound 1.10)
    out["scenario_overhead"] = round(
        out["scanned"]["rounds_per_sec"]
        / out["scanned_scenario"]["rounds_per_sec"], 3)
    with open(json_path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(json.dumps(out, indent=2))
    print(f"# wrote {json_path}: megastep {out['speedup']}x / scanned "
          f"{out['scan_speedup']}x / fused {out['fused_speedup']}x "
          f"rounds/sec vs loop "
          f"({out['loop']['dispatches_per_round']:.1f} -> "
          f"{out['megastep']['dispatches_per_round']:.1f} -> "
          f"{out['scanned']['dispatches_per_round']:.2f} -> "
          f"{out['fused']['dispatches_per_round']:.2f} dispatches/round); "
          f"'{SCENARIO_PRESET}' scenario overhead "
          f"{out['scenario_overhead']}x on the scanned path")
    if check_against:
        _check_regression(out, check_against)
    return out


def _bench_spmd_engine(rounds: int, clients: int) -> dict:
    """Compiled spmd engine with the device control plane attached
    (sync + θ-filter + adaptive selection): raw step throughput, exactly
    one training dispatch per round by construction."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.api import DataSpec, ExperimentSpec, WorldSpec
    from repro.api.runner import build_spmd_components
    from repro.core.async_engine import StrategyConfig

    st = StrategyConfig(mode="sync", theta=0.65, selection=True,
                        select_fraction=0.5, dynamic_batch=False,
                        checkpointing=False, batch_size=64,
                        max_samples_per_round=64)
    spec = ExperimentSpec(
        model="anomaly-mlp",
        data=DataSpec(n_samples=20000, eval_samples=2000),
        world=WorldSpec(num_clients=clients, profile="heterogeneous"),
        strategy=st, engine="spmd", seed=0)
    world = spec.build_world()
    cfg, st, _opt, state, step = build_spmd_components(spec, world=world)
    rng = np.random.default_rng(0)
    xs = np.stack([c["x"][rng.integers(0, len(c["x"]), 64)]
                   for c in world.client_arrays])
    ys = np.stack([c["y"][rng.integers(0, len(c["y"]), 64)]
                   for c in world.client_arrays])
    batch = {"x": jnp.asarray(xs), "y": jnp.asarray(ys)}
    state, m = step(state, batch)                      # compile
    jax.block_until_ready(m)
    t0 = time.perf_counter()
    for _ in range(rounds):
        state, m = step(state, batch)
    jax.block_until_ready(m)
    dt = time.perf_counter() - t0
    return {"seconds": round(dt, 3),
            "rounds_per_sec": round(rounds / dt, 3),
            "dispatches_per_round": 1.0}


def _check_regression(out: dict, committed_path: str,
                      tolerance: float = 0.30) -> None:
    """CI bench-regression guard: compare rounds/sec per path against
    the committed JSON, normalized by the loop path's machine-speed
    ratio; fail on a >``tolerance`` drop."""
    import json

    with open(committed_path) as f:
        committed = json.load(f)
    # the guard is only meaningful under the committed measurement
    # protocol: a different round count changes the scanned path's trace
    # length / eval amortization and a different client count changes
    # every path's work — refuse rather than spuriously pass or fail
    proto = ["clients", "rounds", "batch_size", "max_samples_per_round",
             "scan_rounds_per_dispatch", "scenario"]
    if "sweep" in out and "sweep" in committed:
        proto += ["sweep_seeds", "sweep_clients", "sweep_batch",
                  "sweep_rounds"]
    if "fused" in out and "fused" in committed:
        proto += ["fused_eval_every"]
    mismatch = {k: (out["config"].get(k), committed["config"].get(k))
                for k in proto
                if out["config"].get(k) != committed["config"].get(k)}
    if mismatch:
        raise SystemExit(
            f"bench-guard config mismatch vs {committed_path}: "
            f"{mismatch} — run with the committed protocol "
            f"(--bench-rounds/--bench-clients) to use --check-against")
    scale = (out["loop"]["rounds_per_sec"]
             / max(committed["loop"]["rounds_per_sec"], 1e-9))
    failures = []
    # the ISSUE 5 acceptance bound: world transitions inside the scan
    # must cost <10% of the static scanned path's rounds/sec — a same-
    # machine ratio, so no normalization is needed
    overhead = out.get("scenario_overhead")
    if overhead is not None:
        status = "ok" if overhead <= 1.10 else "REGRESSION"
        print(f"# bench-guard [scenario] scanned overhead x{overhead:.3f} "
              f"(bound x1.10) {status}")
        if overhead > 1.10:
            failures.append("scenario_overhead")
    for path in ("megastep", "scanned", "scanned_scenario", "fused",
                 "spmd"):
        if path not in committed or path not in out:
            continue
        floor = (1.0 - tolerance) * committed[path]["rounds_per_sec"] * scale
        got = out[path]["rounds_per_sec"]
        status = "ok" if got >= floor else "REGRESSION"
        print(f"# bench-guard [{path}] rounds/sec={got:.2f} "
              f"floor={floor:.2f} (committed="
              f"{committed[path]['rounds_per_sec']:.2f} x machine-scale "
              f"{scale:.2f} x {1 - tolerance:.2f}) {status}")
        if got < floor:
            failures.append(path)
    if "sweep" in out and "sweep" in committed:
        # the sweep claim is the vmapped/serial RATIO — both sides are
        # dispatch-bound micro-runs whose absolute rounds/sec doesn't
        # track the loop path's machine scale, but their ratio does not
        # depend on machine speed at all
        floor = (1.0 - tolerance) * committed["sweep"]["speedup"]
        got = out["sweep"]["speedup"]
        status = "ok" if got >= floor else "REGRESSION"
        print(f"# bench-guard [sweep] vmapped/serial speedup={got:.2f} "
              f"floor={floor:.2f} (committed="
              f"{committed['sweep']['speedup']:.2f} x "
              f"{1 - tolerance:.2f}) {status}")
        if got < floor:
            failures.append("sweep")
    if failures:
        raise SystemExit(
            f"bench regression >{tolerance:.0%} on: {failures} "
            f"(see floors above; refresh BENCH_sim.json only with a "
            f"justified perf change)")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--full", action="store_true",
                    help="paper-scale repeat counts (slow on CPU)")
    ap.add_argument("--list", action="store_true",
                    help="instantiate every registered strategy and exit")
    ap.add_argument("--bench-json", default=None, metavar="PATH",
                    help="run the sim-engine perf benchmark and write "
                         "rounds/sec + dispatches/round JSON to PATH")
    ap.add_argument("--bench-rounds", type=int, default=20,
                    help="timed rounds for --bench-json (CI uses fewer)")
    ap.add_argument("--bench-clients", type=int, default=32)
    ap.add_argument("--check-against", default=None, metavar="PATH",
                    help="committed BENCH JSON to guard against: fail if "
                         "any path's rounds/sec drops >30%% below it "
                         "(machine-speed normalized via the loop path)")
    ap.add_argument("--sweep", action="store_true",
                    help="time the vectorized (vmapped seed-stacked) vs "
                         "serial multi-seed spmd sweep; with --bench-json "
                         "its numbers join the JSON and the "
                         "--check-against regression guard")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if args.list:
        list_strategies()
        return
    if args.bench_json:
        bench_sim(args.bench_json, rounds=args.bench_rounds,
                  clients=args.bench_clients,
                  check_against=args.check_against, sweep=args.sweep)
        return
    if args.sweep:
        import json
        print(json.dumps(bench_sweep(), indent=2))
        return
    mods = [args.only] if args.only else MODULES
    failures = []
    for name in mods:
        print(f"\n===== benchmarks.{name} =====")
        t0 = time.time()
        try:
            mod = importlib.import_module(f"benchmarks.{name}")
            if args.full and name == "fig4_fault_tolerance":
                mod.run(runs=100)
            elif args.full and name == "table7_mannwhitney":
                mod.run(runs=30)
            else:
                mod.run()
            print(f"# [{name}] done in {time.time() - t0:.1f}s")
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
    main()
