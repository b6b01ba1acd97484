import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run (deliverable e).

Lowers + compiles every (architecture × input shape) on the production
mesh — 16×16 ("data","model") single-pod and 2×16×16 ("pod","data",
"model") two-pod — using ShapeDtypeStruct inputs (no allocation), prints
memory/cost analysis, and appends roofline rows to a JSONL results file.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # everything
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b \
      --shape train_4k --mesh single                            # one combo
  PYTHONPATH=src python -m repro.launch.dryrun --list           # plan only

The two env-var lines above MUST stay the first statements in this module:
jax locks the device count at first init.
"""

import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs import registry
from repro.configs.shapes import SHAPES
from repro.core import fl_step
from repro.launch import mesh as mesh_mod
from repro.launch import sharding
from repro.models import api
from repro.optim import adamw as optim_mod
from repro.roofline import analysis, hlo_census

RESULTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun_results.jsonl")


def plan(args):
    combos = []
    archs = [args.arch] if args.arch else registry.ASSIGNED_ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"single": False, "multi": True}
    if args.mesh != "both":
        meshes = {args.mesh: meshes[args.mesh]}
    for a in archs:
        for s in shapes:
            if s == "long_500k" and a in registry.LONG_CTX_SKIP:
                continue
            for mname, mp in meshes.items():
                combos.append((a, s, mname, mp))
    return combos


def _completed(path):
    done = set()
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                if line.strip():
                    r = json.loads(line)
                    done.add((r["arch"], r["shape"], r["mesh"]))
    return done


def lower_one(arch: str, shape_name: str, multi_pod: bool, verbose=True):
    cfg = registry.config_for_shape(arch, shape_name)
    shape = SHAPES[shape_name]
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    chips = mesh.size
    optimizer = optim_mod.for_config(cfg)

    if shape.kind == "train":
        C = mesh_mod.num_clients(cfg, mesh)
        specs = api.input_specs(cfg, shape, num_clients=C)
        state_shapes = jax.eval_shape(
            lambda: fl_step.init_state(jax.random.PRNGKey(0), cfg, optimizer))
        state_spec = sharding.state_pspecs(cfg, mesh, optimizer)
        batch_spec = sharding.train_batch_pspecs(cfg, mesh, specs["batch"])
        step = fl_step.make_raw_step(cfg, optimizer, theta=0.65)
        jitted = jax.jit(
            step,
            in_shardings=(sharding.to_named(mesh, state_spec),
                          sharding.to_named(mesh, batch_spec)),
            out_shardings=(sharding.to_named(mesh, state_spec), None),
            donate_argnums=(0,))
        lowered = jitted.lower(state_shapes, specs["batch"])
    elif shape.kind == "prefill":
        specs = api.input_specs(cfg, shape)
        pshapes = jax.eval_shape(
            lambda: api.init_params(jax.random.PRNGKey(0), cfg))
        pspec = sharding.param_pspecs(cfg, mesh, mode="serve")
        bspec = sharding.infer_batch_pspecs(mesh, specs["batch"])
        step = fl_step.build_prefill_step(cfg)
        jitted = jax.jit(step, in_shardings=(
            sharding.to_named(mesh, pspec), sharding.to_named(mesh, bspec)))
        lowered = jitted.lower(pshapes, specs["batch"])
    else:  # decode
        specs = api.input_specs(cfg, shape)
        pshapes = jax.eval_shape(
            lambda: api.init_params(jax.random.PRNGKey(0), cfg))
        pspec = sharding.param_pspecs(cfg, mesh, mode="serve")
        bspec = sharding.infer_batch_pspecs(mesh, specs["batch"])
        cspec = sharding.cache_pspecs(cfg, mesh, specs["cache"])
        step = fl_step.build_serve_step(cfg)
        jitted = jax.jit(
            step,
            in_shardings=(sharding.to_named(mesh, pspec),
                          sharding.to_named(mesh, cspec),
                          sharding.to_named(mesh, bspec)),
            out_shardings=(None, sharding.to_named(mesh, cspec)),
            donate_argnums=(1,))
        lowered = jitted.lower(pshapes, specs["cache"], specs["batch"])

    t0 = time.time()
    compiled = lowered.compile()
    compile_s = time.time() - t0

    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_stats = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception:
        mem_stats = None
    hlo = compiled.as_text()
    census = hlo_census.analyze(hlo)
    roof = analysis.analyze(arch, shape, mesh_name, chips, cost, census,
                            cfg, memory_stats=mem_stats)
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {mesh_name}: "
              f"compiled in {compile_s:.1f}s")
        print(f"  memory_analysis: {mem_stats}")
        print(f"  cost_analysis: flops={cost.get('flops')} "
              f"bytes={cost.get('bytes accessed')}")
        print(f"  collectives: {census['per_op_bytes']}")
        print("  " + roof.as_row())
    return roof


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    choices=registry.ASSIGNED_ARCHS + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--results", default=os.path.abspath(RESULTS))
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--force", action="store_true",
                    help="re-run combos already in the results file")
    args = ap.parse_args(argv)

    combos = plan(args)
    if args.list:
        for c in combos:
            print(*c[:3])
        return 0
    os.makedirs(os.path.dirname(args.results), exist_ok=True)
    done = set() if args.force else _completed(args.results)
    failures = []
    for arch, shape_name, mesh_name, mp in combos:
        key = (arch, shape_name, "2x16x16" if mp else "16x16")
        if key in done:
            print(f"[dryrun] skip (cached): {key}")
            continue
        try:
            roof = lower_one(arch, shape_name, mp)
            analysis.save_jsonl(args.results, [roof])
        except Exception as e:
            traceback.print_exc()
            failures.append((arch, shape_name, mesh_name, repr(e)))
        finally:
            jax.clear_caches()   # keep a long sweep's RSS bounded
    if failures:
        print(f"\n[dryrun] {len(failures)} FAILURES:")
        for f in failures:
            print("  ", *f)
        return 1
    print("\n[dryrun] all combos lowered + compiled successfully")
    return 0


if __name__ == "__main__":
    sys.exit(main())
