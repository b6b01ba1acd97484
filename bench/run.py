#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``. Its
configuration file comes from ``configs``, its traffic mix from
``bench/traffic/<traffic>.json``, and the mix's ``driver`` names the code
in ``bench/drivers/`` that sets it up, drives the window and checks the
outputs. With ``--trace 0`` the result line carries the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
line carries the per-layer metrics, each read by ``bench/metrics/<name>.py``
(or by the reader of its stem, ``<stem>.py``, where one metric is split by
the end-to-end metric it moves).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``checks``: every number compared with the reference,
beside its limit. The same checks close standard error. Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

EXIT_NO_CHIP = 3


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_cell(name: str):
    """(benchmark, workload entry, configuration dict, traffic dict)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    return bench, cell, config, traffic


def check_device(chips: int) -> dict:
    """The chip this run measures; never falls back to the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is on platform "
                     f"{devs[0].platform!r}, not 'tpu'")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks))


def enable_compile_cache() -> str:
    """JAX's persistent cache at the program's fixed path (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax
    from repro.launch import compile_cache
    where = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCounter:
    """Counts programs compiled or loaded from the cache while active."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring as monitoring
        self.count = 0
        self.active = False
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kwargs):
        if self.active and event in self.EVENTS:
            self.count += 1


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def load_driver(kind: str):
    return importlib.import_module(f"bench.drivers.{kind}")


def reader_path(metric: str) -> pathlib.Path:
    """``bench/metrics/<metric>.py``, or, for a metric split by the cells'
    end-to-end metric (``device_idle.train``), the reader its stem shares
    (``device_idle.py``)."""
    own = BENCH / "metrics" / f"{metric}.py"
    if own.is_file():
        return own
    return BENCH / "metrics" / f"{metric.split('.')[0]}.py"


def load_reader(metric: str):
    path = reader_path(metric)
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, traced: bool):
    """The metric entries this cell reports in this kind of run."""
    e2e = [m for m in bench["end_to_end"]
           if m["name"] == "setup_s" or cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell]) and m["moves"] in names]


@contextlib.contextmanager
def profiled(enabled: bool):
    """Runs the block under the profiler; yields the trace directory."""
    if not enabled:
        yield None
        return
    import jax
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    try:
        yield tmp
    finally:
        jax.profiler.stop_trace()


def run_cell(bench, cell, config, traffic, seed, seconds, trace,
             t_start=T_START):
    """Set up, measure, check. Returns the result dict."""
    ctx = types.SimpleNamespace(cell=cell["name"], config=config,
                                traffic=traffic, seed=int(seed),
                                seconds=float(seconds), annotate=annotate)
    device = check_device(cell["chips"])
    enable_compile_cache()
    driver = load_driver(traffic["driver"])
    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start

    counter = CompileCounter()
    window_s = min(float(seconds), float(traffic.get("trace_seconds",
                                                     seconds))) \
        if trace else float(seconds)
    counter.active = True
    with profiled(trace) as trace_dir:
        with annotate("bench.window"):
            raw = driver.window(state, window_s)
    counter.active = False
    device["memory_peak_bytes"] = memory_peak_bytes()

    checks, failed = driver.check(state)
    attempted = raw["attempted"]
    del state
    gc.collect()
    correct = failed == 0 and all(v <= lim for _, v, lim in checks)

    metrics = {}
    breakdown = None
    if trace:
        from bench import flops, xplane
        red = xplane.reduce_events(xplane.load_events(trace_dir),
                                   kernels=traffic.get("kernels", {}))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        mctx = types.SimpleNamespace(
            config=config, traffic=traffic, raw=raw, trace=red,
            peaks=flops.peaks(device["kind"]), seconds=window_s)
        for m in cell_metrics(bench, cell["name"], traced=True):
            value = load_reader(m["name"])(mctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = xplane.breakdown(red)
    else:
        values = dict(driver.end_to_end(raw), setup_s=setup_s)
        for m in cell_metrics(bench, cell["name"], traced=False):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compiles_in_window"] = counter.count
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, config, traffic = load_cell(args.workload)
    try:
        out = run_cell(bench, cell, config, traffic, args.seed,
                       args.seconds, args.trace)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return EXIT_NO_CHIP
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
