"""Small-size cells for the benchmark's own tests on the CPU: the real
traffic files with the population and batches cut so that a run takes
seconds, and a runner that skips the look for a chip."""
from __future__ import annotations

import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from bench import run  # noqa: E402

SMALL = {
    "unsw.silo": dict(records=8000, eval_records=1000, clients=4,
                      strategy_kwargs=dict(batch_size=32,
                                           max_samples_per_round=128),
                      physical_steps_batch=[4, 32]),
    "road.fleet": dict(records=20000, eval_records=1000, clients=100,
                       strategy_kwargs=dict(batch_size=64,
                                            max_samples_per_round=64),
                       physical_steps_batch=[1, 64]),
    "unsw.replay": dict(log_flows=4096),
}


def small_cell(name: str):
    bench, cell, config, traffic = run.load_cell(name)
    for k, v in SMALL[name].items():
        if isinstance(v, dict):
            traffic[k] = dict(traffic[k], **v)
        else:
            traffic[k] = v
    return bench, cell, config, traffic


def run_small(monkeypatch, name: str, seed: int = 7, seconds: float = 1.0,
              trace: int = 0):
    """One run of a small cell on the CPU, the chip check skipped and
    the persistent compile cache left alone."""
    monkeypatch.setattr(run, "check_device", lambda chips: {
        "platform": "cpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    bench, cell, config, traffic = small_cell(name)
    return run.run_cell(bench, cell, config, traffic, seed, seconds, trace,
                        t_start=time.perf_counter())
