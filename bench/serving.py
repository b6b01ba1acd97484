"""What a scoring traffic needs besides its driver: the scorer under
test, built from the seed; warm-up of the batch buckets a mix uses; the
sampled record of answers; and the check against the reference forward
pass.

The detector's weights are made from the seed on the device in one
jitted call (f32, the served type) and published into a ``ModelSlot``;
the flow log is the benchmark's own UNSW-shaped generator at the seed.
A seed-drawn sixteenth of the pumps keeps its answers' probabilities
and scores for the check; every answer's id is counted.
"""
from __future__ import annotations

import functools
import operator

import jax
import jax.numpy as jnp
import numpy as np

from bench import checks, gen, reference


def build(ctx):
    from repro.serve import ModelSlot, ServeEngine
    cfg, tr = ctx.config, ctx.traffic
    dims = tuple(reference.layer_dims(cfg))
    init = jax.jit(functools.partial(reference.init_from_key, dims=dims,
                                     bias_scale=0.1))
    params = init(jax.random.PRNGKey(ctx.seed))
    engine = ServeEngine(ModelSlot(params, model=cfg["program_model"]),
                         _arch_config(cfg), max_batch=tr["max_batch"])
    flows, _ = gen.make_split(cfg["dataset"], ctx.seed, tr["log_flows"], cfg)
    return {"ctx": ctx, "engine": engine, "flows": flows, "params": params,
            "salt": ctx.seed & 15}


def _arch_config(cfg):
    from repro.api import ExperimentSpec
    return ExperimentSpec(model=cfg["program_model"]).resolve_model()


def warm(state, buckets):
    """Compile (or load) each bucket's scorer, then zero the stats."""
    eng, flows = state["engine"], state["flows"]
    for b in buckets:
        eng.submit_many(flows[:b])
        eng.drain()
    eng.reset_stats()


class Record:
    """Ids of every answer; probabilities and score of a seed-drawn
    sixteenth of the pumps, each a whole batch, so every position in a
    batch is sampled. The per-flow work is one C-level ``extend``."""

    EVERY = 16

    def __init__(self, salt: int):
        self.salt = salt % self.EVERY
        self.ids = []
        self.sampled = []
        self.pumps = 0

    def add(self, responses):
        self.ids.extend(map(_REQUEST_ID, responses))
        if self.pumps % self.EVERY == self.salt:
            self.sampled.append(responses)
        self.pumps += 1

    def sample(self):
        """(ids, probs (n, classes), scores) of the sampled answers."""
        rs = [r for out in self.sampled for r in out]
        ids = np.asarray([r.request_id for r in rs], np.int64)
        probs = np.asarray([r.probs for r in rs], np.float32)
        scores = np.asarray([r.score for r in rs], np.float32)
        return ids, probs.reshape(len(rs), -1), scores


_REQUEST_ID = operator.attrgetter("request_id")


def batch_rows(engine) -> float:
    by = engine.stats().by_bucket
    count = sum(v["count"] for v in by.values())
    return sum(v["rows"] for v in by.values()) / max(count, 1)


def check(state, rec: Record, rid0: int, submitted: int):
    """(checks, failed): every submitted id answered exactly once; the
    sampled answers against the reference forward pass of the flow each
    id was given. ``rid0`` is the id of flow 0 of the log."""
    ctx = state["ctx"]
    log = state["flows"]
    params = state["params"]
    state.pop("engine")
    counts = np.bincount(np.asarray(rec.ids, np.int64) - rid0,
                         minlength=submitted)
    failed = int((counts[:submitted] != 1).sum() + counts[submitted:].sum())
    rids, got_p, got_s = rec.sample()
    rows = (rids - rid0) % len(log)
    want_p, want_s = [], []
    for lo in range(0, len(rows), 8192):
        p, s = reference.probs_and_scores(params, jnp.asarray(
            log[rows[lo:lo + 8192]]))
        want_p.append(np.asarray(p))
        want_s.append(np.asarray(s))
    if want_p:
        want_p, want_s = np.concatenate(want_p), np.concatenate(want_s)
    got = checks.serve_numbers(got_p, got_s, np.asarray(want_p),
                               np.asarray(want_s))
    limits = ctx.traffic["limits"]
    return [(n, got[n], limits[n]) for n in limits], failed
