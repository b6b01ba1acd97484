"""Serving driver: batched anomaly scoring through the ``repro.serve``
engine (the paper's detector), or a batched prefill + decode loop for
the LM-family architectures.

Examples:
  PYTHONPATH=src python -m repro.launch.serve --arch anomaly-mlp \
      --batch 256 --requests 2048
  PYTHONPATH=src python -m repro.launch.serve --arch anomaly-mlp \
      --from-checkpoint run.ckpt
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
      --prompt-len 32 --decode-steps 16 --batch 4
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.launch import compile_cache
from repro.models import api


def serve_lm(cfg, batch: int, prompt_len: int, decode_steps: int, seed=0):
    rng = np.random.default_rng(seed)
    params = api.init_params(jax.random.PRNGKey(seed), cfg)
    toks = prompt_len - (cfg.num_patches if cfg.family == "vlm" else 0)
    prompt = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(batch, toks)))}
    if cfg.family == "vlm":
        prompt["patch_embeds"] = jnp.zeros(
            (batch, cfg.num_patches, cfg.d_model), cfg.compute_dtype)
    if cfg.family == "audio":
        prompt["enc_embeds"] = jnp.asarray(
            rng.normal(size=(batch, cfg.encoder_seq, cfg.d_model)),
            cfg.compute_dtype)

    t0 = time.time()
    prefill = jax.jit(lambda p, b: api.prefill(p, b, cfg))
    logits, cache = prefill(params, prompt)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    # pad the cache to prompt_len + decode_steps for the decode loop
    total = prompt_len + decode_steps
    full = api.init_cache(cfg, batch, total)
    cache = jax.tree.map(
        lambda dst, src: jax.lax.dynamic_update_slice(
            dst, src.astype(dst.dtype), (0,) * dst.ndim)
        if dst.ndim == src.ndim and dst.shape != src.shape else src,
        full, cache)
    cache["step"] = jnp.asarray(prompt_len, jnp.int32)

    decode = jax.jit(lambda p, c, b: api.decode_step(p, c, b, cfg))
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    out = [tok]
    t0 = time.time()
    for _ in range(decode_steps):
        logits, cache = decode(params, cache, {"tokens": tok})
        tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        out.append(tok)
    jax.block_until_ready(out[-1])
    t_decode = time.time() - t0
    print(f"prefill: {batch}x{prompt_len} in {t_prefill:.2f}s; "
          f"decode: {decode_steps} steps in {t_decode:.2f}s "
          f"({batch*decode_steps/max(t_decode,1e-9):.1f} tok/s)")
    return jnp.concatenate(out, axis=1)


def serve_anomaly(cfg, batch: int, seed=0, requests: int = 0,
                  checkpoint: str = None, queue_limit: int = None,
                  deadline_ms: float = None):
    """Batched flow scoring via ``repro.serve.ServeEngine`` — request
    queue, power-of-two batch buckets, hot-swappable model slot,
    p50/p99 latency accounting. ``checkpoint`` serves a trained global
    model from an ``ExperimentSession.checkpoint()`` artifact (sidecar-
    validated); otherwise parameters initialize fresh. ``queue_limit``
    and ``deadline_ms`` turn on the engine's admission control; shed /
    expired requests show up in the health line."""
    from repro.data import synthetic
    from repro.serve import ModelSlot, ServeEngine, health_snapshot

    max_batch = 1 << max(0, int(batch) - 1).bit_length()   # next pow2
    if checkpoint:
        slot = ModelSlot(api.init_params(jax.random.PRNGKey(seed), cfg),
                         model=cfg.name)
        slot.publish_checkpoint(checkpoint)
    else:
        slot = ModelSlot(api.init_params(jax.random.PRNGKey(seed), cfg),
                         model=cfg.name)
    engine = ServeEngine(slot, cfg, max_batch=max_batch,
                         queue_limit=queue_limit, deadline_ms=deadline_ms)
    n = requests or max_batch * 4
    X, _y = synthetic.make_unsw_like(seed, n, cfg.num_features,
                                     cfg.num_classes)
    responses = []
    for i in range(0, n, max_batch):
        engine.submit_many(X[i:i + max_batch], best_effort=True)
        responses.extend(engine.pump())
    health = health_snapshot(engine)
    stats = engine.shutdown()
    anomaly_rate = float(np.mean(
        [np.argmax(r.probs) != 0 for r in responses])) if responses else 0.0
    version = responses[-1].model_version if responses else 0
    print(f"scored {stats.served} flows in {stats.busy_seconds*1e3:.1f} ms "
          f"({stats.flows_per_sec:.0f} flows/s, p50 {stats.p50_ms:.2f} ms, "
          f"p99 {stats.p99_ms:.2f} ms, model v{version}); "
          f"flagged {anomaly_rate:.1%} as attack classes")
    print(f"health: {health.status} (shed {health.shed}, "
          f"deadline_miss {health.deadline_miss}, "
          f"dispatch_errors {health.dispatch_errors}, "
          f"degraded_mode {health.degraded_mode})")
    return stats


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="anomaly-mlp",
                    choices=registry.list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--decode-steps", type=int, default=8)
    ap.add_argument("--requests", type=int, default=0,
                    help="anomaly serving: total flows to score "
                         "(default 4 batches)")
    ap.add_argument("--from-checkpoint", default=None, metavar="PATH",
                    help="anomaly serving: hot-load the global model "
                         "from an ExperimentSession checkpoint "
                         "(validated against its sidecar metadata)")
    ap.add_argument("--queue-limit", type=int, default=None,
                    help="anomaly serving: bound the request queue; "
                         "overflow is shed at admission")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="anomaly serving: per-request deadline; expired "
                         "requests answer NaN and count deadline_miss")
    args = ap.parse_args(argv)
    compile_cache.enable()
    cfg = registry.get_config(args.arch, smoke=args.smoke)
    if cfg.family == "mlp":
        serve_anomaly(cfg, args.batch, requests=args.requests,
                      checkpoint=args.from_checkpoint,
                      queue_limit=args.queue_limit,
                      deadline_ms=args.deadline_ms)
    else:
        serve_lm(cfg, args.batch, args.prompt_len, args.decode_steps)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
