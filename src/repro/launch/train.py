"""Production federated trainer driver.

Runs the mesh-mapped FL train step (per-client grads + masked selective
aggregation) on synthetic data. On this CPU container use --smoke configs;
on a real TPU slice the same entry point runs the production mesh.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --smoke \
      --steps 20 --clients 4
  PYTHONPATH=src python -m repro.launch.train --arch anomaly-mlp \
      --steps 50 --clients 8 --theta 0.65
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs import registry
from repro.core import fl_step
from repro.launch import compile_cache
from repro.data import synthetic
from repro.optim import adamw as optim_mod
from repro.optim import schedule


def make_batch_fn(cfg, clients: int, per_client: int, seq: int, seed=0):
    rng = np.random.default_rng(seed)
    if cfg.family == "mlp":
        X, y = synthetic.make_unsw_like(seed, 8192, cfg.num_features,
                                        cfg.num_classes)

        def nxt():
            idx = rng.integers(0, len(X), size=(clients, per_client))
            return {"x": jnp.asarray(X[idx]), "y": jnp.asarray(y[idx])}
        return nxt

    toks = seq - (cfg.num_patches if cfg.family == "vlm" else 0)

    def nxt():
        t, l = synthetic.make_lm_tokens(int(rng.integers(1 << 30)),
                                        clients * per_client, toks,
                                        cfg.vocab_size)
        batch = {
            "tokens": jnp.asarray(t.reshape(clients, per_client, toks)),
            "labels": jnp.asarray(l.reshape(clients, per_client, toks)),
        }
        if cfg.family == "vlm":
            batch["patch_embeds"] = jnp.zeros(
                (clients, per_client, cfg.num_patches, cfg.d_model),
                cfg.compute_dtype)
        if cfg.family == "audio":
            batch["enc_embeds"] = jnp.asarray(rng.normal(size=(
                clients, per_client, cfg.encoder_seq, cfg.d_model)),
                cfg.compute_dtype)
        return batch
    return nxt


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="anomaly-mlp",
                    choices=registry.list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--per-client-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--theta", type=float, default=0.65)
    ap.add_argument("--no-filter", action="store_true",
                    help="synchronous FedAvg baseline")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = registry.get_config(args.arch, smoke=args.smoke)
    optimizer = optim_mod.for_config(cfg, lr=args.lr)
    sched = schedule.cosine(args.lr, warmup_steps=5, total_steps=args.steps)
    theta = None if args.no_filter else args.theta

    state = fl_step.init_state(jax.random.PRNGKey(0), cfg, optimizer)
    step = fl_step.build_fl_train_step(cfg, optimizer, theta=theta,
                                       lr_schedule=sched)
    next_batch = make_batch_fn(cfg, args.clients, args.per_client_batch,
                               args.seq)
    ckpt = CheckpointManager(args.ckpt_dir, total_time=600.0)

    t0 = time.time()
    for i in range(args.steps):
        state, metrics = step(state, next_batch())
        if i % args.log_every == 0 or i == args.steps - 1:
            # per-client leaves (e.g. the (C,) transmit mask) aren't scalars
            m = {k: float(v) for k, v in metrics.items() if v.ndim == 0}
            print(f"step {i:4d} loss={m['loss']:.4f} "
                  f"accept={m['accept_rate']:.2f} "
                  f"align={m['alignment_mean']:.3f} "
                  f"sent={m['bytes_sent']/1e6:.2f}MB "
                  f"(baseline {m['bytes_baseline']/1e6:.2f}MB) "
                  f"[{time.time()-t0:.1f}s]")
        ckpt.maybe_save(state.params, now=time.time() - t0)
    print(f"done: {args.steps} rounds in {time.time()-t0:.1f}s; "
          f"checkpoints={ckpt.saves}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
