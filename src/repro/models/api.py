"""Model API: family dispatch + input specs.

Every family exposes:
  init_params(rng, cfg)                       -> param pytree
  loss_fn(params, batch, cfg)                 -> scalar loss
  prefill(params, batch, cfg)                 -> (logits, cache)
  decode_step(params, cache, batch, cfg)      -> (logits, cache)
  init_cache(cfg, batch, seq)                 -> cache pytree

``input_specs`` builds `jax.ShapeDtypeStruct` stand-ins for every model
input of a given (config × shape × step-kind) — weak-type-correct,
shardable, no device allocation — used by the multi-pod dry-run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.configs.shapes import InputShape
from repro.models import hybrid, mlp_detector, rwkv6, transformer, whisper

_FAMILY = {
    "dense": transformer,
    "moe": transformer,
    "vlm": transformer,
    "ssm": rwkv6,
    "hybrid": hybrid,
    "audio": whisper,
    "mlp": mlp_detector,
}


def module_for(cfg: ArchConfig):
    return _FAMILY[cfg.family]


def init_params(rng, cfg: ArchConfig):
    return module_for(cfg).init_params(rng, cfg)


def loss_fn(params, batch, cfg: ArchConfig):
    return module_for(cfg).loss_fn(params, batch, cfg)


def loss_and_stats(params, batch, cfg: ArchConfig):
    """(loss, stats): the family's counters from the same pass (the
    expert layer's ``expert_rows``), or an empty
    dict for families that count nothing."""
    mod = module_for(cfg)
    if hasattr(mod, "loss_and_stats"):
        return mod.loss_and_stats(params, batch, cfg)
    return mod.loss_fn(params, batch, cfg), {}


def prefill(params, batch, cfg: ArchConfig):
    return module_for(cfg).prefill(params, batch, cfg)


def decode_step(params, cache, batch, cfg: ArchConfig):
    return module_for(cfg).decode_step(params, cache, batch, cfg)


def init_cache(cfg: ArchConfig, batch_size: int, seq_len: int):
    return module_for(cfg).init_cache(cfg, batch_size, seq_len)


def build_default_eval(cfg: ArchConfig):
    """Jitted default quality metric ev(params, batch) -> scalar, shared
    by both FL engines so their accuracy fields stay comparable:
    classification accuracy for the mlp detector family, a -loss quality
    proxy for everything else (LMs etc.)."""

    @jax.jit
    def ev(params, batch):
        if cfg.family == "mlp":
            from repro.models import mlp_detector
            return mlp_detector.accuracy(params, batch, cfg)
        return -loss_fn(params, batch, cfg)

    return ev


# --------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins, no allocation)
# --------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _token_batch(cfg, lead, seq):
    """Token/label specs with modality extras. lead: leading dims tuple."""
    toks = seq
    batch = {}
    if cfg.family == "vlm":
        toks = max(seq - cfg.num_patches, 1)
        batch["patch_embeds"] = _sds(lead + (cfg.num_patches, cfg.d_model),
                                     jnp.dtype(cfg.dtype))
    if cfg.family == "audio":
        batch["enc_embeds"] = _sds(lead + (cfg.encoder_seq, cfg.d_model),
                                   jnp.dtype(cfg.dtype))
    batch["tokens"] = _sds(lead + (toks,), jnp.int32)
    batch["labels"] = _sds(lead + (toks,), jnp.int32)
    return batch


def train_input_specs(cfg: ArchConfig, shape: InputShape, num_clients: int):
    """Per-client-batched training inputs: leading dim = num_clients."""
    per_client = max(shape.global_batch // num_clients, 1)
    if cfg.family == "mlp":
        return {"x": _sds((num_clients, per_client, cfg.num_features), jnp.float32),
                "y": _sds((num_clients, per_client), jnp.int32)}
    return _token_batch(cfg, (num_clients, per_client), shape.seq_len)


def prefill_input_specs(cfg: ArchConfig, shape: InputShape):
    if cfg.family == "mlp":
        return {"x": _sds((shape.global_batch, cfg.num_features), jnp.float32)}
    batch = _token_batch(cfg, (shape.global_batch,), shape.seq_len)
    batch.pop("labels")
    return batch


def decode_input_specs(cfg: ArchConfig, shape: InputShape):
    """(batch, cache) specs for a single-token serve_step."""
    batch = {"tokens": _sds((shape.global_batch, 1), jnp.int32)}
    cache = jax.eval_shape(
        functools.partial(init_cache, cfg, shape.global_batch, shape.seq_len))
    return batch, cache


def input_specs(cfg: ArchConfig, shape: InputShape, num_clients: int = 1):
    """Dispatch on shape.kind. Returns the kwargs pytree for the step fn."""
    if shape.kind == "train":
        return {"batch": train_input_specs(cfg, shape, num_clients)}
    if shape.kind == "prefill":
        return {"batch": prefill_input_specs(cfg, shape)}
    batch, cache = decode_input_specs(cfg, shape)
    return {"batch": batch, "cache": cache}
