"""The benchmark's own traffic generators, kept here so that a change to
the program cannot move the yardstick.

``make_unsw_like`` and ``make_road_like`` are copies of
``repro.data.synthetic``'s surrogates (the program receives their output
through ``DataSpec(factory=...)``). ``dirichlet_partition``,
``profile_arrays``, ``assign_batch_size`` and ``drift_directions`` copy
what the program documents of its world (label-Dirichlet split,
heterogeneous client profiles, the §IV-A initial batch rule, the drift
directions): the reference rebuilds the same world from the seed with
these, and takes nothing the program made.
"""
from __future__ import annotations

import math

import numpy as np

_UNSW_PRIORS = np.array(
    [0.55, 0.12, 0.09, 0.07, 0.05, 0.04, 0.03, 0.025, 0.02, 0.015])


def make_unsw_like(seed: int, n: int, num_features: int = 49,
                   num_classes: int = 10, universe_seed: int = 1234):
    """UNSW-NB15-shaped flow records: a class-conditional Gaussian
    mixture over correlated features, 9 sign-quantized categorical
    columns, per-feature scaling, 5% label noise."""
    rng = np.random.default_rng(seed)
    rng_u = np.random.default_rng(universe_seed)
    priors = _UNSW_PRIORS[:num_classes] / _UNSW_PRIORS[:num_classes].sum()
    y = rng.choice(num_classes, size=n, p=priors)
    basis = (rng_u.normal(size=(num_features, num_features))
             / np.sqrt(num_features))
    means = rng_u.normal(scale=0.9, size=(num_classes, num_features))
    z = rng.normal(size=(n, num_features))
    x = (z @ basis) + means[y]
    x[:, -9:] = np.sign(x[:, -9:])
    x = (x - x.mean(0)) / (x.std(0) + 1e-6)
    flip = rng.random(n) < 0.05
    y = np.where(flip, rng.choice(num_classes, size=n, p=priors), y)
    return x.astype(np.float32), y.astype(np.int32)


def make_road_like(seed: int, n: int, window: int = 32,
                   attack_frac: float = 0.25):
    """ROAD-shaped CAN wheel-speed windows; a quarter carry a flat
    masquerade segment of 8 samples."""
    rng = np.random.default_rng(seed)
    y = (rng.random(n) < attack_frac).astype(np.int32)
    t = np.arange(window) / window
    base_speed = rng.uniform(0.2, 1.0, size=(n, 1))
    phase = rng.uniform(0, 2 * np.pi, size=(n, 1))
    sig = base_speed * (1.0 + 0.1 * np.sin(2 * np.pi * t[None] * 2 + phase))
    sig += rng.normal(scale=0.01, size=(n, window))
    inj_start = rng.integers(4, window - 8, size=n)
    inj_val = rng.uniform(0.0, 1.2, size=n)
    for i in np.nonzero(y)[0]:
        sig[i, inj_start[i]:inj_start[i] + 8] = inj_val[i]
    x = (sig - sig.mean(0)) / (sig.std(0) + 1e-6)
    return x.astype(np.float32), y


GENERATORS = {"unsw": make_unsw_like, "road": make_road_like}


def make_split(dataset: str, seed: int, n: int, cfg: dict):
    """(x, y) of one split of the configuration's dataset."""
    if dataset == "unsw":
        return make_unsw_like(seed, n, cfg["num_features"],
                              cfg["num_classes"])
    if dataset == "road":
        return make_road_like(seed, n, window=cfg["num_features"])
    raise ValueError(f"unknown dataset {dataset!r}")


def dirichlet_partition(labels, num_clients: int, alpha: float, seed: int,
                        min_per_client: int = 8):
    """Label-Dirichlet split into per-client index arrays."""
    rng = np.random.default_rng(seed)
    classes = np.unique(labels)
    idx_by_class = [np.nonzero(labels == c)[0] for c in classes]
    for idx in idx_by_class:
        rng.shuffle(idx)
    shares = rng.dirichlet([alpha] * num_clients, size=len(classes))
    client_idx = [[] for _ in range(num_clients)]
    for ci, idx in enumerate(idx_by_class):
        cuts = (np.cumsum(shares[ci])[:-1] * len(idx)).astype(int)
        for k, part in enumerate(np.split(idx, cuts)):
            client_idx[k].append(part)
    out = [np.concatenate(parts) for parts in client_idx]
    pool = np.concatenate(out)
    for k in range(num_clients):
        if len(out[k]) < min_per_client:
            extra = rng.choice(pool, size=min_per_client - len(out[k]))
            out[k] = np.concatenate([out[k], extra])
        rng.shuffle(out[k])
    return out


def profile_arrays(n: int, seed: int, dropout_p: float,
                   speed_sigma: float = 0.6) -> dict:
    """Heterogeneous client profiles: lognormal speeds, uniform link
    latencies and memory headroom."""
    rng = np.random.default_rng(seed)
    return {"speed": rng.lognormal(0.0, speed_sigma, size=n),
            "net_latency": rng.uniform(0.01, 0.2, size=n),
            "memory": rng.uniform(0.4, 1.0, size=n),
            "dropout_p": np.full(n, float(dropout_p))}


def assign_batch_size(speed: float, memory: float, latency: float,
                      b_min: int = 64, b_max: int = 1024) -> int:
    """§IV-A initial batch: capacity mapped to the nearest power of two."""
    lat_penalty = 1.0 / (1.0 + 10.0 * max(latency, 0.0))
    score = (max(speed, 1e-3) * (0.5 + 0.5 * min(max(memory, 0.0), 1.0))
             * lat_penalty)
    raw = math.sqrt(b_min * b_max) * score
    pow2 = (64, 128, 256, 512, 1024)
    best = min(pow2, key=lambda b: abs(math.log(b) - math.log(max(raw, 1))))
    return int(min(max(best, b_min), b_max))


def drift_directions(seed: int, num_classes: int,
                     num_features: int) -> np.ndarray:
    """Per-class unit-ish drift directions (num_classes, num_features)."""
    rng = np.random.default_rng(seed)
    dirs = rng.normal(size=(num_classes, num_features))
    dirs /= np.sqrt(num_features)
    return dirs.astype(np.float32)

