"""Plain references of the detector, of a federated round and of the
scorer.

Nothing here imports the program or takes what it made. The federated
round follows the documented semantics of the scanned engine for the
paper's ``ours`` strategy (async quorum, θ sign-alignment filter,
staleness-weighted aggregation, reliability-scored top-k selection with
ε-greedy exploration, dropout with checkpoint recovery, §IV-A batch
feedback, and the dynamic-world transitions), written out step by step:
local training in ``jax.numpy`` at ``precision="highest"`` (the model
state in the dtype asked for), the control plane in numpy float32. The
random draws come from the same JAX keys the engine documents
(``fold_in(fold_in(PRNGKey(seed), 7), round)`` split four ways), so the
same seed selects the same clients and samples the same rows.

``dtype=jnp.bfloat16`` gives the training control: the same rounds with
the model's parameters, activations and updates in bf16.
``matmul_dtype=jnp.float8_e4m3fn`` gives the scorer's: its matmul inputs
rounded to fp8, one step below the bf16 inputs that f32 matmuls at the
default precision already take on the TPU's MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen

EPSILON = 0.1          # ε-greedy exploration of the selection
EMA = 0.8              # reliability EMAs
RECOVERY_S, RESTART_S = 0.2, 1.0
T_SAMPLE, T_LAUNCH = 2e-6, 0.0
BANDWIDTH, BEACON_BYTES = 1e9, 0.125
B_MIN, B_MAX, STRAGGLER = 64, 1024, 1.5
SCENARIOS = {
    # the "dynamic" world: drift, churn, link walks, dropout regimes
    "dynamic": {"drift": {"rate": 0.05, "max_amp": 1.0, "seed": 0},
                "churn": {"period": 3, "leave_frac": 0.25, "seed": 0},
                "links": {"bw_sigma": 0.25, "lat_sigma": 0.25, "clip": 4.0,
                          "seed": 0},
                "dropout": {"boundaries": [8], "scales": [1.0, 2.0]}},
}
F32 = np.float32


# ---------------------------------------------------------------------------
# the detector
# ---------------------------------------------------------------------------

def layer_dims(cfg: dict):
    return [cfg["num_features"], *cfg["mlp_hidden"], cfg["num_classes"]]


def init_params(seed: int, cfg: dict, bias_scale: float = 0.0):
    """w_i ~ N(0, 1/fan_in), b_i = 0 (or N(0, bias_scale²)), f32."""
    return init_from_key(jax.random.PRNGKey(seed), tuple(layer_dims(cfg)),
                         bias_scale)


def init_from_key(key, dims, bias_scale: float = 0.0):
    keys = jax.random.split(key, len(dims) - 1)
    params = {}
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        params[f"w{i}"] = (jax.random.normal(keys[i], (a, b), jnp.float32)
                           * (1.0 / np.sqrt(a)))
        bkey = jax.random.fold_in(keys[i], 1)
        params[f"b{i}"] = (bias_scale
                           * jax.random.normal(bkey, (b,), jnp.float32))
    return params


def forward(params, x, matmul_dtype=None):
    """Logits. ``matmul_dtype`` rounds each matmul's inputs to that type
    (f32 accumulation): the lower-precision control of the scorer."""
    n = len(params) // 2
    for i in range(n):
        w = params[f"w{i}"]
        if matmul_dtype is None:
            x = jnp.matmul(x, w, precision="highest")
        else:
            x = jnp.matmul(x.astype(matmul_dtype), w.astype(matmul_dtype),
                           preferred_element_type=jnp.float32)
        x = x + params[f"b{i}"]
        if i < n - 1:
            x = jnp.maximum(x, 0)
    return x


def xent(params, x, y):
    logits = forward(params, x).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


@functools.partial(jax.jit, static_argnames=("matmul_dtype",))
def probs_and_scores(params, x, matmul_dtype=None):
    """The scorer: softmax class probabilities and 1 - P(Normal)."""
    logits = forward(params, x.astype(params["w0"].dtype), matmul_dtype)
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    return p, 1.0 - p[:, 0]


@jax.jit
def accuracy(params, x, y):
    logits = forward(params, x.astype(params["w0"].dtype))
    return jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("lr", "momentum"))
def local_training(params, xb, yb, lr: float, momentum: float):
    """Each client's SGD with momentum from the round's globals (state
    reset every round). xb: (K, steps, B, F); yb: (K, steps, B).
    Returns per-client deltas (f32) and mean step losses."""
    def one(xc, yc):
        mom = jax.tree.map(jnp.zeros_like, params)

        def step(carry, batch):
            p, m = carry
            loss, g = jax.value_and_grad(xent)(p, *batch)
            m = jax.tree.map(lambda mi, gi: momentum * mi + gi, m, g)
            p = jax.tree.map(lambda pi, mi: pi - lr * mi, p, m)
            return (p, m), loss

        (p, _), losses = jax.lax.scan(step, (params, mom), (xc, yc))
        delta = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                             - b.astype(jnp.float32), p, params)
        return delta, losses.astype(jnp.float32).mean()
    return jax.vmap(one)(xb, yb)


# ---------------------------------------------------------------------------
# the world the program is handed, rebuilt from the seed
# ---------------------------------------------------------------------------

def build_world(cfg: dict, traffic: dict, seed: int) -> dict:
    """Client shards, eval split, profiles and batch assignments."""
    n_cl = traffic["clients"]
    st = traffic["strategy_kwargs"]
    x, y = gen.make_split(cfg["dataset"], seed, traffic["records"], cfg)
    xe, ye = gen.make_split(cfg["dataset"], seed + 1,
                            traffic["eval_records"], cfg)
    parts = gen.dirichlet_partition(y, n_cl, traffic["alpha"], seed)
    prof = gen.profile_arrays(n_cl, seed + 1, traffic["dropout_p"])
    sizes = np.array([len(p) for p in parts])
    if st.get("dynamic_batch", True):
        assigned = np.array([gen.assign_batch_size(
            float(prof["speed"][c]), float(prof["memory"][c]),
            float(prof["net_latency"][c])) for c in range(n_cl)])
    else:
        assigned = np.full(n_cl, st["batch_size"])
    batch = np.minimum(assigned, sizes)
    b_phys = int(batch.min())
    cap = max(1, st["max_samples_per_round"] // b_phys)
    steps = [min(cap, 1 << (max(1, -(-int(n) // b_phys)) - 1).bit_length())
             for n in sizes]
    return {"x": x, "y": y, "parts": parts, "xe": xe, "ye": ye,
            "sizes": sizes, "batch": batch.astype(np.int32),
            "speed": prof["speed"].astype(F32),
            "latency": prof["net_latency"].astype(F32),
            "dropout_p": prof["dropout_p"].astype(F32),
            "phys": (min(min(steps), cap), b_phys)}


# ---------------------------------------------------------------------------
# control-plane pieces (numpy float32)
# ---------------------------------------------------------------------------

def _select(scores, k, eps_u, pick_u, live):
    n = len(scores)
    chosen = np.argsort(-scores, kind="stable")[:k].copy()
    if k >= n:
        return chosen
    excluded = np.zeros(n, bool)
    excluded[chosen] = True
    if live is not None:
        excluded |= ~live
    m = int((~excluded).sum())
    pool = np.argsort(excluded, kind="stable")
    for i in range(k):
        if eps_u[i] < F32(EPSILON) and m > 0:
            j = min(int(F32(pick_u[i]) * F32(m)), m - 1)
            chosen[i] = pool[j]
            pool = np.concatenate([pool[:j], pool[j + 1:], pool[-1:]])
            m -= 1
    return chosen


def _local_steps(n, b, max_samples):
    b = np.maximum(b.astype(F32), F32(1))
    cap = np.maximum(F32(1), np.floor(F32(max_samples) / b))
    steps = np.maximum(F32(1), np.ceil(n.astype(F32) / b))
    steps = np.minimum(steps, cap)
    steps = F32(2) ** np.ceil(np.log2(steps))
    return np.minimum(steps, cap).astype(np.int32)


def _world_step(ws, r, scn, n):
    if scn is None:
        return ws
    ws = dict(ws)
    c = scn.get("churn")
    if c:
        leave = min(int(round(c["leave_frac"] * n)), n - 1)
        if leave > 0:
            offset = ((r // c["period"]) * leave + c["seed"]) % n
            ws["live"] = ((np.arange(n) - offset) % n) >= leave
    lk = scn.get("links")
    if lk:
        key = jax.random.fold_in(jax.random.PRNGKey(lk["seed"]), r)
        kb, kl = jax.random.split(key)
        lo, hi = F32(1.0 / lk["clip"]), F32(lk["clip"])
        nb = np.asarray(jax.random.normal(kb, (n,)))
        nl = np.asarray(jax.random.normal(kl, (n,)))
        ws["bw"] = np.clip(ws["bw"] * np.exp(F32(lk["bw_sigma"]) * nb),
                           lo, hi)
        ws["lat"] = np.clip(ws["lat"] * np.exp(F32(lk["lat_sigma"]) * nl),
                            lo, hi)
    d = scn.get("drift")
    if d:
        ws["amp"] = min(F32(d["rate"]) * F32(r), F32(d["max_amp"]))
    dp = scn.get("dropout")
    if dp:
        ws["drop_scale"] = F32(dp["scales"][sum(r >= b for b in
                                                dp["boundaries"])])
    return ws


# ---------------------------------------------------------------------------
# the federated rounds
# ---------------------------------------------------------------------------

def federated_rounds(world: dict, cfg: dict, traffic: dict, seed: int,
                     rounds: int, eval_marks, dtype=jnp.float32):
    """``rounds`` rounds of the scanned engine's semantics from the seed,
    evaluating every ``eval_every`` rounds and at ``eval_marks`` (the last
    round of each ``session.run`` call, whose eval the engine forces).

    Returns (per-round records, initial params, final params), params as
    numpy f32 dicts. Records hold loss, accuracy, sim_time, bytes_sent,
    updates_applied and accept_rate, as the engine's records do, and
    ``theta_margin``: how near the round's closest client came to θ."""
    st = traffic["strategy_kwargs"]
    lr = float(st.get("lr", 5e-3))
    theta = float(st.get("theta", 0.65))
    quorum, alpha0 = 0.5, 1.0
    dyn = bool(st.get("dynamic_batch", True))
    max_samples = int(st["max_samples_per_round"])
    n_cl = traffic["clients"]
    k = max(1, int(st.get("select_fraction", 1.0) * n_cl))
    selecting = st.get("select_fraction", 1.0) < 1.0
    if not selecting:
        k = n_cl
    steps_phys, b_phys = world["phys"]
    scn = SCENARIOS.get(traffic.get("scenario")) if traffic.get(
        "scenario") else None
    every = traffic["eval_every"]

    params0 = init_params(seed, cfg)
    params = jax.tree.map(lambda a: a.astype(dtype), params0)
    n_par = sum(int(np.prod(v.shape)) for v in params0.values())
    payload = F32(4 * n_par)
    xe = jnp.asarray(world["xe"])
    ye = jnp.asarray(world["ye"])
    dirs = None
    if scn and scn.get("drift"):
        dirs = gen.drift_directions(scn["drift"]["seed"], cfg["num_classes"],
                                    cfg["num_features"])
    # client data stacked as the engine keeps it: (N, cap, F), zero pad
    cap = int(world["sizes"].max())
    xs = np.zeros((n_cl, cap, cfg["num_features"]), F32)
    ys = np.zeros((n_cl, cap), np.int32)
    for c, p in enumerate(world["parts"]):
        xs[c, :len(p)] = world["x"][p]
        ys[c, :len(p)] = world["y"][p]
    sizes = world["sizes"].astype(np.int32)

    avail = np.ones(n_cl, F32)
    pass_rate = np.ones(n_cl, F32)
    rtime = np.ones(n_cl, F32)
    batch = world["batch"].copy()
    has_ckpt = np.zeros(n_cl, bool)
    ref_sign, ref_valid = None, False
    sim_t = F32(0)
    bytes_s = F32(0)
    acc = float("nan")
    ws = {"live": np.ones(n_cl, bool), "bw": np.ones(n_cl, F32),
          "lat": np.ones(n_cl, F32), "amp": F32(0),
          "drop_scale": F32(scn["dropout"]["scales"][0]
                            if scn and scn.get("dropout") else 1.0)}
    base = jax.random.fold_in(jax.random.PRNGKey(seed), 7)
    records = []
    for r in range(rounds):
        k_eps, k_pick, k_drop, k_data = jax.random.split(
            jax.random.fold_in(base, r), 4)
        ws = _world_step(ws, r, scn, n_cl)
        live = ws["live"] if scn and scn.get("churn") else None
        if selecting:
            score = (avail * (F32(0.5) + F32(0.5) * pass_rate)
                     * (F32(1) / (F32(1) + rtime)))
            if scn:
                score = np.where(ws["live"], score, F32(-np.inf))
            cohort = _select(score, k,
                             np.asarray(jax.random.uniform(k_eps, (k,))),
                             np.asarray(jax.random.uniform(k_pick, (k,))),
                             ws["live"] if scn else None)
        else:
            cohort = np.arange(k)
        live_c = ws["live"][cohort] if scn else np.ones(k, bool)
        drop_p = world["dropout_p"][cohort]
        if scn and scn.get("dropout"):
            drop_p = drop_p * ws["drop_scale"]
        failed = np.asarray(jax.random.uniform(k_drop, (k,))) < drop_p
        failed &= live_c
        active = live_c.copy()
        delay = np.where(failed, np.where(has_ckpt[cohort], F32(RECOVERY_S),
                                          F32(RESTART_S)), F32(0))
        sz = sizes[cohort]
        idx = np.asarray(jax.random.randint(
            k_data, (k, steps_phys, b_phys), 0,
            jnp.asarray(sz)[:, None, None]))
        xb = xs[cohort[:, None, None], idx]
        yb = ys[cohort[:, None, None], idx]
        if dirs is not None:
            xb = xb + ws["amp"] * dirs[yb]
        deltas, losses = local_training(
            params, jnp.asarray(xb, dtype), jnp.asarray(yb), lr=lr,
            momentum=0.9)
        deltas = {n: np.asarray(v) for n, v in deltas.items()}
        losses = np.asarray(losses, F32)
        flat = np.concatenate([deltas[n].reshape(k, -1)
                               for n in sorted(deltas)], axis=1)
        margin = float("inf")
        if ref_valid:
            ratios = ((np.sign(flat) == ref_sign[None]).sum(1)
                      .astype(F32) / F32(n_par))
            passed = ratios >= F32(theta)
            margin = float(np.min(np.abs(ratios - F32(theta))))
        else:
            passed = np.ones(k, bool)
        sent = active & passed

        b_eff = np.minimum(batch[cohort] if dyn else b_phys, sz)
        steps_t = _local_steps(sz, b_eff, max_samples).astype(F32)
        train_t = ((steps_t * F32(T_LAUNCH)
                    + steps_t * b_eff.astype(F32) * F32(T_SAMPLE))
                   / np.maximum(world["speed"][cohort], F32(1e-3)))
        msg = np.where(sent, payload, F32(BEACON_BYTES))
        if scn and scn.get("links"):
            transfer = (world["latency"][cohort] * ws["lat"][cohort]
                        + msg / (F32(BANDWIDTH) * ws["bw"][cohort]))
        else:
            transfer = world["latency"][cohort] + msg / F32(BANDWIDTH)
        arrive = (delay + train_t + transfer).astype(F32)
        n_active = int(active.sum())
        bytes_s = F32(bytes_s + np.where(active, msg, F32(0)).sum(
            dtype=F32))
        t_act = np.where(active, arrive, F32(np.inf))
        q_idx = max(0, int(np.ceil(F32(quorum) * F32(n_active))) - 1)
        if n_active > 0:
            sim_t = F32(sim_t + np.sort(t_act)[q_idx])
        rank = np.argsort(np.argsort(t_act, kind="stable"), kind="stable")
        tau = np.maximum(0, rank - q_idx).astype(F32)
        alphas = F32(alpha0) * (F32(1) + tau) ** F32(-0.5)
        n_applied = int(sent.sum())
        w = np.where(sent, alphas, F32(0)) / F32(max(n_applied, 1))

        old = params
        params = {n: (old[n].astype(jnp.float32)
                      + jnp.tensordot(jnp.asarray(w), jnp.asarray(deltas[n]),
                                      axes=1, precision="highest")
                      ).astype(dtype) for n in old}
        if n_applied > 0:
            ref_sign = np.concatenate([
                np.sign(np.asarray(params[n], F32) - np.asarray(old[n], F32))
                .reshape(-1) for n in sorted(params)])
            ref_valid = True

        # control-plane transitions: failures observed first, then the
        # clients that took part (a recovered failure gets both)
        e = F32(EMA)
        a_c = avail[cohort]
        a_c = np.where(failed, e * a_c, a_c)
        p_c, t_c = pass_rate[cohort], rtime[cohort]
        a_c = np.where(active, e * a_c + (F32(1) - e), a_c)
        p_c = np.where(active, e * p_c + (F32(1) - e) * sent.astype(F32),
                       p_c)
        t_c = np.where(active, e * t_c + (F32(1) - e) * arrive, t_c)
        avail[cohort], pass_rate[cohort], rtime[cohort] = a_c, p_c, t_c
        if dyn:
            b_c = batch[cohort]
            m = int(active.sum())
            ts = np.where(active, arrive, F32(np.inf))
            med = np.sort(ts)[min(m // 2, k - 1)]
            f = F32(STRAGGLER)
            demote = (arrive > f * med) & (b_c > B_MIN)
            promote = (arrive < med / f) & (b_c < B_MAX)
            new_b = np.where(demote, b_c // 2,
                             np.where(promote, b_c * 2, b_c))
            if m > 0:
                batch[cohort] = np.where(active, new_b, b_c)
        has_ckpt[cohort] |= active

        loss = (np.where(active, losses, F32(0)).sum(dtype=F32)
                / F32(max(n_active, 1)))
        denom = (F32(max(int(live_c.sum()), 1))
                 if scn and scn.get("churn") else F32(k))
        if r % every == 0 or r in eval_marks:
            acc = float(accuracy(params, xe, ye))
        records.append({"loss": float(loss), "accuracy": acc,
                        "sim_time": float(sim_t),
                        "bytes_sent": float(bytes_s),
                        "updates_applied": n_applied,
                        "accept_rate": float(F32(sent.sum()) / denom),
                        "theta_margin": margin})
    to_np = lambda t: {n: np.asarray(v, F32) for n, v in t.items()}  # noqa
    return records, to_np(params0), to_np(params)
