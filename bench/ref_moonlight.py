"""Plain reference of the Moonlight-16B-A3B share (``moonlight.silo``):
its forward pass, loss and gradients, and federated rounds of it.

Nothing here imports the program or takes what it made. The block is
DeepSeek-V3's (arXiv:2412.19437 §2.1) as Moonlight configures it: no
q-LoRA, no YaRN, ``n_group`` 1, no multi-token prediction. Written out
in ``jax.numpy`` at ``precision="highest"``, in the dtype asked for:

  q        = x Wq, per head [q_nope (128) | q_pe (64)], RoPE on q_pe
  [c | kp] = x Wkv_a; c ← RMSNorm(c) (512 wide); kp ← RoPE(kp), 64 wide,
             one key part shared by all heads
  [kn | v] = c Wkv_b, per head 128 + 128
  attn     = softmax((q_nope·kn + q_pe·kp) / √192) over keys at or before
             the query, out = (attn v) Wo
  MoE      = Σ_e g_e(x) FFN_e(x) over the experts held here + FFN_shared(x)
             with s = sigmoid(x Wr) over all 64 experts, top-6 of s + b
             (b a seeded bias that only chooses), g = s over the chosen,
             divided by their sum (+1e-20), times 2.446; an expert that is
             chosen but not held adds nothing (another chip holds it)
  FFN(x)   = (silu(x Wg) ⊙ x Wu) Wd; RMSNorm eps 1e-5; RoPE θ 50,000

RoPE rotates interleaved pairs (x0, x1), (x2, x3), …; HF's DeepSeek code
first de-interleaves the 64 rope dims and rotates halves, which permutes
q_pe and kp alike and leaves q_pe·kp unchanged. The expert layer is
computed densely over the held experts (no dispatch, nothing dropped).
Attention runs one head at a time and each layer under ``jax.checkpoint``
so that S = 4,096 fits beside the optimizer state. The loss scores the
logits at position i against ``labels[i]``, the token after ``tokens[i]``.
One departure, the program's own choice and noted in the configuration
file: there is no sequence auxiliary loss.

Parameters: leaf i of the tree, dict keys in sorted order, is drawn from
``fold_in(PRNGKey(seed), i)``: norm weights 1, the embedding N(0, 0.02²),
every other matrix N(0, 1/fan_in), fan_in its second-to-last axis. The
router bias is ``router_bias_std · N(0, 1)`` from
``PRNGKey(router_bias_seed)``, shape (MoE layers, 64).

``dtype=jnp.bfloat16`` gives the training control: parameters,
activations and updates in bf16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen
from bench import reference as ref

HI = "highest"
F32 = np.float32


# ---------------------------------------------------------------------------
# the traffic's tokens and world, rebuilt from the seed
# ---------------------------------------------------------------------------

def make_lm_tokens(seed: int, n_seq: int, seq_len: int, vocab: int):
    """Zipf(1.1) unigrams with 30% repeats of the previous token; the
    labels are the tokens one place on (``repro.data.synthetic``'s
    documented generator)."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab + 1)
    p = 1.0 / ranks ** 1.1
    p /= p.sum()
    toks = rng.choice(vocab, size=(n_seq, seq_len + 1), p=p)
    rep = rng.random((n_seq, seq_len + 1)) < 0.3
    for j in range(1, seq_len + 1):
        toks[:, j] = np.where(rep[:, j], toks[:, j - 1], toks[:, j])
    return toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)


def build_world(cfg: dict, traffic: dict, seed: int) -> dict:
    """Client shards (an iid split of the sequences), eval sequences,
    profiles; batch fixed (``dynamic_batch`` off)."""
    n_cl, st = traffic["clients"], traffic["strategy_kwargs"]
    vocab, seq = cfg["vocab_size"], traffic["seq_len"]
    toks, labels = make_lm_tokens(seed, traffic["records"], seq, vocab)
    parts = np.array_split(
        np.random.default_rng(seed).permutation(traffic["records"]), n_cl)
    et, el = make_lm_tokens(seed + 1, traffic["eval_records"], seq, vocab)
    prof = gen.profile_arrays(n_cl, seed + 1, traffic["dropout_p"])
    sizes = np.array([len(p) for p in parts])
    b = int(min(st["batch_size"], sizes.min()))
    cap = max(1, st["max_samples_per_round"] // b)
    steps = min(min(cap, 1 << (max(1, -(-int(n) // b)) - 1).bit_length())
                for n in sizes)
    return {"tokens": toks, "labels": labels, "parts": parts,
            "eval": {"tokens": et, "labels": el}, "sizes": sizes,
            "speed": prof["speed"].astype(F32),
            "latency": prof["net_latency"].astype(F32),
            "dropout_p": prof["dropout_p"].astype(F32),
            "phys": (min(steps, cap), b)}


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _layer_shapes(c: dict, moe: bool) -> dict:
    d, H, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    layer = {"ln1": {"w": (d,)}, "ln2": {"w": (d,)},
             "attn": {"wq": (d, H * (nope + rope)), "wkv_a": (d, r + rope),
                      "kv_norm": {"w": (r,)}, "wkv_b": (r, H * (nope + vd)),
                      "wo": (H * vd, d)}}
    if not moe:
        ff = c["intermediate_size"]
        layer["ffn"] = {"wg": (d, ff), "wu": (d, ff), "wd": (ff, d)}
        return layer
    ff, held = c["moe_intermediate_size"], c["n_routed_experts"]
    shared = ff * c["n_shared_experts"]
    layer["moe"] = {"router": (d, c["router_experts"]),
                    "wg": (held, d, ff), "wu": (held, d, ff),
                    "wd": (held, ff, d),
                    "shared": {"wg": (d, shared), "wu": (d, shared),
                               "wd": (shared, d)}}
    return layer


def param_shapes(c: dict) -> dict:
    """The cut's parameter tree: leading dense layers and MoE layers
    each stacked on a layer axis."""
    d, v = c["hidden_size"], c["vocab_size"]
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    stack = lambda n, t: jax.tree.map(  # noqa: E731
        lambda s: (n,) + s, t, is_leaf=lambda s: isinstance(s, tuple))
    return {"embed": (v, d), "lm_head": (d, v), "final_norm": {"w": (d,)},
            "dense_layers": stack(n_dense, _layer_shapes(c, False)),
            "layers": stack(n_moe, _layer_shapes(c, True))}


def init_params(seed: int, c: dict) -> dict:
    """f32 parameters from the seed (the module docstring's rule)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(c), is_leaf=lambda s: isinstance(s, tuple))
    key = jax.random.PRNGKey(seed)
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = path[-1].key
        if name == "w":
            leaves.append(jnp.ones(shape, jnp.float32))
            continue
        std = 0.02 if name == "embed" else shape[-2] ** -0.5
        leaves.append(std * jax.random.normal(jax.random.fold_in(key, i),
                                              shape, jnp.float32))
    return jax.tree.unflatten(treedef, leaves)


def router_bias(c: dict) -> jnp.ndarray:
    n_moe = c["num_hidden_layers"] - c["first_k_dense_replace"]
    return c["router_bias_std"] * jax.random.normal(
        jax.random.PRNGKey(c["router_bias_seed"]),
        (n_moe, c["router_experts"]), jnp.float32)


def _mm(a, b):
    return jnp.matmul(a, b, precision=HI)


def _rmsnorm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x (B, S, H, D): RoPE on interleaved pairs at positions 0..S-1."""
    S, D = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return out.reshape(x.shape).astype(x.dtype)


def _ffn(p, x):
    return _mm(jax.nn.silu(_mm(x, p["wg"])) * _mm(x, p["wu"]), p["wd"])


def attention(c, p, x):
    B, S, _ = x.shape
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
    q = _mm(x, p["wq"]).reshape(B, S, H, nope + rope)
    kv_a = _mm(x, p["wkv_a"])
    latent = _rmsnorm(kv_a[..., :r], p["kv_norm"]["w"], c["rms_norm_eps"])
    kp = _rope(kv_a[..., None, r:], c["rope_theta"])[:, :, 0]
    kv = _mm(latent, p["wkv_b"]).reshape(B, S, H, nope + vd)
    qn, qp = q[..., :nope], _rope(q[..., nope:], c["rope_theta"])
    scale = (nope + rope) ** -0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]

    def head(args):
        qn_h, qp_h, kn_h, v_h = args                  # (B, S, ·)
        s = (jnp.einsum("bqd,bkd->bqk", qn_h, kn_h, precision=HI)
             + jnp.einsum("bqd,bkd->bqk", qp_h, kp, precision=HI)) * scale
        s = jnp.where(causal, s.astype(jnp.float32), -jnp.inf)
        a = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        return jnp.einsum("bqk,bkd->bqd", a, v_h, precision=HI)

    heads_first = lambda t: jnp.moveaxis(t, 2, 0)  # noqa: E731
    o = jax.lax.map(head, (heads_first(qn), heads_first(qp),
                           heads_first(kv[..., :nope]),
                           heads_first(kv[..., nope:])))
    return _mm(jnp.moveaxis(o, 0, 2).reshape(B, S, H * vd), p["wo"])


def experts(c, p, x, bias, offset: int = 0):
    """The held experts' part and the shared experts, x (T, d)."""
    scores = jax.nn.sigmoid(_mm(x.astype(jnp.float32),
                                p["router"].astype(jnp.float32)))
    _, top = jax.lax.top_k(scores + bias, c["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, top, -1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * c["routed_scaling_factor"]
    held = p["wg"].shape[0]
    gate = jnp.zeros((x.shape[0], c["router_experts"]), jnp.float32)
    gate = gate.at[jnp.arange(x.shape[0])[:, None], top].set(w)
    gate = gate[:, offset:offset + held].astype(x.dtype)
    out = _ffn(p["shared"], x)
    for e in range(held):
        fe = {"wg": p["wg"][e], "wu": p["wu"][e], "wd": p["wd"][e]}
        out = out + gate[:, e:e + 1] * _ffn(fe, x)
    return out


def forward(c, params, tokens):
    """Logits (B, S, vocab) of the cut."""
    eps = c["rms_norm_eps"]
    x = params["embed"][tokens]
    B, S, d = x.shape

    @jax.checkpoint
    def dense(h, lp):
        h = h + attention(c, lp["attn"], _rmsnorm(h, lp["ln1"]["w"], eps))
        return h + _ffn(lp["ffn"], _rmsnorm(h, lp["ln2"]["w"], eps)), None

    @jax.checkpoint
    def moe(h, lp_b):
        lp, b = lp_b
        h = h + attention(c, lp["attn"], _rmsnorm(h, lp["ln1"]["w"], eps))
        f = experts(c, lp["moe"], _rmsnorm(h, lp["ln2"]["w"], eps)
                    .reshape(B * S, d), b)
        return h + f.reshape(B, S, d), None

    x, _ = jax.lax.scan(dense, x, params["dense_layers"])
    x, _ = jax.lax.scan(moe, x, (params["layers"],
                                 router_bias(c).astype(x.dtype)))
    return _mm(_rmsnorm(x, params["final_norm"]["w"], eps),
               params["lm_head"])


def loss(c, params, tokens, labels):
    logits = forward(c, params, tokens).astype(jnp.float32)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - gold)


@functools.partial(jax.jit, static_argnums=(0,))
def _eval_loss(c, params, tokens, labels):
    return loss(dict(c), params, tokens, labels)


@functools.partial(jax.jit, static_argnums=(0,), static_argnames=(
    "lr", "momentum"))
def local_training(c, params, tokens, labels, lr: float, momentum: float):
    """One client's SGD with momentum from the round's globals over its
    (steps, B, S) batches. Returns its delta (f32) and mean step loss."""
    c = dict(c)
    mom = jax.tree.map(jnp.zeros_like, params)

    def step(carry, batch):
        p, m = carry
        value, g = jax.value_and_grad(
            lambda q: loss(c, q, *batch))(p)
        m = jax.tree.map(lambda mi, gi: momentum * mi + gi, m, g)
        p = jax.tree.map(lambda pi, mi: pi - lr * mi, p, m)
        return (p, m), value

    (p, _), losses = jax.lax.scan(step, (params, mom), (tokens, labels))
    delta = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                         - b.astype(jnp.float32), p, params)
    return delta, losses.astype(jnp.float32).mean()


@jax.jit
def _aligned(delta, ref_sign):
    return sum(jnp.sum(jnp.sign(d).astype(jnp.int8) == r)
               for d, r in zip(jax.tree.leaves(delta),
                               jax.tree.leaves(ref_sign)))


# ---------------------------------------------------------------------------
# the federated rounds (the scanned engine's semantics, as in reference.py)
# ---------------------------------------------------------------------------

def federated_rounds(world: dict, cfg: dict, traffic: dict, seed: int,
                     rounds: int, eval_marks, dtype=jnp.float32):
    """``rounds`` rounds of the ``ours`` strategy (async quorum 0.5, θ
    filter, staleness weights, reliability-scored top-k with ε-greedy)
    without a dynamic world, the control plane from ``reference.py``.
    Returns (records, initial params, final params), params as flat
    numpy f32 dicts keyed by their tree path."""
    st = traffic["strategy_kwargs"]
    lr, theta = float(st["lr"]), float(st["theta"])
    quorum, alpha0 = 0.5, 1.0
    n_cl = traffic["clients"]
    k = max(1, int(st["select_fraction"] * n_cl))
    steps, b = world["phys"]
    every = traffic["eval_every"]
    c = tuple(sorted((n, v) for n, v in cfg.items()
                     if isinstance(v, (int, float)) and not
                     isinstance(v, bool)))

    p0 = init_params(seed, cfg)
    params = jax.tree.map(lambda a: a.astype(dtype), p0)
    n_par = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(p0))
    p0 = flat_numpy(p0)
    payload = F32(4 * n_par)
    ev = {n: jnp.asarray(v) for n, v in world["eval"].items()}
    sizes = world["sizes"].astype(np.int32)
    rows = [world["parts"][i] for i in range(n_cl)]

    avail, pass_rate, rtime = (np.ones(n_cl, F32) for _ in range(3))
    has_ckpt = np.zeros(n_cl, bool)
    ref_sign, ref_valid = None, False
    sim_t, bytes_s, acc = F32(0), F32(0), float("nan")
    base = jax.random.fold_in(jax.random.PRNGKey(seed), 7)
    records = []
    for r in range(rounds):
        k_eps, k_pick, k_drop, k_data = jax.random.split(
            jax.random.fold_in(base, r), 4)
        score = (avail * (F32(0.5) + F32(0.5) * pass_rate)
                 * (F32(1) / (F32(1) + rtime)))
        cohort = ref._select(score, k,
                             np.asarray(jax.random.uniform(k_eps, (k,))),
                             np.asarray(jax.random.uniform(k_pick, (k,))),
                             None)
        failed = (np.asarray(jax.random.uniform(k_drop, (k,)))
                  < world["dropout_p"][cohort])
        active = np.ones(k, bool)
        delay = np.where(failed, np.where(has_ckpt[cohort],
                                          F32(ref.RECOVERY_S),
                                          F32(ref.RESTART_S)), F32(0))
        sz = sizes[cohort]
        idx = np.asarray(jax.random.randint(
            k_data, (k, steps, b), 0, jnp.asarray(sz)[:, None, None]))
        deltas, losses, ratios = [], [], []
        for j, cl in enumerate(cohort):
            seqs = rows[cl][idx[j]]                   # (steps, b)
            d, lo = local_training(
                c, params, jnp.asarray(world["tokens"][seqs]),
                jnp.asarray(world["labels"][seqs]), lr=lr, momentum=0.9)
            if ref_valid:
                ratios.append(F32(int(_aligned(d, ref_sign))) / F32(n_par))
            deltas.append(jax.device_get(d))
            losses.append(F32(lo))
            del d
        losses = np.asarray(losses, F32)
        if ref_valid:
            ratios = np.asarray(ratios, F32)
            passed = ratios >= F32(theta)
            margin = float(np.min(np.abs(ratios - F32(theta))))
        else:
            passed, margin = np.ones(k, bool), float("inf")
        sent = active & passed

        steps_t = ref._local_steps(sz, np.minimum(b, sz),
                                   int(st["max_samples_per_round"]))
        train_t = ((steps_t.astype(F32) * F32(ref.T_LAUNCH)
                    + steps_t.astype(F32) * F32(b) * F32(ref.T_SAMPLE))
                   / np.maximum(world["speed"][cohort], F32(1e-3)))
        msg = np.where(sent, payload, F32(ref.BEACON_BYTES))
        transfer = world["latency"][cohort] + msg / F32(ref.BANDWIDTH)
        arrive = (delay + train_t + transfer).astype(F32)
        n_active = int(active.sum())
        bytes_s = F32(bytes_s + np.where(active, msg, F32(0)).sum(dtype=F32))
        t_act = np.where(active, arrive, F32(np.inf))
        q_idx = max(0, int(np.ceil(F32(quorum) * F32(n_active))) - 1)
        sim_t = F32(sim_t + np.sort(t_act)[q_idx])
        rank = np.argsort(np.argsort(t_act, kind="stable"), kind="stable")
        tau = np.maximum(0, rank - q_idx).astype(F32)
        alphas = F32(alpha0) * (F32(1) + tau) ** F32(-0.5)
        n_applied = int(sent.sum())
        w = np.where(sent, alphas, F32(0)) / F32(max(n_applied, 1))

        old = params
        params = jax.tree.map(
            lambda o, *ds: (o.astype(jnp.float32) + jnp.tensordot(
                jnp.asarray(w), jnp.stack([jnp.asarray(x) for x in ds]),
                axes=1, precision=HI)).astype(dtype), old, *deltas)
        del deltas
        if n_applied > 0:
            ref_sign = jax.tree.map(
                lambda n, o: jnp.sign(n.astype(jnp.float32)
                                      - o.astype(jnp.float32))
                .astype(jnp.int8), params, old)
            ref_valid = True
        del old

        e = F32(ref.EMA)
        a_c, p_c, t_c = avail[cohort], pass_rate[cohort], rtime[cohort]
        a_c = np.where(failed, e * a_c, a_c)
        a_c = e * a_c + (F32(1) - e)
        p_c = e * p_c + (F32(1) - e) * sent.astype(F32)
        t_c = e * t_c + (F32(1) - e) * arrive
        avail[cohort], pass_rate[cohort], rtime[cohort] = a_c, p_c, t_c
        has_ckpt[cohort] = True

        if r % every == 0 or r in eval_marks:
            acc = -float(_eval_loss(c, params, ev["tokens"], ev["labels"]))
        records.append({"loss": float(losses.sum(dtype=F32) / F32(k)),
                        "accuracy": acc, "sim_time": float(sim_t),
                        "bytes_sent": float(bytes_s),
                        "updates_applied": n_applied,
                        "accept_rate": float(F32(sent.sum()) / F32(k)),
                        "theta_margin": margin,
                        "ratios": [float(x) for x in np.atleast_1d(ratios)]
                        if ref_valid and len(records) else []})
    return records, p0, flat_numpy(params)


def flat_numpy(tree) -> dict:
    """{"layers/moe/wg": array, ...} as numpy f32."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {"/".join(k.key for k in path): np.asarray(v, F32)
            for path, v in flat}
