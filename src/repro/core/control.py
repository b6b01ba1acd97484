"""Device-resident server control plane (paper §IV-A, §V-C).

The paper's three control mechanisms — adaptive client selection,
dynamic batch sizing and staleness-aware aggregation — are pure score
arithmetic over per-client statistics (the same formulation as the
companion works arXiv:2501.15038 / arXiv:2502.00036). Host-side they
lived in ``core/selection.AdaptiveClientSelector`` (numpy EMAs),
``core/batchsize.BatchSizeController`` (dicts) and per-arrival staleness
weights, which forced a device→host sync between every simulated round
and capped the cohort megastep at one dispatch *per round*.

``ControlState`` keeps every statistic the server reads or writes as
``(num_clients,)``-shaped device arrays, and the transitions below are
pure jnp functions usable inside ``jit``/``lax.scan``:

  ``observe``               — availability / pass-rate / round-time EMAs
                              (the selector's §V-C reliability history)
  ``score``                 — reliability × timeliness selection score
  ``select_topk_epsilon``   — stable top-k + ε-greedy pool swaps, the
                              exact decision function of
                              ``AdaptiveClientSelector.select`` given the
                              same uniform draws
  ``batch_feedback``        — straggler demote / fast-client promote over
                              power-of-two batch assignments (§IV-A)
  ``local_steps``           — device twin of
                              ``async_engine.local_step_count``
  ``lr_scale_update``       — FedL2P-style per-client LR adaptation
  ``staleness / grad-norm`` — per-client counters and EMAs

The host classes stay as the seeded oracles: ``tests/test_control.py``
pins every transition to them (same observation stream → same EMA /
score / assignment trajectories, f32 vs f64 tolerance only).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import selection

_POW2_MIN, _POW2_MAX = 64, 1024


class ControlState(NamedTuple):
    """Per-client control-plane statistics, all device-resident.

    Every field is ``(num_clients,)``-shaped except ``ef``, the batched
    error-feedback arena for int8 wire compression — ``(num_clients + 1,
    rows, lane)`` f32 (the +1 dummy row absorbs residuals of cohort
    padding), or a ``(0,)`` placeholder when compression is off.
    """
    avail: jnp.ndarray        # f32 availability EMA (init 1)
    pass_rate: jnp.ndarray    # f32 θ-filter pass-rate EMA (init 1)
    round_time: jnp.ndarray   # f32 round-time EMA (init 1)
    batch: jnp.ndarray        # i32 power-of-two batch assignment
    lr_scale: jnp.ndarray     # f32 per-client LR scale (FedL2P)
    grad_norm: jnp.ndarray    # f32 update-norm EMA (ACFL proxy)
    staleness: jnp.ndarray    # i32 rounds since last transmitted update
    has_ckpt: jnp.ndarray     # bool local checkpoint exists (§IV-C)
    ef: jnp.ndarray           # f32 error-feedback arena (quantize only)


def init_control(num_clients: int, batch_sizes=None, lr_scale=None,
                 arena=None, quantize: bool = False) -> ControlState:
    """Initial state matching the host classes' defaults (all EMAs 1)."""
    n = int(num_clients)
    # a fresh buffer per field: the scanned run donates the whole state,
    # and one buffer donated under two fields is refused
    ones = lambda: jnp.ones((n,), jnp.float32)  # noqa: E731
    if batch_sizes is None:
        batch = jnp.full((n,), _POW2_MIN, jnp.int32)
    else:
        batch = jnp.asarray(batch_sizes, jnp.int32)
    if quantize:
        assert arena is not None, "quantize=True needs the ParamArena"
        ef = jnp.zeros((n + 1, arena.rows, arena.lane), jnp.float32)
    else:
        ef = jnp.zeros((0,), jnp.float32)
    return ControlState(
        avail=ones(), pass_rate=ones(), round_time=ones(), batch=batch,
        lr_scale=(ones() if lr_scale is None
                  else jnp.asarray(lr_scale, jnp.float32)),
        grad_norm=ones(), staleness=jnp.zeros((n,), jnp.int32),
        has_ckpt=jnp.zeros((n,), bool), ef=ef)


# ---------------------------------------------------------------------------
# selection statistics (oracle: core.selection.AdaptiveClientSelector)
# ---------------------------------------------------------------------------

def observe_ema(avail_c: jnp.ndarray, pass_c: jnp.ndarray,
                rt_c: jnp.ndarray, mask: jnp.ndarray,
                delivered: jnp.ndarray, passed: jnp.ndarray,
                round_time: jnp.ndarray, ema: float):
    """The EMA arithmetic of one observation batch on GATHERED values.

    Factored out of ``observe`` so the shard-local population kernels
    (core/population.py) run the IDENTICAL float ops on their local
    gathers — bit-identity between the sharded and the single-device
    control plane hinges on sharing this function."""
    e = jnp.float32(ema)
    new_avail = e * avail_c + (1.0 - e) * delivered.astype(jnp.float32)
    new_avail = jnp.where(mask, new_avail, avail_c)
    upd = mask & delivered
    new_pass = jnp.where(upd,
                         e * pass_c + (1.0 - e) * passed.astype(jnp.float32),
                         pass_c)
    new_rt = jnp.where(upd, e * rt_c + (1.0 - e) * round_time, rt_c)
    return new_avail, new_pass, new_rt


def observe(state: ControlState, cohort: jnp.ndarray, mask: jnp.ndarray,
            delivered: jnp.ndarray, passed: jnp.ndarray,
            round_time: jnp.ndarray, ema: float = 0.8) -> ControlState:
    """Scatter one batch of observations into the EMAs.

    cohort: (K,) client ids; mask: (K,) bool — which slots are observed
    at all (unmasked slots keep their statistics); delivered/passed:
    (K,) bool; round_time: (K,) f32. The EMA arithmetic is the oracle's:
    availability moves toward ``delivered``; pass-rate and round-time
    move only when the client delivered.
    """
    new_avail, new_pass, new_rt = observe_ema(
        state.avail[cohort], state.pass_rate[cohort],
        state.round_time[cohort], mask, delivered, passed, round_time, ema)
    return state._replace(
        avail=state.avail.at[cohort].set(new_avail),
        pass_rate=state.pass_rate.at[cohort].set(new_pass),
        round_time=state.round_time.at[cohort].set(new_rt))


def observe_round(state: ControlState, cohort: jnp.ndarray,
                  failed: jnp.ndarray, active: jnp.ndarray,
                  passed: jnp.ndarray, round_time: jnp.ndarray,
                  ema: float = 0.8) -> ControlState:
    """One simulated round's observations for a (K,)-cohort, matching
    the host engine's two-phase order: every client whose dropout draw
    fired is observed ``delivered=False`` first; every client that ended
    up participating (never failed, or failed but checkpoint-recovered)
    is then observed ``delivered=True`` with its θ verdict and round
    time. A failed-then-recovered client receives BOTH observations,
    exactly like the host loop."""
    false = jnp.zeros_like(failed)
    state = observe(state, cohort, mask=failed, delivered=false,
                    passed=false, round_time=round_time, ema=ema)
    return observe(state, cohort, mask=active, delivered=active,
                   passed=passed, round_time=round_time, ema=ema)


def score(state: ControlState) -> jnp.ndarray:
    """(N,) selection scores: availability × (0.5+0.5·pass) × 1/(1+t)."""
    timeliness = 1.0 / (1.0 + state.round_time)
    return state.avail * (0.5 + 0.5 * state.pass_rate) * timeliness


def select_topk_epsilon(scores: jnp.ndarray, k: int,
                        epsilon: float = 0.0,
                        eps_u: Optional[jnp.ndarray] = None,
                        pick_u: Optional[jnp.ndarray] = None,
                        live: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """(k,) selected client ids — the oracle's decision function.

    Stable descending-score top-k, then ε-greedy exploration: slot i is
    swapped (prob ε, via ``eps_u[i]``) for a uniformly-drawn member of
    the shrinking not-chosen pool (``pick_u[i]`` mapped to a pool index,
    the picked client popped). With ``epsilon=0`` (or no draws) this is
    exactly ``AdaptiveClientSelector.select``'s top-k; with draws it is
    the same algorithm with the randomness injected explicitly.

    ``live`` (optional (n,) bool, scenario churn) restricts the
    EXPLORATION POOL to live clients — the caller already masks dead
    scores to -inf for the top-k, and the host oracle's pool is its
    live not-chosen cids (``AdaptiveClientSelector.select(k, live=...)``),
    so without this mask an ε-swap could pull a churned-out client into
    the cohort on the device paths only.
    """
    n = scores.shape[0]
    k = int(k)
    order = jnp.argsort(-scores, stable=True)
    chosen = order[:k]
    if epsilon <= 0.0 or eps_u is None or pick_u is None or k >= n:
        return chosen
    # pool = (live) not-chosen cids in ascending order (stable sort of
    # the exclusion mask: zeros/False — the pool members — come first)
    in_chosen = jnp.zeros((n,), bool).at[chosen].set(True)
    if live is None:
        excluded = in_chosen
        m0 = jnp.int32(n - k)
    else:
        excluded = in_chosen | ~live
        m0 = (~excluded).sum().astype(jnp.int32)
    pool = jnp.argsort(excluded, stable=True)
    idx = jnp.arange(n)

    def body(i, carry):
        chosen, pool, m = carry
        explore = (eps_u[i] < epsilon) & (m > 0)
        j = jnp.minimum((pick_u[i] * m.astype(jnp.float32))
                        .astype(jnp.int32), m - 1)
        pick = pool[j]
        chosen = chosen.at[i].set(jnp.where(explore, pick, chosen[i]))
        shifted = jnp.take(pool, jnp.minimum(idx + 1, n - 1))
        pool = jnp.where(explore & (idx >= j), shifted, pool)
        m = m - explore.astype(jnp.int32)
        return chosen, pool, m

    chosen, _, _ = jax.lax.fori_loop(
        0, k, body, (chosen, pool, m0))
    return chosen


def select_topk(scores: jnp.ndarray, k: int, key=None,
                epsilon: float = 0.0,
                live: Optional[jnp.ndarray] = None,
                candidate_frac: Optional[float] = None,
                candidate_shards: int = 8) -> jnp.ndarray:
    """Convenience wrapper drawing the exploration uniforms from a PRNG
    key (one ``(k,)`` draw per decision, mirroring the oracle's one
    ``rng.random()`` + one ``rng.integers()`` per slot). Routes through
    ``two_stage_select`` so callers can attach the candidate
    pre-filter; ``candidate_frac=None`` keeps the legacy single-stage
    decision untouched."""
    if key is None or epsilon <= 0.0:
        return two_stage_select(scores, k, candidate_frac=candidate_frac,
                                candidate_shards=candidate_shards,
                                live=live)
    ke, kp = jax.random.split(key)
    return two_stage_select(
        scores, k, candidate_frac=candidate_frac,
        candidate_shards=candidate_shards, epsilon=epsilon,
        eps_u=jax.random.uniform(ke, (int(k),)),
        pick_u=jax.random.uniform(kp, (int(k),)), live=live)


# ---------------------------------------------------------------------------
# two-stage selection (oracle: core.selection.candidate_mask_np)
# ---------------------------------------------------------------------------

def candidate_mask(scores: jnp.ndarray, k: int, frac: float,
                   shards: int) -> jnp.ndarray:
    """(N,) bool — stage 1 of two-stage selection: the sharded candidate
    pre-filter.

    The score vector is viewed as ``shards`` contiguous logical shards
    (last one -inf-padded) and each shard keeps only its top-``quota``
    entries (``selection.candidate_quota``; ties -> lower index, the
    same order as the stable descending argsort stage 2 uses). The
    union of the per-shard winners is what the exact masked top-k then
    sees. Cost per shard is O(per·quota) instead of a global O(N log N)
    sort, and under ``shard_map`` each device only ranks its own rows.

    Exactness: with ``quota >= k`` (always true at ``frac=1.0``, where
    the mask is all-True) every global top-k member survives its own
    shard's cut, so stage 2 returns bit-identical selections.
    """
    n = scores.shape[0]
    shards = max(1, min(int(shards), int(n)))
    per = -(-n // shards)
    quota = selection.candidate_quota(n, k, frac, shards)
    pad = shards * per - n
    s = scores
    if pad:
        s = jnp.concatenate(
            [s, jnp.full((pad,), -jnp.inf, scores.dtype)])
    s = s.reshape(shards, per)
    _, keep = jax.lax.top_k(s, quota)
    mask = jnp.zeros((shards, per), bool)
    mask = mask.at[jnp.arange(shards)[:, None], keep].set(True)
    return mask.reshape(-1)[:n]


def two_stage_select(scores: jnp.ndarray, k: int, *,
                     candidate_frac: Optional[float] = None,
                     candidate_shards: int = 8,
                     epsilon: float = 0.0,
                     eps_u: Optional[jnp.ndarray] = None,
                     pick_u: Optional[jnp.ndarray] = None,
                     live: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Candidate pre-filter + the existing exact masked top-k.

    ``candidate_frac=None`` is the legacy single-stage path, untouched.
    Otherwise non-candidates are masked to -inf for the top-k AND
    removed from the ε-exploration pool (exploration stays inside the
    candidate union by design — at scale the pool must not require the
    full population). At ``frac=1.0`` the mask is all-True, so both the
    scores and the pool are bit-identical to single-stage.
    """
    if candidate_frac is None:
        return select_topk_epsilon(scores, k, epsilon,
                                   eps_u=eps_u, pick_u=pick_u, live=live)
    cand = candidate_mask(scores, k, candidate_frac, candidate_shards)
    masked = jnp.where(cand, scores, -jnp.inf)
    pool_live = cand if live is None else (live & cand)
    return select_topk_epsilon(masked, k, epsilon,
                               eps_u=eps_u, pick_u=pick_u, live=pool_live)


# ---------------------------------------------------------------------------
# dynamic batch sizing (oracle: core.batchsize.BatchSizeController)
# ---------------------------------------------------------------------------

def batch_feedback(state: ControlState, cohort: jnp.ndarray,
                   round_times: jnp.ndarray, valid: jnp.ndarray,
                   b_min: int = _POW2_MIN, b_max: int = _POW2_MAX,
                   straggler_factor: float = 1.5) -> ControlState:
    """Straggler demote / fast promote over the cohort's round times.

    cohort: (K,) ids; round_times: (K,) f32; valid: (K,) bool (clients
    that actually reported a time this round). The median is the upper
    median over the valid entries — ``sorted(ts)[len(ts)//2]`` — exactly
    the host controller's rule.
    """
    new_b = batch_rule(state.batch[cohort], round_times, valid,
                       b_min, b_max, straggler_factor)
    return state._replace(batch=state.batch.at[cohort].set(new_b))


def batch_rule(b: jnp.ndarray, round_times: jnp.ndarray,
               valid: jnp.ndarray, b_min: int = _POW2_MIN,
               b_max: int = _POW2_MAX,
               straggler_factor: float = 1.5) -> jnp.ndarray:
    """``batch_feedback``'s decision on GATHERED assignments (shared
    with the shard-local kernels). The median is computed from the
    replicated (K,) cohort observations, so every shard derives the
    identical threshold."""
    m = valid.sum().astype(jnp.int32)
    ts = jnp.where(valid, round_times, jnp.inf)
    med = jnp.sort(ts)[jnp.minimum(m // 2, ts.shape[0] - 1)]
    f = jnp.float32(straggler_factor)
    demote = (round_times > f * med) & (b > b_min)
    promote = (round_times < med / f) & (b < b_max)
    new_b = jnp.where(demote, b // 2, jnp.where(promote, b * 2, b))
    return jnp.where(valid & (m > 0), new_b, b)


# ---------------------------------------------------------------------------
# misc per-client transitions
# ---------------------------------------------------------------------------

def grad_norm_update(state: ControlState, cohort: jnp.ndarray,
                     norms: jnp.ndarray, valid: jnp.ndarray) -> ControlState:
    """0.5/0.5 EMA of update L2 norms (the ACFL critical-period proxy)."""
    g = state.grad_norm[cohort]
    new_g = jnp.where(valid, 0.5 * g + 0.5 * norms, g)
    return state._replace(grad_norm=state.grad_norm.at[cohort].set(new_g))


def lr_scale_update(state: ControlState, cohort: jnp.ndarray,
                    norms: jnp.ndarray, valid: jnp.ndarray) -> ControlState:
    """FedL2P-style meta-rule: grow the scale while updates are small,
    shrink while they are large; clipped to [0.25, 2]."""
    s = state.lr_scale[cohort]
    new_s = jnp.clip(s * jnp.where(norms < 1.0, 1.05, 0.9), 0.25, 2.0)
    new_s = jnp.where(valid, new_s, s)
    return state._replace(lr_scale=state.lr_scale.at[cohort].set(new_s))


def staleness_update(state: ControlState, cohort: jnp.ndarray,
                     sent: jnp.ndarray) -> ControlState:
    """Per-client staleness counters: +1 every round, reset on transmit."""
    stale = state.staleness + 1
    new_c = jnp.where(sent, 0, stale[cohort])
    return state._replace(staleness=stale.at[cohort].set(new_c))


def checkpoint_update(state: ControlState, cohort: jnp.ndarray,
                      active: jnp.ndarray) -> ControlState:
    """Participating clients persist a local checkpoint (§IV-C)."""
    new_c = state.has_ckpt[cohort] | active
    return state._replace(has_ckpt=state.has_ckpt.at[cohort].set(new_c))


# ---------------------------------------------------------------------------
# local step count (oracle: async_engine.local_step_count)
# ---------------------------------------------------------------------------

def local_steps(n: jnp.ndarray, batch: jnp.ndarray, local_epochs: int,
                max_samples: int) -> jnp.ndarray:
    """Device twin of ``local_step_count``: per-round local steps,
    quantized UP to powers of two, capped by the per-round sample budget.
    All inputs broadcastable i32/f32 arrays; returns i32."""
    b = jnp.maximum(batch.astype(jnp.float32), 1.0)
    cap = jnp.maximum(1.0, jnp.floor(jnp.float32(max_samples) / b))
    steps = jnp.maximum(1.0, jnp.ceil(jnp.float32(local_epochs)
                                      * n.astype(jnp.float32) / b))
    steps = jnp.minimum(steps, cap)
    steps = 2.0 ** jnp.ceil(jnp.log2(steps))     # next power of two
    return jnp.minimum(steps, cap).astype(jnp.int32)
