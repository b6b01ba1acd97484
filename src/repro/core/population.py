"""Sharded population plane — the million-client control state.

The device control plane (core/control.py) keeps every per-client
statistic as a ``(num_clients,)`` array and every transition as a
gather → EMA → scatter over a (K,)-cohort. At 1M clients those arrays
must live sharded over the mesh "data" axis, and a transition must touch
only the shard-local rows: each shard gathers with OWNED indices (ids
that fall inside its slice), applies the identical arithmetic
(``control.observe_ema`` / ``control.batch_rule`` are shared, so the
float ops are bitwise the same) and scatters through a dummy-row trick —
non-owned cohort slots are redirected to an appended scratch row that is
sliced off, so the scatter is deterministic (owned indices are unique;
only the discarded dummy row ever sees colliding writes).

Two drivers run the same kernel:

  ``round_update_logical``  — single-device: the (N,) arrays are viewed
                              as (shards, N/shards) and the kernel is
                              vmapped with per-shard offsets. This is
                              how tests pin shard-local == global
                              bit-identity without a multi-device host,
                              and how the scaling benchmark isolates the
                              sharded arithmetic from device count.
  ``round_update_sharded``  — the real ``shard_map`` over mesh "data"
                              (cohort observations replicated, state
                              sharded); exercised by the CI scale-smoke
                              under ``--xla_force_host_platform_device_
                              count=8`` and by the dry-run launcher.

Selection stage 1 lives here too: ``sharded_candidates`` ranks only the
local rows per shard (partial top-k, ``selection.candidate_quota``) and
emits a small replicated candidate union; ``topk_from_candidates``
recovers the EXACT global top-k from the union via a (score desc, id
asc) lexsort — the same order as the single-stage stable argsort, so the
two-stage result is bit-identical whenever quota >= k (always at
``candidate_frac=1.0``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import control, selection

# the (num_clients,)-shaped ControlState fields the kernels shard; the
# error-feedback arena ``ef`` is cohort-indexed (N+1 dummy-row layout of
# its own) and stays outside the population kernels.
_FIELDS = ("avail", "pass_rate", "round_time", "batch", "lr_scale",
           "grad_norm", "staleness", "has_ckpt")


# ---------------------------------------------------------------------------
# single-device reference: the full per-round control update
# ---------------------------------------------------------------------------

def round_update(state, cohort, *, failed, active, passed, round_time,
                 sent, norms, ema: float = 0.8):
    """The canonical per-round control-plane composition the sharded
    kernels are pinned against: two-phase observation (dropouts first,
    then participants — core/megastep.py's order), batch feedback, norm
    EMAs, LR meta-rule, staleness counters, checkpoint bits."""
    state = control.observe_round(state, cohort, failed, active, passed,
                                  round_time, ema)
    state = control.batch_feedback(state, cohort, round_time, active)
    state = control.grad_norm_update(state, cohort, norms, active)
    state = control.lr_scale_update(state, cohort, norms, active)
    state = control.staleness_update(state, cohort, sent)
    state = control.checkpoint_update(state, cohort, active)
    return state


# ---------------------------------------------------------------------------
# the shard-local kernel
# ---------------------------------------------------------------------------

def _round_kernel(leaves, cohort, failed, active, passed, round_time,
                  sent, norms, offset, ema):
    """One shard's slice of ``round_update``.

    ``leaves``: the 8 per-client arrays (local slices, length per);
    observations are the full replicated (K,) cohort stream; ``offset``
    is the shard's first global client id. Gathers clip non-owned ids to
    a safe local index (their values are garbage but masked out of the
    scatter); scatters append one dummy row, write non-owned slots
    there, and slice it off."""
    avail, pass_rate, rtime, batch, lr_scale, grad_norm, \
        staleness, has_ckpt = leaves
    local_n = avail.shape[0]
    rel = cohort - offset
    owned = (rel >= 0) & (rel < local_n)
    safe = jnp.clip(rel, 0, local_n - 1)
    idx = jnp.where(owned, safe, local_n)

    def scat(arr, vals):
        ext = jnp.concatenate([arr, jnp.zeros((1,), arr.dtype)])
        return ext.at[idx].set(vals.astype(arr.dtype))[:local_n]

    # observe_round, phase 1: every dropout observed delivered=False
    false = jnp.zeros_like(failed)
    a1, p1, t1 = control.observe_ema(
        avail[safe], pass_rate[safe], rtime[safe],
        failed, false, false, round_time, ema)
    avail, pass_rate, rtime = scat(avail, a1), scat(pass_rate, p1), \
        scat(rtime, t1)
    # phase 2: every participant observed delivered=True (gathers read
    # the POST-phase-1 values, exactly like the chained global observes)
    a2, p2, t2 = control.observe_ema(
        avail[safe], pass_rate[safe], rtime[safe],
        active, active, passed, round_time, ema)
    avail, pass_rate, rtime = scat(avail, a2), scat(pass_rate, p2), \
        scat(rtime, t2)
    # batch feedback — the median comes from the replicated cohort
    # observations, so every shard computes the identical threshold
    batch = scat(batch, control.batch_rule(batch[safe], round_time,
                                           active))
    g = grad_norm[safe]
    grad_norm = scat(grad_norm, jnp.where(active, 0.5 * g + 0.5 * norms,
                                          g))
    s = lr_scale[safe]
    lr_scale = scat(lr_scale, jnp.where(
        active, jnp.clip(s * jnp.where(norms < 1.0, 1.05, 0.9),
                         0.25, 2.0), s))
    stale = staleness + 1
    staleness = scat(stale, jnp.where(sent, 0, stale[safe]))
    has_ckpt = scat(has_ckpt, has_ckpt[safe] | active)
    return (avail, pass_rate, rtime, batch, lr_scale, grad_norm,
            staleness, has_ckpt)


def _pad_leaf(arr, padded: int):
    """Zero-extend a (n,) population leaf to ``padded`` rows. Pad rows
    are inert in ``_round_kernel``: cohort ids are < n so no gather or
    scatter ever selects them (every row transition is row-local — the
    only cross-row statistic, the batch-rule median, comes from the
    replicated cohort observations), and they are sliced off after."""
    n = arr.shape[0]
    if padded == n:
        return arr
    return jnp.concatenate([arr, jnp.zeros((padded - n,), arr.dtype)])


def _split_state(state, shards: int):
    """(leaves viewed as (shards, per), per) — ragged populations are
    zero-padded up to the next multiple of ``shards``."""
    n = state.avail.shape[0]
    per = -(-n // shards)           # ceil: pad instead of raising
    padded = per * shards
    return tuple(_pad_leaf(getattr(state, f), padded).reshape(shards, per)
                 for f in _FIELDS), per


def round_update_logical(state, cohort, *, shards: int, failed, active,
                         passed, round_time, sent, norms,
                         ema: float = 0.8):
    """Single-device logical-shard driver: vmap ``_round_kernel`` over
    ``shards`` contiguous slices. Bit-identical to ``round_update`` —
    the parity suite (tests/test_population.py) pins exactly this.
    Populations that don't divide ``shards`` are zero-padded to the
    next multiple (masked dummy rows, sliced off) — same bits as the
    unsharded update either way."""
    leaves, per = _split_state(state, int(shards))
    offsets = (jnp.arange(int(shards)) * per).astype(cohort.dtype)
    out = jax.vmap(
        lambda lv, off: _round_kernel(lv, cohort, failed, active, passed,
                                      round_time, sent, norms, off, ema),
        in_axes=(0, 0))(leaves, offsets)
    n = state.avail.shape[0]
    return state._replace(**{f: o.reshape((-1,))[:n]
                             for f, o in zip(_FIELDS, out)})


def round_update_sharded(state, cohort, *, mesh, failed, active, passed,
                         round_time, sent, norms, ema: float = 0.8):
    """The real thing: state sharded over mesh "data" via ``shard_map``,
    cohort observations replicated. Same kernel, same bits. Ragged
    populations (n % devices != 0) are zero-padded to the next multiple
    of the "data" axis with inert dummy rows and sliced back — bitwise
    parity with ``round_update`` holds either way."""
    nshards = mesh.shape["data"]
    n = state.avail.shape[0]
    per = -(-n // nshards)
    padded = per * nshards
    leaves = tuple(_pad_leaf(getattr(state, f), padded) for f in _FIELDS)
    rep = P()

    def body(lv, cohort, failed, active, passed, round_time, sent, norms):
        off = (jax.lax.axis_index("data") * per).astype(cohort.dtype)
        return _round_kernel(lv, cohort, failed, active, passed,
                             round_time, sent, norms, off, ema)

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=((P("data"),) * len(_FIELDS),
                  rep, rep, rep, rep, rep, rep, rep),
        out_specs=(P("data"),) * len(_FIELDS),
        check_vma=False)(leaves, cohort, failed, active, passed,
                         round_time, sent, norms)
    return state._replace(**{f: o[:n] for f, o in zip(_FIELDS, out)})


# ---------------------------------------------------------------------------
# two-stage selection over the sharded population
# ---------------------------------------------------------------------------

def sharded_candidates(scores: jnp.ndarray, k: int, frac: float, *,
                       mesh):
    """Stage 1 under ``shard_map``: each "data" shard ranks ONLY its own
    rows (``lax.top_k``, quota per ``selection.candidate_quota``) and
    emits (quota,) winners as (score, global id). Returns the
    (shards·quota,) concatenated union — tiny next to N, and the only
    cross-shard traffic selection needs."""
    n = scores.shape[0]
    nshards = mesh.shape["data"]
    per = -(-n // nshards)
    quota = selection.candidate_quota(n, k, frac, nshards)
    pad = per * nshards - n
    if pad:
        # ragged population: -inf pad rows lose every ranking, and the
        # quota already budgets for quota-displacing padding positions
        # (selection.candidate_quota), so the union still holds >= k
        # real clients
        scores = jnp.concatenate(
            [scores, jnp.full((pad,), -jnp.inf, scores.dtype)])

    def local(s):
        v, i = jax.lax.top_k(s, quota)
        gid = i.astype(jnp.int32) + jax.lax.axis_index("data") * per
        return v, gid

    return jax.shard_map(local, mesh=mesh, in_specs=P("data"),
                      out_specs=(P("data"), P("data")),
                      check_vma=False)(scores)


def logical_candidates(scores: jnp.ndarray, k: int, frac: float,
                       shards: int):
    """Single-device twin of ``sharded_candidates`` (same union, same
    order) — lets the scaling benchmark time the two-stage arithmetic
    independently of host device count."""
    n = scores.shape[0]
    shards = int(shards)
    per = -(-n // shards)
    quota = selection.candidate_quota(n, k, frac, shards)
    pad = per * shards - n
    if pad:
        scores = jnp.concatenate(
            [scores, jnp.full((pad,), -jnp.inf, scores.dtype)])
    v, i = jax.lax.top_k(scores.reshape(shards, per), quota)
    gid = i.astype(jnp.int32) + (jnp.arange(shards, dtype=jnp.int32)
                                 * per)[:, None]
    return v.reshape(-1), gid.reshape(-1)


def topk_from_candidates(cand_scores: jnp.ndarray,
                         cand_idx: jnp.ndarray, k: int) -> jnp.ndarray:
    """Stage 2: exact top-k over the union, ordered (score desc, global
    id asc). ``jnp.lexsort`` sorts by its LAST key first, so ties break
    toward the lower global id — the same order as the single-stage
    stable descending argsort, hence bit-identical selections whenever
    every global top-k member is in the union (quota >= k)."""
    order = jnp.lexsort((cand_idx, -cand_scores))
    return cand_idx[order[:int(k)]]


# ---------------------------------------------------------------------------
# population-only round (the scaling benchmark's unit of work)
# ---------------------------------------------------------------------------

def build_population_round(num_clients: int, select_k: int, *,
                           candidate_frac: Optional[float] = None,
                           candidate_shards: int = 8,
                           mesh=None, ema: float = 0.8, seed: int = 0):
    """Score → (two-stage) selection → synthetic cohort observations →
    full control round update; training deliberately absent. This
    isolates the selection+control cost per round — the quantity
    ``BENCH_scale.json`` tracks from 1k to 1M clients. Observations are
    folded from the ABSOLUTE round index, so the stream is independent
    of how rounds are grouped into dispatches.

    With ``mesh`` the state transitions run under ``shard_map`` and
    stage 1 ranks per-device rows; without, logical shards on one
    device. Returns ``round_fn(state, round_idx) -> (state, cohort)``
    (scan-compatible)."""
    n, k = int(num_clients), int(select_k)
    base = jax.random.PRNGKey(seed)

    def round_fn(state, r):
        scores = control.score(state)
        if candidate_frac is not None:
            if mesh is not None:
                v, i = sharded_candidates(scores, k, candidate_frac,
                                          mesh=mesh)
            else:
                v, i = logical_candidates(scores, k, candidate_frac,
                                          candidate_shards)
            cohort = topk_from_candidates(v, i, k)
        else:
            cohort = control.select_topk_epsilon(scores, k)
        key = jax.random.fold_in(base, r)
        kf, kp, kt, kn = jax.random.split(key, 4)
        failed = jax.random.bernoulli(kf, 0.05, (k,))
        active = ~failed
        passed = jax.random.bernoulli(kp, 0.9, (k,)) & active
        rt = jax.random.uniform(kt, (k,), jnp.float32, 0.5, 1.5)
        norms = jax.random.uniform(kn, (k,), jnp.float32, 0.1, 2.0)
        kwargs = dict(failed=failed, active=active, passed=passed,
                      round_time=rt, sent=active, norms=norms, ema=ema)
        if mesh is not None:
            state = round_update_sharded(state, cohort, mesh=mesh,
                                         **kwargs)
        else:
            state = round_update(state, cohort, **kwargs)
        return state, cohort

    return round_fn
