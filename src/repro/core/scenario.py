"""Dynamic-world scenario engine: the non-stationarity the control plane
was built for (paper §V "varying client conditions"; companion works
arXiv:2501.15038 / arXiv:2502.00036 motivate selection by churn and
shifting client quality).

Every world the simulators ran before this module was frozen at round 0:
profiles, partitions and link quality never changed, so adaptive
selection (§V-C), dynamic batch feedback (§IV-A) and staleness-aware
aggregation (§IV-C) were never exercised against the conditions they
exist to absorb. A :class:`ScenarioSpec` composes per-round world
transitions:

  drift      — label-conditional feature shift: x ← x + amp(t)·dir[y]
               with a fixed per-class direction matrix, amplitude on a
               linear or sinusoidal schedule (concept drift over the
               synthetic UNSW/ROAD surrogates in data/synthetic.py);
  churn      — join/leave masks: a rotating block of clients is offline
               each membership phase (deterministic, so every execution
               path sees the identical federation roster);
  links      — link-quality dynamics: per-client multiplicative
               lognormal walks on bandwidth and latency, re-pricing
               every CommModel byte (flaky networks, Fig. 2 regime);
  dropout    — failure-rate regime switches: a piecewise-constant
               multiplier on every profile's dropout probability;
  byzantine  — adversarial clients whose updates are scaled and/or
               sign-flipped before transmission — exactly the updates
               the θ sign-alignment filter (§IV-C) should reject.

The world lives in a :class:`WorldState` of device arrays with pure-jnp
transitions (:func:`world_step`), mirroring ``core/control.py``'s
``ControlState`` design: the SAME transition function runs eagerly in
the host loop/megastep paths, inside the ``lax.scan`` of
``core/megastep.build_scanned_rounds`` (the world joins the scan carry),
and inside the compiled spmd ``fl_step`` (the world rides in
``FLState``), so all execution paths traverse bit-identical world
trajectories. Randomized transitions (the link walks) fold a JAX key
from the absolute round index, making them independent of dispatch
grouping — ``rounds_per_dispatch=4`` replays ``=1`` exactly — and the
state serializes through ``ExperimentSession.checkpoint()/restore()``.
"""
from __future__ import annotations

import dataclasses
from typing import List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

DRIFT_MODES = ("linear", "sine")


# ---------------------------------------------------------------------------
# component specs (all pure data, all frozen)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DriftSpec:
    """Label-conditional concept drift: x ← x + amp(round)·dir[y].

    ``dir`` is a fixed (num_classes, num_features) matrix drawn once
    from ``seed`` (unit-ish rows), so the drift moves each class's
    feature cloud along its own direction — the class-conditional shift
    that degrades a frozen detector but not an adapting one. ``linear``
    grows amp by ``rate`` per round up to ``max_amp``; ``sine`` cycles
    0 → max_amp → 0 with the given ``period``. Round 0 has amp 0, so a
    drift world is indistinguishable from a static one at round 0.
    Training batches drift; the eval split stays at the round-0
    distribution (accuracy measures the original task).
    """
    rate: float = 0.05
    max_amp: float = 1.0
    mode: str = "linear"          # linear | sine
    period: int = 16              # sine mode: rounds per full cycle
    seed: int = 0

    def issues(self, prefix="scenario.drift") -> List[Tuple[str, object, str]]:
        out = []
        if self.mode not in DRIFT_MODES:
            out.append((f"{prefix}.mode", self.mode,
                        f"expected one of {DRIFT_MODES}"))
        if self.rate < 0:
            out.append((f"{prefix}.rate", self.rate, "rate must be >= 0"))
        if self.max_amp <= 0:
            out.append((f"{prefix}.max_amp", self.max_amp,
                        "max_amp must be > 0"))
        if self.period < 1:
            out.append((f"{prefix}.period", self.period,
                        "period must be >= 1"))
        return out


@dataclasses.dataclass(frozen=True)
class ChurnSpec:
    """Join/leave membership: every ``period`` rounds the offline block
    of ``round(leave_frac·N)`` clients rotates to the next position, so
    clients keep joining and leaving but the live count stays constant
    (the mask-conservation invariant the differential harness checks).
    Deterministic by construction — no draws — so the host loop, the
    scanned control plane and the spmd path agree on the roster bit-
    for-bit. ``seed`` offsets the rotation start."""
    period: int = 4
    leave_frac: float = 0.25
    seed: int = 0

    def issues(self, prefix="scenario.churn") -> List[Tuple[str, object, str]]:
        out = []
        if self.period < 1:
            out.append((f"{prefix}.period", self.period,
                        "period must be >= 1"))
        if not (0.0 <= self.leave_frac < 1.0):
            out.append((f"{prefix}.leave_frac", self.leave_frac,
                        "leave_frac must be in [0, 1) — at least one "
                        "client must stay live"))
        return out


@dataclasses.dataclass(frozen=True)
class LinkSpec:
    """Per-client link-quality walks: bandwidth and latency scales take
    multiplicative lognormal steps each round, clipped to
    [1/clip, clip]. Transfer time is re-priced every round as
    ``latency·lat_scale + bytes/(bandwidth·bw_scale)`` — the flaky-link
    regime that makes reliability-scored selection earn its keep. The
    steps draw from a key folded with the absolute round index, so the
    walk is identical on every execution path and at any
    rounds_per_dispatch grouping."""
    bw_sigma: float = 0.25
    lat_sigma: float = 0.25
    clip: float = 4.0
    seed: int = 0

    def issues(self, prefix="scenario.links") -> List[Tuple[str, object, str]]:
        out = []
        if self.bw_sigma < 0:
            out.append((f"{prefix}.bw_sigma", self.bw_sigma,
                        "bw_sigma must be >= 0"))
        if self.lat_sigma < 0:
            out.append((f"{prefix}.lat_sigma", self.lat_sigma,
                        "lat_sigma must be >= 0"))
        if self.clip <= 1.0:
            out.append((f"{prefix}.clip", self.clip, "clip must be > 1"))
        return out


@dataclasses.dataclass(frozen=True)
class DropoutSchedule:
    """Failure-rate regime switches: a piecewise-constant multiplier on
    every profile's dropout_p. ``scales[i]`` applies from round
    ``boundaries[i-1]`` (inclusive) to ``boundaries[i]`` (exclusive);
    ``scales[0]`` applies before the first boundary."""
    boundaries: Tuple[int, ...] = (8,)
    scales: Tuple[float, ...] = (1.0, 3.0)

    def issues(self, prefix="scenario.dropout") -> List[Tuple[str, object, str]]:
        out = []
        if len(self.scales) != len(self.boundaries) + 1:
            out.append((f"{prefix}.scales", self.scales,
                        f"need len(boundaries)+1 = "
                        f"{len(self.boundaries) + 1} scales"))
        if any(b2 <= b1 for b1, b2 in zip(self.boundaries,
                                          self.boundaries[1:])):
            out.append((f"{prefix}.boundaries", self.boundaries,
                        "boundaries must be strictly increasing"))
        if any(s < 0 for s in self.scales):
            out.append((f"{prefix}.scales", self.scales,
                        "scales must be >= 0"))
        return out


@dataclasses.dataclass(frozen=True)
class ByzantineSpec:
    """Adversarial clients: the FIRST ``n_byz`` client ids transmit
    updates multiplied by ``-scale`` (sign_flip) or ``+scale``. A
    sign-flipped update's alignment ratio against the reference
    direction collapses, so the θ-filter (§IV-C) rejects it at the
    source — the property the differential harness asserts."""
    n_byz: int = 1
    scale: float = 2.0
    sign_flip: bool = True

    def issues(self, prefix="scenario.byzantine") -> List[Tuple[str, object, str]]:
        out = []
        if self.n_byz < 0:
            out.append((f"{prefix}.n_byz", self.n_byz,
                        "n_byz must be >= 0"))
        if self.scale <= 0:
            out.append((f"{prefix}.scale", self.scale,
                        "scale must be > 0 (sign_flip controls direction)"))
        return out


@dataclasses.dataclass(frozen=True)
class ScenarioSpec:
    """Composition of per-round world transitions; all-None == static."""
    drift: Optional[DriftSpec] = None
    churn: Optional[ChurnSpec] = None
    links: Optional[LinkSpec] = None
    dropout: Optional[DropoutSchedule] = None
    byzantine: Optional[ByzantineSpec] = None

    def active(self) -> bool:
        return any((self.drift, self.churn, self.links, self.dropout,
                    self.byzantine))

    def issues(self) -> List[Tuple[str, object, str]]:
        out: List[Tuple[str, object, str]] = []
        for comp in (self.drift, self.churn, self.links, self.dropout,
                     self.byzantine):
            if comp is not None:
                out.extend(comp.issues())
        return out

    def validate(self) -> "ScenarioSpec":
        issues = self.issues()
        if issues:
            raise ValueError(
                "invalid ScenarioSpec: "
                + "; ".join(f"{f}={v!r}: {h}" for f, v, h in issues))
        return self


# ---------------------------------------------------------------------------
# presets (the differential-harness matrix columns)
# ---------------------------------------------------------------------------

SCENARIO_PRESETS = {
    "static": ScenarioSpec(),
    "drift": ScenarioSpec(drift=DriftSpec(rate=0.08, max_amp=1.2)),
    "churn": ScenarioSpec(churn=ChurnSpec(period=2, leave_frac=0.25)),
    "flaky-links": ScenarioSpec(
        links=LinkSpec(bw_sigma=0.35, lat_sigma=0.35),
        dropout=DropoutSchedule(boundaries=(4,), scales=(1.0, 2.5))),
    "byzantine": ScenarioSpec(
        byzantine=ByzantineSpec(n_byz=1, scale=2.0, sign_flip=True)),
    "churn+flaky-links": ScenarioSpec(
        churn=ChurnSpec(period=2, leave_frac=0.25),
        links=LinkSpec(bw_sigma=0.35, lat_sigma=0.35),
        dropout=DropoutSchedule(boundaries=(4,), scales=(1.0, 2.5))),
    "dynamic": ScenarioSpec(
        drift=DriftSpec(rate=0.05, max_amp=1.0),
        churn=ChurnSpec(period=3, leave_frac=0.25),
        links=LinkSpec(bw_sigma=0.25, lat_sigma=0.25),
        dropout=DropoutSchedule(boundaries=(8,), scales=(1.0, 2.0))),
}


def resolve_scenario(scenario) -> Optional[ScenarioSpec]:
    """None | preset name | ScenarioSpec -> validated ScenarioSpec or
    None (inactive scenarios normalize to None)."""
    if scenario is None:
        return None
    if isinstance(scenario, str):
        if scenario not in SCENARIO_PRESETS:
            raise ValueError(
                f"unknown scenario preset {scenario!r}; expected one of "
                f"{sorted(SCENARIO_PRESETS)} or a ScenarioSpec")
        scenario = SCENARIO_PRESETS[scenario]
    if not isinstance(scenario, ScenarioSpec):
        raise ValueError(f"cannot resolve scenario from {type(scenario)}; "
                         "expected None, a preset name or a ScenarioSpec")
    return scenario if scenario.active() else None


def is_active(scenario) -> bool:
    return scenario is not None and scenario.active()


# ---------------------------------------------------------------------------
# WorldState + pure-jnp transitions
# ---------------------------------------------------------------------------

class WorldState(NamedTuple):
    """Per-round world, all device-resident (the scenario twin of
    ``control.ControlState``). ``(N,)``-shaped per-client fields plus
    two scalars; an INACTIVE scenario uses the 0-width placeholder from
    :func:`empty_world` so the scan carry keeps one structure."""
    live: jnp.ndarray           # (N,) bool — churn membership
    bw_scale: jnp.ndarray       # (N,) f32 — bandwidth multiplier walk
    lat_scale: jnp.ndarray      # (N,) f32 — latency multiplier walk
    drift_amp: jnp.ndarray      # f32 scalar — current drift amplitude
    dropout_scale: jnp.ndarray  # f32 scalar — failure-regime multiplier
    byz_factor: jnp.ndarray     # (N,) f32 — update multiplier (1 honest)


def empty_world() -> WorldState:
    """Structure-compatible placeholder for static worlds (0-width).
    Every field is its own buffer, so the whole state can be donated."""
    z = lambda: jnp.zeros((0,), jnp.float32)  # noqa: E731
    s = lambda: jnp.zeros((), jnp.float32)  # noqa: E731
    return WorldState(live=jnp.zeros((0,), bool), bw_scale=z(),
                      lat_scale=z(), drift_amp=s(), dropout_scale=s(),
                      byz_factor=z())


def _byz_factor(scn: ScenarioSpec, n: int) -> jnp.ndarray:
    if scn.byzantine is None or scn.byzantine.n_byz == 0:
        return jnp.ones((n,), jnp.float32)
    b = scn.byzantine
    f = jnp.float32((-b.scale) if b.sign_flip else b.scale)
    return jnp.where(jnp.arange(n) < b.n_byz, f, jnp.float32(1.0))


def init_world(scn: Optional[ScenarioSpec], num_clients: int) -> WorldState:
    """The pre-round-0 world: everyone live, neutral scales, amp 0."""
    if not is_active(scn):
        return empty_world()
    n = int(num_clients)
    scale0 = (scn.dropout.scales[0] if scn.dropout is not None else 1.0)
    return WorldState(
        live=jnp.ones((n,), bool), bw_scale=jnp.ones((n,), jnp.float32),
        lat_scale=jnp.ones((n,), jnp.float32),
        drift_amp=jnp.float32(0.0), dropout_scale=jnp.float32(scale0),
        byz_factor=_byz_factor(scn, n))


def world_step(ws: WorldState, round_idx, scn: Optional[ScenarioSpec],
               num_clients: int) -> WorldState:
    """One round's world transition — pure jnp, safe inside jit/scan.

    ``round_idx`` is the ABSOLUTE round about to execute (traced i32 is
    fine); the returned state is the world THAT round runs under.
    Everything except the link walks is a closed-form function of
    ``round_idx``; the walks are recurrent but their steps fold a key
    from ``round_idx``, so trajectories never depend on how rounds are
    grouped into dispatches.
    """
    if not is_active(scn):
        return ws
    n = int(num_clients)
    r = jnp.asarray(round_idx, jnp.int32)

    live = ws.live
    if scn.churn is not None:
        c = scn.churn
        leave = min(int(round(c.leave_frac * n)), n - 1)
        if leave > 0:
            phase = r // jnp.int32(c.period)
            offset = (phase * jnp.int32(leave)
                      + jnp.int32(c.seed)) % jnp.int32(n)
            idx = jnp.arange(n, dtype=jnp.int32)
            live = ((idx - offset) % jnp.int32(n)) >= jnp.int32(leave)

    bw, lat = ws.bw_scale, ws.lat_scale
    if scn.links is not None:
        lk = scn.links
        key = jax.random.fold_in(jax.random.PRNGKey(lk.seed), r)
        kb, kl = jax.random.split(key)
        lo, hi = jnp.float32(1.0 / lk.clip), jnp.float32(lk.clip)
        bw = jnp.clip(bw * jnp.exp(jnp.float32(lk.bw_sigma)
                                   * jax.random.normal(kb, (n,))), lo, hi)
        lat = jnp.clip(lat * jnp.exp(jnp.float32(lk.lat_sigma)
                                     * jax.random.normal(kl, (n,))), lo, hi)

    amp = ws.drift_amp
    if scn.drift is not None:
        d = scn.drift
        if d.mode == "sine":
            amp = jnp.float32(d.max_amp) * 0.5 * (
                1.0 - jnp.cos(2.0 * jnp.pi * r.astype(jnp.float32)
                              / jnp.float32(d.period)))
        else:
            amp = jnp.minimum(jnp.float32(d.rate) * r.astype(jnp.float32),
                              jnp.float32(d.max_amp))

    scale = ws.dropout_scale
    if scn.dropout is not None and scn.dropout.boundaries:
        dp = scn.dropout
        regime = jnp.sum(
            (r >= jnp.asarray(dp.boundaries, jnp.int32)).astype(jnp.int32))
        scale = jnp.asarray(dp.scales, jnp.float32)[regime]

    return WorldState(live=live, bw_scale=bw, lat_scale=lat, drift_amp=amp,
                      dropout_scale=scale, byz_factor=ws.byz_factor)


# ---------------------------------------------------------------------------
# drift application (shared by every execution path)
# ---------------------------------------------------------------------------

def drift_directions(drift: DriftSpec, num_classes: int,
                     num_features: int) -> np.ndarray:
    """Fixed (num_classes, num_features) f32 per-class drift directions,
    unit-ish scale (||dir_c|| ≈ 1), drawn once from ``drift.seed``."""
    rng = np.random.default_rng(drift.seed)
    dirs = rng.normal(size=(num_classes, num_features))
    dirs /= np.sqrt(num_features)
    return dirs.astype(np.float32)


def apply_drift(batch: dict, amp, dirs, label_key: str = "y") -> dict:
    """x ← x + amp·dir[y], elementwise over any leading batch dims —
    bit-identical whether the batch is (B, F), (steps, B, F) or a
    stacked cohort (C, steps, B, F), so the host loop, megastep, scanned
    and spmd paths all drift the same samples the same way."""
    if "x" not in batch or label_key not in batch:
        raise ValueError("drift needs feature/label batches "
                         f"('x' + {label_key!r}); token datasets do not "
                         "support label-conditional feature drift")
    shift = jnp.asarray(amp, jnp.float32) * jnp.asarray(dirs)[batch[label_key]]
    return {**batch, "x": batch["x"] + shift}


# ---------------------------------------------------------------------------
# drift DETECTION (serving side): the same machinery, pointed the other way.
# The simulator above injects distribution shift; repro.serve's online
# monitor needs to *measure* it on live traffic. A DriftStats summary
# (per-feature mean/var + score-distribution mean/var) serves both as the
# training-time reference snapshot and as the streaming serving-time EMA
# state; drift_stats_update is pure jnp so the serving engine fuses it
# into the scoring dispatch (one jit per batch bucket, no extra dispatch).
# ---------------------------------------------------------------------------

class DriftStats(NamedTuple):
    """Distribution summary: feature moments + anomaly-score moments.

    ``count`` is the number of samples absorbed; a freshly initialized
    state (count 0) snaps to the first batch it sees, after which
    updates are exponential moving averages."""
    feat_mean: jnp.ndarray    # (F,) f32
    feat_var: jnp.ndarray     # (F,) f32
    score_mean: jnp.ndarray   # f32 scalar
    score_var: jnp.ndarray    # f32 scalar
    count: jnp.ndarray        # f32 scalar — samples absorbed


def init_drift_stats(num_features: int) -> DriftStats:
    z = jnp.zeros((num_features,), jnp.float32)
    s = jnp.zeros((), jnp.float32)
    return DriftStats(feat_mean=z, feat_var=jnp.ones_like(z),
                      score_mean=s, score_var=jnp.ones_like(s), count=s)


def reference_snapshot(x, scores) -> DriftStats:
    """Exact moments of a reference sample — the training-time snapshot
    the serving monitor compares live traffic against. ``x`` is (N, F)
    features, ``scores`` (N,) anomaly scores of the SAME samples under
    the model about to be served."""
    x = jnp.asarray(x, jnp.float32)
    s = jnp.asarray(scores, jnp.float32)
    return DriftStats(
        feat_mean=x.mean(0), feat_var=x.var(0),
        score_mean=s.mean(), score_var=s.var(),
        count=jnp.float32(x.shape[0]))


def drift_stats_update(stats: DriftStats, x, scores, mask=None,
                       decay: float = 0.98) -> DriftStats:
    """One streaming window's masked EMA update — pure jnp, safe inside
    jit (the serving engine fuses it into the scoring dispatch).

    ``mask`` flags the real rows of a padded batch bucket (None == all
    real). A batch absorbing ``m`` samples moves the EMA by
    ``1 - decay**m`` toward the batch moments, so the state trajectory
    does not depend on how a stream is chunked into buckets; an all-
    padding batch is a no-op and the FIRST real batch snaps the state."""
    x = jnp.asarray(x, jnp.float32)
    s = jnp.asarray(scores, jnp.float32)
    if mask is None:
        mask = jnp.ones(x.shape[:1], jnp.float32)
    mask = jnp.asarray(mask, jnp.float32)
    m = mask.sum()
    denom = jnp.maximum(m, 1.0)
    bm = (x * mask[:, None]).sum(0) / denom
    bv = (((x - bm) ** 2) * mask[:, None]).sum(0) / denom
    sm = (s * mask).sum() / denom
    sv = (((s - sm) ** 2) * mask).sum() / denom
    w = 1.0 - jnp.float32(decay) ** m
    w = jnp.where(m > 0, jnp.where(stats.count > 0, w, 1.0), 0.0)
    return DriftStats(
        feat_mean=stats.feat_mean + w * (bm - stats.feat_mean),
        feat_var=stats.feat_var + w * (bv - stats.feat_var),
        score_mean=stats.score_mean + w * (sm - stats.score_mean),
        score_var=stats.score_var + w * (sv - stats.score_var),
        count=stats.count + m)


def drift_statistic(stats: DriftStats, ref: DriftStats,
                    eps: float = 1e-6) -> jnp.ndarray:
    """Normalized shift of ``stats`` away from ``ref`` — 0 when the
    streaming moments match the reference, ~1 when feature means have
    moved one reference standard deviation on average (or the score
    distribution has moved equivalently). Pure jnp.

      feat term:  mean_f |mu_f - mu_ref,f| / sqrt(var_ref,f + eps)
      score term: |s - s_ref| / sqrt(svar_ref + eps)

    The max of the two is reported so either signal alone can trip the
    monitor (covariate shift without score shift, or vice versa)."""
    feat = jnp.mean(jnp.abs(stats.feat_mean - ref.feat_mean)
                    / jnp.sqrt(ref.feat_var + eps))
    score = (jnp.abs(stats.score_mean - ref.score_mean)
             / jnp.sqrt(ref.score_var + eps))
    return jnp.maximum(feat, score)


# ---------------------------------------------------------------------------
# host views (the event-driven engines read the SAME device trajectory)
# ---------------------------------------------------------------------------

def host_view(ws: WorldState) -> dict:
    """One device_get of the whole state as numpy (host-path reads)."""
    h = jax.device_get(ws)
    return {"live": np.asarray(h.live), "bw_scale": np.asarray(h.bw_scale),
            "lat_scale": np.asarray(h.lat_scale),
            "drift_amp": float(h.drift_amp),
            "dropout_scale": float(h.dropout_scale),
            "byz_factor": np.asarray(h.byz_factor)}


def replay(scn: Optional[ScenarioSpec], num_clients: int,
           rounds: int) -> List[dict]:
    """Host replay of the first ``rounds`` world states (one host_view
    per round) — the differential harness's oracle for invariants like
    churn mask conservation, independent of any engine."""
    out = []
    ws = init_world(scn, num_clients)
    for r in range(rounds):
        ws = world_step(ws, r, scn, num_clients)
        out.append(host_view(ws) if is_active(scn) else None)
    return out
