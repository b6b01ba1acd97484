"""Declarative experiment specification — the single public entry point.

An ``ExperimentSpec`` names everything a paper experiment varies (model,
data/partition, client world, communication model, strategy, engine,
rounds, seed) and ``run_experiment(spec)`` executes it on either engine:

  engine="sim"   — the event-driven heterogeneous-client simulator
                   (repro.core.async_engine.FederatedSimulation)
  engine="spmd"  — the compiled one-round-per-step SPMD path
                   (repro.core.fl_step), with the same CommModel applied
                   analytically for time/byte accounting

Both return the normalized ``ExperimentResult`` / ``RoundRecord`` schema,
so benchmark tables are spec sweeps instead of hand-wired setups.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Union

from repro.api import strategies as strategies_mod
from repro.api import world as world_mod
from repro.core.async_engine import CommModel, StrategyConfig
from repro.core.scenario import ScenarioSpec, resolve_scenario
from repro.core.schedule import ScheduleSpec, resolve_schedule
from repro.topology.spec import TopologySpec, resolve_topology

ENGINES = ("sim", "spmd")
DATASETS = ("auto", "unsw", "road", "lm")
PARTITIONS = ("dirichlet", "iid")
PROFILES = ("heterogeneous", "uniform")


@dataclasses.dataclass(frozen=True)
class SpecIssue:
    """One validation violation: the field, its offending value, a hint."""
    field: str
    value: Any
    hint: str

    def __str__(self):
        return f"{self.field}={self.value!r}: {self.hint}"


class SpecError(ValueError):
    """Raised by ``ExperimentSpec.validate()`` with EVERY violation at
    once (``.issues``), not just the first — a sweep over hundreds of
    generated specs should surface all problems in one round trip."""

    def __init__(self, issues: List[SpecIssue]):
        self.issues = list(issues)
        detail = "; ".join(str(i) for i in self.issues)
        super().__init__(
            f"invalid ExperimentSpec — {len(self.issues)} problem"
            f"{'s' if len(self.issues) != 1 else ''}: {detail}")


@dataclasses.dataclass
class DataSpec:
    dataset: str = "auto"             # auto | unsw | road | lm (auto infers
                                      # from the model config)
    n_samples: int = 20000
    eval_samples: int = 4000
    partition: str = "dirichlet"
    alpha: float = 0.5                # Dirichlet concentration (lower=skewed)
    seq_len: int = 128                # lm datasets only
    factory: Optional[Callable[[int, int], Any]] = None
    # factory(seed, n) -> (X, y) or {"x": ..., "y": ...} overrides `dataset`
    samples_per_client: Optional[int] = None
    # non-resident worlds only (WorldSpec.resident=False): each client's
    # shard is synthesized lazily at this fixed size, so `n_samples` (a
    # population-wide total) never has to be materialized


@dataclasses.dataclass
class WorldSpec:
    num_clients: int = 10
    profile: str = "heterogeneous"    # heterogeneous | uniform
    dropout_p: float = 0.0
    speed_sigma: float = 0.6          # lognormal speed spread (stragglers)
    profile_seed_offset: int = 1      # profiles seeded at seed + offset
    resident: bool = True             # False -> client shards are NOT
                                      # materialized up front: build_world
                                      # returns a LazyWorld that
                                      # synthesizes each selected client's
                                      # data on demand (host memory scales
                                      # with the cohort, not the
                                      # population; needs
                                      # data.samples_per_client)


@dataclasses.dataclass
class ExperimentSpec:
    model: Union[str, Any] = "anomaly-mlp"     # config name or ArchConfig
    data: DataSpec = dataclasses.field(default_factory=DataSpec)
    world: WorldSpec = dataclasses.field(default_factory=WorldSpec)
    comm: Optional[CommModel] = None           # None -> CommModel() defaults
    strategy: Union[str, StrategyConfig, Any] = "ours"
    strategy_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    schedule: Union[str, ScheduleSpec, None] = None
    # the server-coordination axis (core/schedule.py): None derives the
    # schedule from the strategy's legacy ``mode`` field (the shim that
    # keeps every preset working); "sync" | "async" | "semi-async" or a
    # full ScheduleSpec overrides it — e.g. fedavg under an async quorum,
    # or "ours" with a bounded-staleness semi-async server
    scenario: Union[str, ScenarioSpec, None] = None
    # the dynamic-world axis (core/scenario.py): None -> the world stays
    # frozen at round 0 (the historical behavior); a preset name
    # ("drift", "churn", "flaky-links", "byzantine", ...) or a full
    # ScenarioSpec composes per-round transitions — concept drift, client
    # churn, link-quality walks, dropout regime switches, byzantine
    # updates — identically on every execution path of both engines
    topology: Union[str, TopologySpec, None] = None
    # the hierarchical-federation axis (repro.topology): None (or a
    # single-tier spec, which normalizes to None) -> today's flat star,
    # bit-identically; a preset name ("edge-region-global",
    # "two-tier-pods") or a full TopologySpec attaches an
    # accumulate-and-sync tier tree — leaf pods accumulate their
    # clients' weighted deltas every round, tier boundaries sync upward
    # on their cadence with per-tier θ vetoes, and inter-tier bytes are
    # priced per tier link — on every execution path of both engines
    engine: str = "sim"
    rounds: int = 5
    seed: int = 0
    eval_every: int = 1                        # evaluate every k-th round
                                               # (+ the final round); >1
                                               # skips the eval dispatch on
                                               # off-rounds of long runs
    megastep: bool = True                      # sim engine: one compiled
                                               # cohort dispatch per round
                                               # (False -> the reference
                                               # per-client loop)
    rounds_per_dispatch: Optional[int] = None  # sim engine: device-resident
                                               # control plane — R rounds of
                                               # {select, train, θ-filter,
                                               # aggregate, control update}
                                               # per compiled lax.scan
                                               # dispatch (core/control.py).
                                               # None -> host control plane
                                               # (the pinned reference paths)
    fused_eval: bool = False                   # sim engine, scanned path:
                                               # evaluation joins the
                                               # lax.scan carry (eval_every
                                               # cadence inside the scan, no
                                               # per-dispatch host readback)
                                               # — needs rounds_per_dispatch
                                               # and the default eval
    eval_fn: Optional[Callable] = None         # custom eval(params, batch)
    lr_schedule: Optional[Callable] = None     # spmd engine only
    candidate_frac: Optional[float] = None     # two-stage selection: each
                                               # of `candidate_shards`
                                               # logical population shards
                                               # pre-filters its top
                                               # ceil(frac·shard) scores
                                               # and only the union feeds
                                               # the exact masked top-k.
                                               # None -> legacy single-
                                               # stage; 1.0 is bit-
                                               # identical to it on every
                                               # execution path
    candidate_shards: int = 8                  # logical shards of the
                                               # stage-1 pre-filter (the
                                               # mesh "data" axis at scale)
    optimizer: Union[str, Any, None] = None    # spmd engine only:
                                               # None -> per-round SGD (the
                                               # sim's semantics); or
                                               # "sgd"|"adamw"|"adafactor"
                                               # or a prebuilt Optimizer

    # ------------------------------------------------------------------
    # resolution helpers
    # ------------------------------------------------------------------
    def resolve_model(self):
        if not isinstance(self.model, str):
            return self.model                  # already an ArchConfig
        from repro.configs import anomaly_mlp, moonlight_16b_a3b
        named = {"anomaly-mlp": anomaly_mlp.CONFIG,
                 "anomaly-mlp-road": anomaly_mlp.ROAD_CONFIG,
                 "anomaly-mlp-smoke": anomaly_mlp.SMOKE,
                 "moonlight-16b-a3b-ep8-smoke": moonlight_16b_a3b.SMOKE}
        if self.model in named:
            return named[self.model]
        from repro.configs import registry
        return registry.get_config(self.model)

    def resolve_strategy(self) -> StrategyConfig:
        return strategies_mod.resolve_strategy(self.strategy,
                                               **self.strategy_kwargs)

    def resolve_schedule(self) -> ScheduleSpec:
        return resolve_schedule(self.schedule, self.resolve_strategy())

    def resolve_comm(self) -> CommModel:
        return self.comm or CommModel()

    def resolve_scenario(self) -> Optional[ScenarioSpec]:
        return resolve_scenario(self.scenario)

    def resolve_topology(self) -> Optional[TopologySpec]:
        return resolve_topology(self.topology)

    def strategy_name(self) -> str:
        if isinstance(self.strategy, str):
            return self.strategy
        return getattr(self.strategy, "name", "<custom>")

    def build_world(self) -> world_mod.World:
        return world_mod.build_world(self)

    # ------------------------------------------------------------------
    # validation
    # ------------------------------------------------------------------
    def validate(self) -> "ExperimentSpec":
        """Raise :class:`SpecError` listing EVERY violation (field name,
        offending value, hint) — not just the first one found."""
        issues: List[SpecIssue] = []
        if self.engine not in ENGINES:
            issues.append(SpecIssue(
                "engine", self.engine,
                f"unknown engine; expected one of {ENGINES}"))
        if self.rounds < 1:
            issues.append(SpecIssue("rounds", self.rounds,
                                    "rounds must be >= 1"))
        if self.eval_every < 1:
            issues.append(SpecIssue("eval_every", self.eval_every,
                                    "eval_every must be >= 1"))
        if self.rounds_per_dispatch is not None:
            if self.rounds_per_dispatch < 1:
                issues.append(SpecIssue(
                    "rounds_per_dispatch", self.rounds_per_dispatch,
                    "rounds_per_dispatch must be >= 1"))
            if self.engine != "sim":
                issues.append(SpecIssue(
                    "rounds_per_dispatch", self.rounds_per_dispatch,
                    "rounds_per_dispatch is a sim-engine knob (the spmd "
                    "step is already one compiled round)"))
            if not self.megastep:
                issues.append(SpecIssue(
                    "megastep", self.megastep,
                    "rounds_per_dispatch requires megastep=True (the "
                    "scanned path runs on the parameter arena)"))
        if self.fused_eval:
            if self.rounds_per_dispatch is None:
                issues.append(SpecIssue(
                    "fused_eval", self.fused_eval,
                    "fused_eval folds evaluation into the scanned "
                    "lax.scan carry — set rounds_per_dispatch"))
            if self.engine != "sim":
                issues.append(SpecIssue(
                    "fused_eval", self.fused_eval,
                    "fused_eval is a sim-engine knob (the scanned "
                    "control plane)"))
            if self.eval_fn is not None:
                issues.append(SpecIssue(
                    "fused_eval", self.fused_eval,
                    "fused_eval traces evaluation inside the compiled "
                    "scan; custom eval_fn callables are not guaranteed "
                    "traceable — drop one of the two"))
        if self.world.num_clients < 1:
            issues.append(SpecIssue("world.num_clients",
                                    self.world.num_clients,
                                    "world.num_clients must be >= 1"))
        if self.candidate_frac is not None and not (
                0.0 < self.candidate_frac <= 1.0):
            issues.append(SpecIssue(
                "candidate_frac", self.candidate_frac,
                "candidate_frac must be in (0, 1] (1.0 reproduces "
                "single-stage selection bit-exactly; None disables the "
                "pre-filter)"))
        if self.candidate_shards < 1:
            issues.append(SpecIssue(
                "candidate_shards", self.candidate_shards,
                "candidate_shards must be >= 1"))
        if not self.world.resident:
            if self.data.samples_per_client is None:
                issues.append(SpecIssue(
                    "world.resident", self.world.resident,
                    "non-resident worlds need data.samples_per_client "
                    "(each client's shard is synthesized lazily at a "
                    "fixed size)"))
            elif self.data.samples_per_client < 1:
                issues.append(SpecIssue(
                    "data.samples_per_client", self.data.samples_per_client,
                    "samples_per_client must be >= 1"))
            if self.engine == "spmd":
                issues.append(SpecIssue(
                    "world.resident", self.world.resident,
                    "engine='spmd' stacks every client's batch into one "
                    "compiled step — non-resident data needs the sim "
                    "engine's cohort dispatch"))
            if self.rounds_per_dispatch is not None:
                issues.append(SpecIssue(
                    "world.resident", self.world.resident,
                    "the scanned control plane gathers client data "
                    "device-side, so the population must be resident — "
                    "drop rounds_per_dispatch for lazy worlds"))
            if self.data.factory is not None:
                issues.append(SpecIssue(
                    "data.factory", self.data.factory,
                    "non-resident worlds synthesize per-client shards "
                    "from the seeded generators; a whole-population "
                    "factory cannot be materialized lazily"))
        if self.data.dataset not in DATASETS and self.data.factory is None:
            issues.append(SpecIssue(
                "data.dataset", self.data.dataset,
                f"unknown dataset; expected one of {DATASETS} or a "
                "factory"))
        if self.data.partition not in PARTITIONS:
            issues.append(SpecIssue(
                "data.partition", self.data.partition,
                f"unknown partition; expected one of {PARTITIONS}"))
        if self.world.profile not in PROFILES:
            issues.append(SpecIssue(
                "world.profile", self.world.profile,
                f"unknown profile; expected one of {PROFILES}"))
        scenario = None
        try:
            scenario = self.resolve_scenario()
        except ValueError as e:
            issues.append(SpecIssue("scenario", self.scenario, str(e)))
        if scenario is not None:
            issues.extend(SpecIssue(f, v, h)
                          for f, v, h in scenario.issues())
            if scenario.drift is not None:
                issues.extend(self._validate_drift())
            if (scenario.byzantine is not None
                    and scenario.byzantine.n_byz >= self.world.num_clients):
                issues.append(SpecIssue(
                    "scenario.byzantine.n_byz", scenario.byzantine.n_byz,
                    f"needs at least one honest client (world has "
                    f"{self.world.num_clients}); the θ-filter has no "
                    "honest majority to form a reference otherwise"))
        topology = None
        try:
            topology = self.resolve_topology()
        except (ValueError, TypeError) as e:
            issues.append(SpecIssue("topology", self.topology, str(e)))
        if topology is not None:
            issues.extend(SpecIssue(f, v, h)
                          for f, v, h in topology.issues())
        strategy = schedule = None
        try:
            strategy = self.resolve_strategy()
        except (ValueError, TypeError) as e:
            issues.append(SpecIssue("strategy", self.strategy_name(),
                                    str(e)))
        if strategy is not None:
            try:
                schedule = self.resolve_schedule()
            except TypeError as e:
                issues.append(SpecIssue("schedule", self.schedule, str(e)))
        if schedule is not None:
            issues.extend(SpecIssue(f, v, h) for f, v, h
                          in schedule.issues())
            if self.engine == "spmd":
                issues.extend(self._validate_spmd(strategy, schedule))
        if issues:
            raise SpecError(issues)
        return self

    def _validate_drift(self) -> List[SpecIssue]:
        """Label-conditional feature drift needs feature/label batches —
        token (lm) datasets have no per-sample class direction."""
        if self.data.factory is not None:
            return []          # user factory: checked at batch time
        try:
            cfg = self.resolve_model()
        except Exception:
            return []          # model issues surface on their own
        if world_mod._dataset_kind(self.data, cfg) == "lm":
            return [SpecIssue(
                "scenario.drift", self.data.dataset,
                "label-conditional feature drift needs a feature/label "
                "dataset ('unsw'/'road'); token datasets are unsupported")]
        return []

    def _validate_spmd(self, st: StrategyConfig,
                       schedule: ScheduleSpec) -> List[SpecIssue]:
        """The compiled path is a synchronous cohort step. Selection,
        dropout, per-client LR scaling and quantized updates are all
        handled by the device-resident control plane as cohort MASKING
        (core/control.py routed through core/fl_step.py), so only knobs
        that genuinely need the event-driven simulator are rejected."""
        issues = []
        if not schedule.is_sync:
            issues.append(SpecIssue(
                "schedule.kind", schedule.kind,
                "engine='spmd' does not support asynchronous schedules — "
                "the quorum clock is event-driven (use engine='sim')"))
        if st.dynamic_batch:
            issues.append(SpecIssue(
                "strategy.dynamic_batch", st.dynamic_batch,
                "engine='spmd' does not support dynamic_batch (per-round "
                "shape changes would retrace the compiled step)"))
        return issues
