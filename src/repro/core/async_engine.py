"""Event-driven federated simulation engine (paper §IV-B, §V).

Reproduces the paper's experimental apparatus on CPU: N clients with
heterogeneous speed / network / dropout profiles train REAL models (jitted
JAX local steps on their non-IID shard); the server runs either

  sync  — barrier aggregation: the round completes when the SLOWEST
          selected client's update arrives (straggler effect, Fig. 2
          left); barrier idle time is tracked explicitly;
  async — continuous aggregation: updates are applied in completion-time
          order with staleness weighting α(τ)=α₀(1+τ)^-0.5; the round
          clock advances at a QUORUM of arrivals (default 50%), so fast
          clients never wait for stragglers (Fig. 2 right). Straggler
          updates are still applied, discounted by their staleness.

Composable strategy flags mirror the paper's ablations (Table III):
  theta            — gradient-sign-alignment client-side filter (§IV-C);
                     the reference direction is the sign of the LAST
                     GLOBAL UPDATE (w_g^t − w_g^{t−1}), per Algorithm 1
  selection        — adaptive top-k client selection from reliability EMAs
  dynamic_batch    — capacity-proportional batch assignment (§IV-A)
  checkpointing    — Weibull-interval checkpoint/restore on dropout (§IV-C)

Execution: by default each round's client work runs as ONE compiled
cohort megastep (core/megastep.py) — selected clients' fixed-shape
batches are stacked into (C, steps, B, ...) and a single jitted
vmap-of-scan returns per-client deltas (packed into the flat parameter
arena), losses, sign-alignment ratios and update norms; server
aggregation is one weighted arena sum (Pallas on TPU, jnp oracle on
CPU). Heterogeneous (steps, batch) shapes fall into a handful of
power-of-two groups, each one dispatch. ``megastep=False`` selects the
original per-client Python loop, kept as the seeded reference
implementation (tests/test_megastep.py pins the two trajectories to each
other). Timing and byte accounting stay event-driven in Python either
way, consuming the batched device results.

Simulated time model (recorded separately from real wall time):
  train_time  = steps · batch · t_sample / speed
  comm_time   = latency + bytes/bandwidth   (only if the update is SENT —
                filtered clients transmit a 1-bit "skip" beacon)
All stochastic choices draw from a seeded Generator → runs are exactly
reproducible; with equal speeds, zero latency, no dropout, full quorum and
theta=None, the async trajectory coincides with sync FedAvg (tested).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation, alignment, compression
from repro.core import control as control_mod
from repro.core import megastep as megastep_mod
from repro.core import records as records_mod
from repro.core import scenario as scenario_mod
from repro.core.batchsize import BatchSizeController, ClientMetrics
from repro.core.checkpoint_policy import fit_weibull, optimal_interval
from repro.core.schedule import ScheduleSpec
from repro.core.selection import AdaptiveClientSelector, candidate_mask_np
from repro.data.loader import ArrayLoader, LoaderPool
from repro.kernels import arena as arena_mod
from repro.models import api
from repro.obs import span
from repro.optim import adamw as optim_mod
from repro.topology import engine as topology_mod
from repro.topology.spec import resolve_topology


@dataclasses.dataclass
class CommModel:
    bandwidth: float = 1e9        # bytes/s client->server
    latency: float = 0.05         # s per message
    t_sample: float = 2e-6        # s of compute per training sample (ref speed)
    t_launch: float = 0.0         # fixed per-step dispatch overhead — the
                                  # paper's kernel-launch/memcpy cost that
                                  # large batches amortize (Tables V-VI)
    beacon_bytes: float = 0.125   # 1-bit "skip" beacon a θ-filtered client
                                  # still transmits (§IV-C); charged to both
                                  # bytes_sent and transfer time so the sim
                                  # and SPMD engines account identically


@dataclasses.dataclass
class ClientProfile:
    speed: float = 1.0            # relative compute throughput
    net_latency: float = 0.05
    dropout_p: float = 0.0
    memory: float = 1.0


@dataclasses.dataclass
class StrategyConfig:
    # mode / quorum / alpha0 are the LEGACY spelling of the server
    # schedule axis — engines consume a ScheduleSpec (core/schedule.py),
    # derived from these fields via ScheduleSpec.from_strategy when no
    # explicit schedule is given (see the CHANGES.md migration table)
    mode: str = "async"                   # async | sync
    theta: Optional[float] = 0.65         # None -> no filtering
    selection: bool = True
    select_fraction: float = 1.0          # top-k fraction when selecting
    dynamic_batch: bool = False
    checkpointing: bool = True
    local_epochs: int = 1
    batch_size: int = 64
    lr: float = 5e-3
    alpha0: float = 1.0                   # fresh-update weight in buffered
                                          # async aggregation: α(τ)=α₀(1+τ)^-½
                                          # discounts stale arrivals; τ=0 ->
                                          # exactly FedAvg over the senders.
                                          # (Sequential convex mixing with
                                          # α₀>0.2 chased the last arrival
                                          # and collapsed the θ-filter —
                                          # kept in EXPERIMENTS §Sim.)
    quorum: float = 0.5                   # async round advances at this frac
    per_client_lr: bool = False           # FedL2P-style personalization
    grad_norm_selection: bool = False     # ACFL-style critical-period proxy
    quantize_updates: bool = False        # beyond-paper §VI hybrid: int8 +
                                          # error feedback on the wire (4x
                                          # fewer bytes, multiplies with θ)
    max_samples_per_round: int = 4096     # per-round sample cap (NOT a step
                                          # cap: batch sizes then see equal
                                          # data, isolating the launch-
                                          # overhead effect the paper measures)


def local_step_count(n: int, batch_size: int, st: StrategyConfig) -> int:
    """Per-round local step count, quantized UP to powers of two.

    Heterogeneous client datasets otherwise produce a distinct
    (steps, batch) shape per client, and every distinct shape re-traces
    the jitted local scan — the dominant CPU cost at 100 clients.
    Power-of-two quantization caps the trace count at ~7 per batch size
    (and, on the megastep path, caps the number of cohort shape GROUPS —
    each group is one compiled dispatch per round).
    Shared with the spmd runner (repro.api) so both engines consume and
    account the same per-round sample volume.
    """
    cap = max(1, st.max_samples_per_round // batch_size)
    steps = max(1, math.ceil(st.local_epochs * n / batch_size))
    steps = min(steps, cap)
    steps = 1 << (steps - 1).bit_length()          # next power of two
    return min(steps, cap)


@dataclasses.dataclass
class RoundMetrics:
    round: int
    sim_time: float          # simulated end-to-end wall clock so far
    comm_time: float         # cumulative transfer seconds
    idle_time: float         # cumulative barrier-idle seconds (sync only)
    bytes_sent: float
    updates_applied: int
    accept_rate: float
    accuracy: float
    loss: float
    expert_rows: int = 0     # rows the cohort routed to held experts
                             # (scanned path; 0 for models without them)


class FederatedSimulation:
    def __init__(self, cfg, client_arrays: List[dict], eval_arrays: dict,
                 strategy: StrategyConfig, profiles: List[ClientProfile],
                 comm: CommModel = None, seed: int = 0,
                 eval_fn: Callable = None, eval_every: int = 1,
                 megastep: bool = True,
                 rounds_per_dispatch: Optional[int] = None,
                 schedule: Optional[ScheduleSpec] = None,
                 scenario: Optional[scenario_mod.ScenarioSpec] = None,
                 candidate_frac: Optional[float] = None,
                 candidate_shards: int = 8, topology=None,
                 fused_eval: bool = False):
        self.cfg = cfg
        self.strategy = strategy
        # schedule=None -> legacy StrategyConfig.mode shim
        self.schedule = (schedule if schedule is not None
                         else ScheduleSpec.from_strategy(strategy)).validate()
        self.comm = comm or CommModel()
        self.profiles = profiles
        self.rng = np.random.default_rng(seed)
        self.seed = seed
        self.num_clients = len(client_arrays)
        self.eval_arrays = eval_arrays
        # device-cache the eval batch ONCE (was re-transferred every round)
        self._eval_dev = jax.tree.map(jnp.asarray, eval_arrays)
        self.eval_every = max(1, int(eval_every))
        self.megastep = bool(megastep)
        # rounds_per_dispatch=None -> host control plane (per-round
        # megastep / reference loop); an int >= 1 -> the device-resident
        # control plane, R rounds per compiled dispatch (lax.scan)
        self.rounds_per_dispatch = (int(rounds_per_dispatch)
                                    if rounds_per_dispatch else None)
        if self.rounds_per_dispatch and not self.megastep:
            raise ValueError("rounds_per_dispatch requires megastep=True "
                             "(the scanned path runs on the parameter "
                             "arena)")
        # whole-experiment fusion: eval lives in the scan carry (no
        # per-dispatch host readback); needs the scanned path and the
        # default (traceable) eval — a custom eval_fn has no traceability
        # contract, so it keeps the host eval dispatch
        self.fused_eval = bool(fused_eval)
        if self.fused_eval and not self.rounds_per_dispatch:
            raise ValueError("fused_eval folds evaluation into the "
                             "scanned lax.scan carry — set "
                             "rounds_per_dispatch")
        if self.fused_eval and eval_fn is not None:
            raise ValueError("fused_eval traces the eval inside the "
                             "compiled scan; custom eval_fn callables "
                             "are not guaranteed traceable — drop one "
                             "of the two")
        # two-stage selection: None -> legacy single-stage; 1.0 is
        # bit-identical to it (all-True candidate mask) on every path
        self.candidate_frac = (None if candidate_frac is None
                               else float(candidate_frac))
        self.candidate_shards = max(1, int(candidate_shards))
        self._lazy_world = bool(getattr(client_arrays, "lazy", False))
        if self._lazy_world and self.rounds_per_dispatch:
            raise ValueError(
                "the scanned control plane gathers client data "
                "device-side, so the population must be resident — drop "
                "rounds_per_dispatch for lazy worlds")
        self.dispatches = 0           # compiled-call count (bench metric)
        # scanned dispatches enqueued while an earlier one's metrics were
        # still on their way back (_run_scanned's look-ahead)
        self.overlapped_dispatches = 0
        # rows routed to held experts over every scanned round (0 for
        # models without an expert layer)
        self.expert_rows = 0

        # --- dynamic-world scenario (core/scenario.py) --------------------
        # None / inactive -> the world stays frozen at round 0 and every
        # code path below is bit-identical to the pre-scenario engine
        self.scenario = scenario_mod.resolve_scenario(scenario)
        self._world_state = scenario_mod.init_world(self.scenario,
                                                    len(client_arrays))
        self._world_view = None       # this round's host view (or None)
        self._drift_dirs = None
        self._drift_label = None
        if self.scenario is not None and self.scenario.drift is not None:
            keys = set(client_arrays[0])
            if "x" not in keys or "y" not in keys:
                raise ValueError(
                    "scenario.drift needs feature/label client arrays "
                    f"('x' + 'y'); got {sorted(keys)}")
            self._drift_label = "y"
            self._drift_dirs = jnp.asarray(scenario_mod.drift_directions(
                self.scenario.drift, cfg.num_classes, cfg.num_features))

        # --- model/optim setup ------------------------------------------
        self._params_tree = api.init_params(jax.random.PRNGKey(seed), cfg)
        self.param_bytes = sum(x.size * x.dtype.itemsize
                               for x in jax.tree.leaves(self._params_tree))
        self.opt = optim_mod.sgd(lr=strategy.lr)
        self.ref_sign = None          # sign(w_g^t − w_g^{t−1}); None round 0
        self._local_run = self._build_local_run()
        self._eval = eval_fn or self._build_eval()

        # --- cohort megastep / parameter arena ----------------------------
        self._arena = arena_mod.ParamArena(self._params_tree)
        self._params_mat = None       # canonical device state when megastep
        self._ref_mat = None          # (rows, lane) int8, -2 padding
        self._ef_arena = None         # (N, rows, lane) batched EF buffers
        if self.megastep:
            self._params_mat = self._arena.pack(self._params_tree)
            self._cohort_step = megastep_mod.build_cohort_step(
                cfg, self.opt, self._arena, theta=strategy.theta,
                quantize=strategy.quantize_updates)
            self._apply_update = megastep_mod.build_apply_update(self._arena)
            self._unpack = jax.jit(self._arena.unpack)
            if strategy.quantize_updates:
                # +1 dummy row absorbs the EF residuals of cohort-width
                # padding rows (see _run_round_mega pass 3)
                self._ef_arena = compression.init_error_arena(
                    self.num_clients + 1, self._arena)

        # --- hierarchical topology (repro.topology) -----------------------
        # an accumulate-and-sync measurement layer over the flat round:
        # the training trajectory is untouched (None / single-tier is
        # bit-identical to today's path); the carry advances EVERY round
        # on every execution path so the absolute-round sync cadence is
        # independent of loop/mega/scanned grouping
        self.topology = resolve_topology(topology)
        self._topo = None
        self._topo_state = None
        if self.topology is not None:
            self._topo = topology_mod.TopologyRuntime(
                self.topology, self.num_clients, self._arena, self.comm)
            self._topo_state = self._topo.init()
            self._topo_step = jax.jit(self._topo.step)

        # --- per-client state --------------------------------------------
        self.batch_ctrl = BatchSizeController()

        def initial_bs(cid: int) -> int:
            bs = strategy.batch_size
            if strategy.dynamic_batch:
                p = profiles[cid]
                bs = self.batch_ctrl.initial(cid, ClientMetrics(
                    compute=p.speed, memory=p.memory,
                    latency=p.net_latency))
            return bs

        if self._lazy_world:
            # non-resident world: loaders (and the client shards behind
            # them) materialize per selected cohort, LRU-bounded — host
            # memory scales with cohort size, not population
            k = max(1, int(strategy.select_fraction * self.num_clients))
            self.loaders = LoaderPool(client_arrays, initial_bs,
                                      seed=seed,
                                      capacity=max(4 * k, 64))
        else:
            self.loaders = [ArrayLoader(arrays, initial_bs(cid),
                                        seed=seed + cid)
                            for cid, arrays in enumerate(client_arrays)]
        self.selector = AdaptiveClientSelector(self.num_clients, seed=seed)
        self.client_lr_scale = np.ones(self.num_clients)
        self.grad_norms = np.ones(self.num_clients)

        # --- fault tolerance ----------------------------------------------
        self.failure_log: List[float] = []
        self.checkpoints: Dict[int, bool] = {}
        self.ckpt_interval = 10.0
        self.recovery_time = 0.2      # restore from checkpoint
        self.restart_time = 1.0      # cold restart without one

        # --- compression (beyond-paper) -----------------------------------
        self._ef_state = {}
        self._wire_bytes = (compression.arena_wire_bytes(self._arena)
                            if (self.megastep and strategy.quantize_updates)
                            else None)

        # --- unified staleness weights (one jnp impl for both engines):
        # τ < #arrivals <= N, so one table lookup replaces the per-arrival
        # host formula — identical values on every execution path
        self._alpha_table = aggregation.staleness_weights_np(
            np.arange(self.num_clients + 1), self.schedule.alpha0)

        # --- device-resident control plane (scanned path, built lazily) ---
        self._scan_fns: Dict[int, Callable] = {}   # R -> jitted scan
        self._scan_world = None          # (record table, sizes, profiles)
        self._scan_ctl = None                      # ControlState carry
        self._scan_ref_valid = jnp.asarray(False)
        self._scan_round0 = 0
        self._scan_key = jax.random.fold_in(jax.random.PRNGKey(seed), 7)

        # --- accounting -----------------------------------------------------
        self.sim_time = 0.0
        self.comm_time = 0.0
        self.idle_time = 0.0
        self.bytes_sent = 0.0
        self.server_step = 0
        self.round_idx = 0            # absolute rounds completed — run()
                                      # calls CONTINUE numbering, so a
                                      # checkpointed/resumed session is
                                      # label-identical to an unbroken one
        self.history: List[RoundMetrics] = []

    # ------------------------------------------------------------------
    # parameter state (pytree view lazily unpacked from the arena)
    # ------------------------------------------------------------------
    @property
    def params(self):
        if self._params_tree is None:
            self._params_tree = self._unpack(self._params_mat)
            self.dispatches += 1
        return self._params_tree

    @params.setter
    def params(self, tree):
        self._params_tree = tree

    # ------------------------------------------------------------------
    # jitted pieces
    # ------------------------------------------------------------------
    def _build_local_run(self):
        cfg, opt = self.cfg, self.opt

        @jax.jit
        def run(params, batches, lr_scale):
            opt_state = opt.init(params)

            def step(carry, batch):
                p, s = carry
                loss, grads = jax.value_and_grad(
                    lambda q: api.loss_fn(q, batch, cfg))(p)
                grads = jax.tree.map(lambda g: g * lr_scale, grads)
                p, s = opt.update(grads, s, p)
                return (p, s), loss

            (params, _), losses = jax.lax.scan(step, (params, opt_state), batches)
            return params, losses.mean()

        return run

    def _build_eval(self):
        return api.build_default_eval(self.cfg)

    # ------------------------------------------------------------------
    # client-local training (simulated timing + real gradients)
    # ------------------------------------------------------------------
    def _client_batches(self, cid: int):
        """Fixed-step resampled batches -> stable jit shapes (step count
        from ``local_step_count``)."""
        loader = self.loaders[cid]
        bs = loader.batch_size
        steps = local_step_count(loader.n, bs, self.strategy)
        batches = [loader.sample() for _ in range(steps)]
        stacked = {k: np.stack([b[k] for b in batches]) for k in batches[0]}
        return stacked, steps, steps * bs

    def _train_time(self, steps: int, n_samples: int,
                    prof: ClientProfile) -> float:
        """Per-step dispatch overhead + per-sample compute (paper §IV-A:
        larger batches -> fewer steps -> amortized launch cost)."""
        return (steps * self.comm.t_launch
                + n_samples * self.comm.t_sample) / max(prof.speed, 1e-3)

    def _train_client(self, cid: int):
        batches, steps, n_samples = self._client_batches(cid)
        dev = jax.tree.map(jnp.asarray, batches)
        if self._drift_dirs is not None:
            dev = scenario_mod.apply_drift(
                dev, jnp.float32(self._world_view["drift_amp"]),
                self._drift_dirs, self._drift_label)
        new_params, loss = self._local_run(
            self.params, dev, jnp.float32(self.client_lr_scale[cid]))
        self.dispatches += 1
        prof = self.profiles[cid]
        train_time = self._train_time(steps, n_samples, prof)
        delta = jax.tree.map(lambda n, o: (n - o).astype(jnp.float32),
                             new_params, self.params)
        wv = self._world_view
        if wv is not None and float(wv["byz_factor"][cid]) != 1.0:
            # byzantine corruption BEFORE wire compression — the client
            # transmits (and the θ-filter scores) the corrupted update
            f = jnp.float32(wv["byz_factor"][cid])
            delta = jax.tree.map(lambda d: d * f, delta)
            new_params = jax.tree.map(
                lambda o, d: (o.astype(jnp.float32) + d).astype(o.dtype),
                self.params, delta)
        if self.strategy.quantize_updates:
            # int8 + error feedback on the wire; server dequantizes
            err = self._ef_state.setdefault(
                cid, compression.init_error_state(delta))
            q, s, _n, self._ef_state[cid] = compression.compress_update(
                delta, err)
            delta = compression.decompress_update(q, s, delta)
            new_params = jax.tree.map(
                lambda o, d: (o.astype(jnp.float32) + d).astype(o.dtype),
                self.params, delta)
            self._wire_bytes = compression.transport_bytes(q, s)
            self.dispatches += 2
        return new_params, delta, float(loss), train_time

    def _filter_update(self, delta) -> tuple:
        """Client-side sign-alignment filter (Algorithm 1 lines 27-32)."""
        if self.strategy.theta is None or self.ref_sign is None:
            return True, 1.0
        ratio = float(alignment.alignment_ratio(delta, self.ref_sign))
        self.dispatches += 1
        return ratio >= self.strategy.theta, ratio

    def _payload_bytes(self) -> float:
        if self.strategy.quantize_updates and self._wire_bytes:
            return float(self._wire_bytes)
        return float(self.param_bytes)

    def _transfer_time(self, sent: bool, prof: ClientProfile,
                       cid: Optional[int] = None) -> float:
        lat, bw = prof.net_latency, self.comm.bandwidth
        wv = self._world_view
        if wv is not None and cid is not None:
            # link-quality walk re-prices this round's transfer
            lat *= float(wv["lat_scale"][cid])
            bw *= float(wv["bw_scale"][cid])
        if sent:
            return lat + self._payload_bytes() / bw
        # 1-bit skip beacon: still a message, still on the wire
        return lat + self.comm.beacon_bytes / bw

    # ------------------------------------------------------------------
    # hierarchical topology (host paths)
    # ------------------------------------------------------------------
    def _topology_host_round(self, deltas, cids, weights) -> None:
        """Advance the topology carry for the round that just ran on a
        host path (loop/megastep): leaf-pod accumulation of exactly the
        weighted deltas the flat aggregation consumed, plus any due
        inter-tier syncs. Called EVERY round — the cadence is a closed
        form on the absolute round index (``round_idx - 1``; run_round
        already counted this round), matching the scanned carry.

        deltas: list of (rows, lane) arena rows (device), or a list of
        (cids, padded, deltas) shape groups from the megastep path;
        cids: matching client ids; weights: cid -> aggregation weight.
        """
        if self._topo is None:
            return
        r = self.round_idx - 1
        if deltas and isinstance(deltas[0], tuple):
            groups = deltas
            d = jnp.concatenate([g[2][:len(g[0])] for g in groups])
            cids = [c for g in groups for c in g[0]]
        elif deltas:
            d = jnp.stack(deltas)
        else:                              # empty round: cadence still ticks
            d = jnp.zeros((1, self._arena.rows, self._arena.lane),
                          jnp.float32)
            cids = [0]
        w = jnp.asarray([float(weights.get(c, 0.0)) for c in cids],
                        jnp.float32)
        pods = self._topo.pod_of[jnp.asarray(cids, jnp.int32)]
        self._topo_state = self._topo_step(self._topo_state, jnp.int32(r),
                                           d, w, pods)
        self.dispatches += 1

    def topology_summary(self) -> Optional[dict]:
        """Per-tier inter-tier byte/time/sync accounting + the flat-star
        comparison (None when no topology is attached)."""
        if self._topo is None:
            return None
        return self._topo.summary(self._topo_state, rounds=self.round_idx)

    # ------------------------------------------------------------------
    # rounds
    # ------------------------------------------------------------------
    def _select_clients(self) -> List[int]:
        """This round's cohort. Under scenario churn the live roster is
        applied BEFORE top-k — matching the scanned/spmd control plane,
        which masks churned-out scores to -inf before selecting — so
        every execution path fills its cohort from the same candidate
        set (churned clients are absent, never observed, not failed)."""
        st = self.strategy
        k = max(1, int(st.select_fraction * self.num_clients))
        wv = self._world_view
        live = wv["live"] if wv is not None else None
        if st.grad_norm_selection:
            gn = self.grad_norms
            if live is not None:
                gn = np.where(live, gn, -np.inf)
            selected = [int(c) for c in np.argsort(-gn)[:k]
                        if live is None or live[c]]
        elif st.selection and st.select_fraction < 1.0:
            candidates = None
            if self.candidate_frac is not None:
                # stage 1: the sharded candidate pre-filter, computed on
                # the SAME effective scores the device paths rank (live
                # mask applied before the per-shard top-k). frac=1.0 is
                # an all-True mask -> bit-identical selections.
                scores = np.array([self.selector.score(c)
                                   for c in range(self.num_clients)])
                if live is not None:
                    scores = np.where(np.asarray(live, bool), scores,
                                      -np.inf)
                candidates = candidate_mask_np(scores, k,
                                               self.candidate_frac,
                                               self.candidate_shards)
            selected = self.selector.select(k, live=live,
                                            candidates=candidates)
        else:
            selected = [c for c in range(self.num_clients)
                        if live is None or live[c]]
        return selected

    def _dropout_p(self, prof: ClientProfile) -> float:
        wv = self._world_view
        scale = wv["dropout_scale"] if wv is not None else 1.0
        return min(1.0, prof.dropout_p * scale)

    def _advance_world(self) -> None:
        """Transition the WorldState for the round now starting (the
        absolute index ``round_idx - 1``: run_round already counted it)
        and cache one host view for this round's event accounting."""
        if self.scenario is None:
            return
        self._world_state = scenario_mod.world_step(
            self._world_state, self.round_idx - 1, self.scenario,
            self.num_clients)
        self._world_view = scenario_mod.host_view(self._world_state)

    def run_round(self, rnd: int, evaluate: bool = True) -> RoundMetrics:
        self.round_idx += 1
        self._advance_world()
        if self.megastep:
            return self._run_round_mega(rnd, evaluate)
        return self._run_round_loop(rnd, evaluate)

    def _finish_round(self, rnd: int, evaluate: bool, n_selected: int,
                      losses: List[float], n_sent: int, updates_applied: int,
                      round_times: Dict[int, float]) -> RoundMetrics:
        """Round tail shared by both execution paths: Weibull checkpoint
        refit, dynamic-batch feedback, (optional) evaluation, metrics."""
        st = self.strategy
        if st.checkpointing and len(self.failure_log) >= 2:
            lam, k = fit_weibull(np.diff(sorted(self.failure_log)))
            self.ckpt_interval = optimal_interval(
                max(self.sim_time, 1.0), self.recovery_time, lam, k)
        if st.dynamic_batch:
            for cid, b in self.batch_ctrl.feedback(round_times).items():
                if cid < len(self.loaders):
                    self.loaders[cid].set_batch_size(b)
        if evaluate:
            acc = float(self._eval(self.params, self._eval_dev))
            self.dispatches += 1
        else:
            # off-round: carry the last measured accuracy forward
            acc = self.history[-1].accuracy if self.history else float("nan")
        m = RoundMetrics(
            round=rnd, sim_time=self.sim_time, comm_time=self.comm_time,
            idle_time=self.idle_time, bytes_sent=self.bytes_sent,
            updates_applied=updates_applied,
            accept_rate=n_sent / max(n_selected, 1), accuracy=acc,
            loss=float(np.mean(losses)) if losses else float("nan"))
        self.history.append(m)
        return m

    # ------------------------------------------------------------------
    # megastep path: one compiled dispatch per cohort shape group
    # ------------------------------------------------------------------
    def _run_round_mega(self, rnd: int, evaluate: bool = True) -> RoundMetrics:
        st = self.strategy
        selected = self._select_clients()
        round_start = self.sim_time

        # pass 1: dropout draws — SAME Generator order as the loop path
        cohort: List[int] = []
        meta: Dict[int, tuple] = {}       # cid -> (delay, steps, n_samples)
        for cid in selected:
            prof = self.profiles[cid]
            delay = 0.0
            if self.rng.random() < self._dropout_p(prof):
                self.failure_log.append(round_start)
                self.selector.observe(cid, delivered=False)
                if not st.checkpointing:
                    continue                      # client lost this round
                delay = (self.recovery_time if self.checkpoints.get(cid)
                         else self.restart_time)
            cohort.append(cid)
            meta[cid] = (delay, 0, 0)

        # pass 2: per-loader batch draws (per-client Generators — identical
        # draws to the loop path), grouped by rectangular (steps, batch)
        groups: Dict[tuple, dict] = {}
        for cid in cohort:
            batches, steps, n_samples = self._client_batches(cid)
            meta[cid] = (meta[cid][0], steps, n_samples)
            g = groups.setdefault((steps, self.loaders[cid].batch_size),
                                  {"cids": [], "batches": []})
            g["cids"].append(cid)
            g["batches"].append(batches)

        # pass 3: ONE compiled dispatch per shape group — per-client
        # deltas stay on device in the arena; only (C,)-vectors come home.
        # The cohort width is bucketed UP to a power of two (padding
        # replicates the last client; pad results are discarded and pad
        # aggregation weights are zero) so dropout-varying survivor
        # counts reuse compiled traces instead of re-tracing per C.
        has_ref = self._ref_mat is not None and st.theta is not None
        per_client: Dict[int, tuple] = {}     # cid -> (loss, ratio, norm)
        group_results = []                    # (cids, padded_C, deltas_dev)
        for (steps, bs), g in groups.items():
            cids = g["cids"]
            C = len(cids)
            padded = 1 << (C - 1).bit_length()
            blist = g["batches"] + [g["batches"][-1]] * (padded - C)
            batch = {k: jnp.asarray(np.stack([b[k] for b in blist]))
                     for k in blist[0]}
            if self._drift_dirs is not None:
                # same elementwise shift as the loop path's per-client
                # batches — bit-identical regardless of cohort stacking
                batch = scenario_mod.apply_drift(
                    batch, jnp.float32(self._world_view["drift_amp"]),
                    self._drift_dirs, self._drift_label)
            lr_scale = np.ones(padded, np.float32)
            lr_scale[:C] = self.client_lr_scale[cids]
            byz = None
            wv = self._world_view
            if wv is not None and (wv["byz_factor"] != 1.0).any():
                byz_np = np.ones(padded, np.float32)
                byz_np[:C] = wv["byz_factor"][cids]
                byz = jnp.asarray(byz_np)
            idx = None
            if st.quantize_updates:
                # pad rows scatter their EF residual into the dummy row
                # (index num_clients) of the (N+1)-row error arena
                idx = jnp.asarray(
                    np.concatenate([cids, np.full(padded - C,
                                                  self.num_clients)]),
                    jnp.int32)
            deltas, losses, ratios, norms, new_ef = self._cohort_step(
                self._params_mat, batch, jnp.asarray(lr_scale), byz,
                self._ref_mat if has_ref else None,
                self._ef_arena, idx, has_ref=has_ref)
            self.dispatches += 1
            if st.quantize_updates:
                self._ef_arena = new_ef
            losses, ratios, norms = (np.asarray(losses), np.asarray(ratios),
                                     np.asarray(norms))
            for j, cid in enumerate(cids):
                per_client[cid] = (float(losses[j]), float(ratios[j]),
                                   float(norms[j]))
            group_results.append((cids, padded, deltas))

        # pass 4: event-driven accounting, in the loop path's client order
        losses_all: List[float] = []
        arrivals = []                     # (arrive, cid, sent)
        round_times: Dict[int, float] = {}
        n_sent = 0
        for cid in cohort:
            delay, steps, n_samples = meta[cid]
            loss, ratio, gn = per_client[cid]
            prof = self.profiles[cid]
            losses_all.append(loss)
            sent = (st.theta is None or not has_ref
                    or ratio >= st.theta)
            transfer = self._transfer_time(sent, prof, cid)
            arrive = (round_start + delay
                      + self._train_time(steps, n_samples, prof) + transfer)
            arrivals.append((arrive, cid, sent))
            round_times[cid] = arrive - round_start
            self.selector.observe(cid, delivered=True, passed=sent,
                                  round_time=arrive - round_start)
            self.grad_norms[cid] = 0.5 * self.grad_norms[cid] + 0.5 * gn
            if st.per_client_lr:
                self.client_lr_scale[cid] = float(np.clip(
                    self.client_lr_scale[cid] * (1.05 if gn < 1.0 else 0.9),
                    0.25, 2.0))
            if sent:
                n_sent += 1
                self.bytes_sent += self._payload_bytes()
            else:
                self.bytes_sent += self.comm.beacon_bytes
            self.comm_time += transfer
            if st.checkpointing:
                self.checkpoints[cid] = True   # periodic local state save

        arrivals.sort(key=lambda a: a[0])
        updates_applied = 0
        sched = self.schedule
        weights: Dict[int, float] = {}    # cid -> aggregation weight

        if sched.is_sync:
            senders = [cid for (_, cid, sent) in arrivals if sent]
            if senders:
                w = 1.0 / len(senders)
                weights = {cid: w for cid in senders}
                self.server_step += 1
                updates_applied = len(senders)
            if arrivals:
                barrier = arrivals[-1][0]
                self.idle_time += sum(barrier - a for (a, *_r) in arrivals)
                self.sim_time = barrier
        else:
            # async: quorum clock + FedBuff-style buffered mean of
            # staleness-discounted deltas (see the loop path's notes);
            # semi-async DROPS arrivals staler than the bound instead of
            # discounting them (bounded-staleness aggregation)
            if arrivals:
                q_idx = max(0, math.ceil(sched.quorum * len(arrivals)) - 1)
                self.sim_time = arrivals[q_idx][0]
                buf = []
                for i, (_arrive, cid, sent) in enumerate(arrivals):
                    if not sent:
                        continue
                    tau = max(0, i - q_idx)
                    if (sched.max_staleness is not None
                            and tau > sched.max_staleness):
                        continue          # too stale: transmitted, dropped
                    alpha = float(self._alpha_table[tau])
                    buf.append((cid, alpha))
                    self.server_step += 1
                    updates_applied += 1
                if buf:
                    inv = 1.0 / len(buf)
                    weights = {cid: alpha * inv for cid, alpha in buf}

        # server aggregation: ONE weighted arena sum over all shape groups
        # (w_g ← w_anchor + Σ w_i·Δ_i covers both sync FedAvg and async
        # staleness buffering — no per-round pytree stacking)
        if weights:
            d_groups = tuple(d for (_cids, _p, d) in group_results)
            w_groups = []
            for cids, padded, _d in group_results:
                w = np.zeros(padded, np.float32)    # pad rows weigh nothing
                w[:len(cids)] = [weights.get(c, 0.0) for c in cids]
                w_groups.append(jnp.asarray(w))
            new_mat, ref_mat = self._apply_update(self._params_mat,
                                                  d_groups, tuple(w_groups))
            self.dispatches += 1
            self._params_mat = new_mat
            self._params_tree = None      # pytree view now stale
            # reference direction = sign of the global movement this round
            if updates_applied and st.theta is not None:
                self._ref_mat = ref_mat

        self._topology_host_round(group_results, None, weights)

        return self._finish_round(rnd, evaluate, len(selected), losses_all,
                                  n_sent, updates_applied, round_times)

    # ------------------------------------------------------------------
    # reference path: the original per-client loop (O(clients) dispatches
    # per round) — kept as the seeded oracle the megastep is pinned to
    # ------------------------------------------------------------------
    def _run_round_loop(self, rnd: int, evaluate: bool = True) -> RoundMetrics:
        st = self.strategy
        selected = self._select_clients()
        round_start = self.sim_time
        prev_params = self.params
        arrivals = []   # (arrive, cid, new_params, sent, transfer)
        round_times: Dict[int, float] = {}
        losses = []
        n_sent = 0
        topo_deltas: List = []        # arena-packed rows (topology only)
        topo_cids: List[int] = []

        for cid in selected:
            prof = self.profiles[cid]
            delay = 0.0
            if self.rng.random() < self._dropout_p(prof):
                self.failure_log.append(round_start)
                self.selector.observe(cid, delivered=False)
                if not st.checkpointing:
                    continue                      # client lost this round
                delay = (self.recovery_time if self.checkpoints.get(cid)
                         else self.restart_time)
            new_params, delta, loss, t_train = self._train_client(cid)
            if self._topo is not None:
                topo_deltas.append(self._arena.pack(delta))
                topo_cids.append(cid)
            losses.append(loss)
            sent, ratio = self._filter_update(delta)
            transfer = self._transfer_time(sent, prof, cid)
            arrive = round_start + delay + t_train + transfer
            arrivals.append((arrive, cid, new_params, sent, transfer))
            round_times[cid] = arrive - round_start
            self.selector.observe(cid, delivered=True, passed=sent,
                                  round_time=arrive - round_start)
            gn = float(np.sqrt(sum(float(jnp.vdot(g, g))
                                   for g in jax.tree.leaves(delta))))
            self.dispatches += 1
            self.grad_norms[cid] = 0.5 * self.grad_norms[cid] + 0.5 * gn
            if st.per_client_lr:
                self.client_lr_scale[cid] = float(np.clip(
                    self.client_lr_scale[cid] * (1.05 if gn < 1.0 else 0.9),
                    0.25, 2.0))
            if sent:
                n_sent += 1
                self.bytes_sent += self._payload_bytes()
            else:
                self.bytes_sent += self.comm.beacon_bytes
            self.comm_time += transfer
            if st.checkpointing:
                self.checkpoints[cid] = True   # periodic local state save

        arrivals.sort(key=lambda a: a[0])
        updates_applied = 0
        sched = self.schedule
        weights: Dict[int, float] = {}    # cid -> aggregation weight

        if sched.is_sync:
            sent_params = [p for (_, _, p, sent, _) in arrivals if sent]
            if sent_params:
                stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *sent_params)
                self.params = aggregation.fedavg(stacked)
                self.dispatches += 1
                self.server_step += 1
                updates_applied = len(sent_params)
                w1 = 1.0 / len(sent_params)
                weights = {cid: w1 for (_, cid, _p, sent, _t) in arrivals
                           if sent}
            if arrivals:
                barrier = arrivals[-1][0]
                self.idle_time += sum(barrier - a for (a, *_r) in arrivals)
                self.sim_time = barrier
        else:
            # async: clock advances at the quorum arrival; later updates are
            # stale (they overlap the next round) and are discounted.
            # Aggregation is FedBuff-style BUFFERED (mean of staleness-
            # discounted deltas): sequential convex mixing over-weights the
            # last arrival and destabilizes the θ-filter (EXPERIMENTS §Sim).
            # semi-async drops arrivals staler than the bound entirely.
            if arrivals:
                q_idx = max(0, math.ceil(sched.quorum * len(arrivals)) - 1)
                self.sim_time = arrivals[q_idx][0]
                buf = []
                buf_cids = []
                for i, (arrive, cid, new_params, sent, _t) in enumerate(arrivals):
                    if not sent:
                        continue
                    tau = max(0, i - q_idx)
                    if (sched.max_staleness is not None
                            and tau > sched.max_staleness):
                        continue          # too stale: transmitted, dropped
                    alpha = float(self._alpha_table[tau])
                    buf.append((alpha, new_params))
                    buf_cids.append(cid)
                    self.server_step += 1
                    updates_applied += 1
                if buf:
                    self.params = aggregation.buffered_async_update(
                        self.params, buf)
                    self.dispatches += 1
                    inv = 1.0 / len(buf)
                    weights = {c: a * inv
                               for c, (a, _p) in zip(buf_cids, buf)}

        self._topology_host_round(topo_deltas, topo_cids, weights)

        # reference direction = sign of the global movement this round
        if updates_applied and st.theta is not None:
            self.ref_sign = jax.tree.map(
                lambda n, o: jnp.sign(n.astype(jnp.float32)
                                      - o.astype(jnp.float32)).astype(jnp.int8),
                self.params, prev_params)
            self.dispatches += 1

        return self._finish_round(rnd, evaluate, len(selected), losses,
                                  n_sent, updates_applied, round_times)

    # ------------------------------------------------------------------
    # scanned path: the device-resident control plane — R rounds of
    # {select -> train -> θ-filter -> aggregate -> control update} per
    # compiled dispatch (core/megastep.build_scanned_rounds)
    # ------------------------------------------------------------------
    def _scan_setup(self):
        """Build the device world + ControlState once (lazy)."""
        if self._scan_world is not None:
            return self._scan_world
        if self._lazy_world:
            raise RuntimeError(
                "the scanned control plane stacks the full population "
                "device-side; non-resident worlds run the loop/megastep "
                "paths")
        data = records_mod.pack([l.arrays for l in self.loaders])
        sizes = jnp.asarray([l.n for l in self.loaders], jnp.int32)
        speed = jnp.asarray([p.speed for p in self.profiles], jnp.float32)
        latency = jnp.asarray([p.net_latency for p in self.profiles],
                              jnp.float32)
        dropout_p = jnp.asarray([p.dropout_p for p in self.profiles],
                                jnp.float32)
        self._scan_world = (data, sizes, speed, latency, dropout_p)
        self._scan_ctl = control_mod.init_control(
            self.num_clients,
            batch_sizes=[l.batch_size for l in self.loaders],
            arena=self._arena,
            quantize=self.strategy.quantize_updates)
        return self._scan_world

    def _scan_shapes(self):
        """Static (select_k, steps_phys, batch_phys) of the scanned trace."""
        st = self.strategy
        k = max(1, int(st.select_fraction * self.num_clients))
        if not (st.grad_norm_selection
                or (st.selection and st.select_fraction < 1.0)):
            k = self.num_clients
        batch_phys = min(l.batch_size for l in self.loaders)
        steps_phys = min(local_step_count(l.n, batch_phys, st)
                         for l in self.loaders)
        return k, steps_phys, batch_phys

    def _scan_fn(self, R: int):
        if R not in self._scan_fns:
            k, steps_phys, batch_phys = self._scan_shapes()
            self._scan_fns[R] = megastep_mod.build_scanned_rounds(
                self.cfg, self.opt, self._arena, self.strategy, self.comm,
                num_clients=self.num_clients, select_k=k,
                steps_phys=steps_phys, batch_phys=batch_phys,
                rounds_per_dispatch=R, param_bytes=self.param_bytes,
                wire_bytes=self._wire_bytes,
                recovery_time=self.recovery_time,
                restart_time=self.restart_time,
                schedule=self.schedule,
                scenario=self.scenario, drift_dirs=self._drift_dirs,
                drift_label=self._drift_label or "y",
                candidate_frac=self.candidate_frac,
                candidate_shards=self.candidate_shards,
                topology=self._topo,
                eval_fn=(self._eval if self.fused_eval else None),
                eval_every=self.eval_every)
        return self._scan_fns[R]

    def _run_scanned(self, num_rounds: int,
                     eval_final: bool = True) -> List[RoundMetrics]:
        """Runs ``num_rounds`` rounds as scanned dispatches, one dispatch
        ahead of the host: dispatch k+1 is chained on dispatch k's device
        carry (parameters, θ reference, control, world and topology
        state, accounting, fused accuracy) and enqueued before the host
        waits for k's metrics, whose copy back starts as k is launched.
        Every dispatch's records are in ``history``, in round order, when
        this returns."""
        data, sizes, speed, latency, dropout_p = self._scan_setup()
        R = self.rounds_per_dispatch
        ref_mat = self._ref_mat
        if ref_mat is None:      # no reference yet; gated by ref_valid
            ref_mat = jnp.where(jnp.asarray(self._arena.valid_mask()),
                                jnp.int8(0), jnp.int8(-2))
        start = self.round_idx   # absolute round labels across run() calls
        end = start + num_rounds
        round0 = self._scan_round0 - start   # scan round of round 0
        # the first dispatch's accounting (and fused accuracy) come from
        # the host: current here, and what load_state_dict set; later
        # dispatches take them from the carry. Host values are NumPy
        # scalars and arrays, copied to the device rather than run as
        # programs of their own; the carry values are put there as
        # (uncommitted) device arrays, like the carry's, so every dispatch
        # hits one cached signature.
        acc = jax.device_put(np.asarray(
            [self.sim_time, self.comm_time, self.idle_time,
             self.bytes_sent], np.float32))
        if self.fused_eval:
            prev_acc = jax.device_put(np.float32(
                self.history[-1].accuracy if self.history
                else float("nan")))
        pending = None           # the launched dispatch not yet recorded
        for first in range(start, end, R):
            Rg = min(R, end - first)
            last = first + Rg - 1
            with span("repro.fl.args"):
                args = [self._params_mat, ref_mat, self._scan_ref_valid,
                        self._scan_ctl, self._world_state, self._topo_state,
                        data, sizes, speed, latency, dropout_p,
                        self._scan_key, np.int32(round0 + first), acc]
                if self.fused_eval:
                    # eval rides the scan carry: only the final round of the
                    # whole run() is forced (eval_final), the rest follow
                    # the absolute-round eval_every cadence inside the scan
                    mark = last if (eval_final and last == end - 1) else -1
                    args += [prev_acc, np.int32(mark), self._eval_dev]
            # the arena is the state from here on: a pytree view kept from
            # init or a params read would hold a second copy of the model
            # through the dispatch
            self._params_tree = None
            try:
                with span("repro.fl.launch"):
                    carry, ms = self._scan_fn(Rg)(*args)
            except BaseException:
                if pending is not None:   # history stays whole to here
                    self._scan_records(*pending)
                raise
            self.dispatches += 1
            self.overlapped_dispatches += pending is not None
            (self._params_mat, ref_mat, self._scan_ref_valid,
             self._scan_ctl, self._world_state, self._topo_state,
             acc, *tail) = carry
            if self.fused_eval:
                (prev_acc,) = tail
            for v in ms.values():
                v.copy_to_host_async()
            final = last == end - 1
            if final:
                self._scan_ref_valid.copy_to_host_async()

            acc_val = None
            # evaluate once per dispatch (at its last round) when the eval
            # cadence lands inside the dispatch or the run ends — cadence
            # over the ABSOLUTE round index, so a resumed session keeps the
            # uninterrupted run's eval rounds. It reads this dispatch's
            # parameters, so it runs before the next dispatch takes them.
            if not self.fused_eval and (
                    any(r % self.eval_every == 0
                        for r in range(first, last + 1))
                    or (eval_final and final)):
                with span("repro.fl.readback"):
                    acc_val = float(self._eval(self.params,
                                               self._eval_dev))
                self.dispatches += 1
            if pending is not None:
                self._scan_records(*pending)
            pending = (first, ms, acc_val, ref_mat if final else None)
        if pending is not None:
            self._scan_records(*pending)
        return self.history

    def _scan_records(self, first: int, ms, acc_val: Optional[float],
                      ref_mat) -> None:
        """Appends one dispatch's rounds (from ``first``) to ``history``
        and advances the host clocks; ``ms`` is its metric arrays, already
        copying to the host. The call's last dispatch also passes its θ
        reference, kept if the device marked it valid."""
        with span("repro.fl.readback"):
            ms = {k: np.asarray(v) for k, v in ms.items()}
            if ref_mat is not None:
                valid = bool(self._scan_ref_valid)
        Rg = len(ms["sim_time"])
        prev_acc = (self.history[-1].accuracy if self.history
                    else float("nan"))
        with span("repro.fl.records"):
            for j in range(Rg):
                is_last = j == Rg - 1
                self.history.append(RoundMetrics(
                    round=first + j,
                    sim_time=float(ms["sim_time"][j]),
                    comm_time=float(ms["comm_time"][j]),
                    idle_time=float(ms["idle_time"][j]),
                    bytes_sent=float(ms["bytes_sent"][j]),
                    updates_applied=int(ms["updates_applied"][j]),
                    accept_rate=float(ms["accept_rate"][j]),
                    accuracy=(float(ms["accuracy"][j]) if self.fused_eval
                              else (acc_val
                                    if (is_last and acc_val is not None)
                                    else prev_acc)),
                    loss=float(ms["loss"][j]),
                    expert_rows=int(ms["expert_rows"][j])
                    if "expert_rows" in ms else 0))
            if "expert_rows" in ms:
                self.expert_rows += int(ms["expert_rows"].sum())
            self.server_step += int(ms["updates_applied"].sum())
            # failure times are only known to round granularity on the
            # scanned path; log each at its round's start clock
            starts = [self.sim_time] + [float(t) for t
                                        in ms["sim_time"][:-1]]
            for j in range(Rg):
                self.failure_log.extend([starts[j]]
                                        * int(ms["n_failures"][j]))
            self.sim_time = float(ms["sim_time"][-1])
            self.comm_time = float(ms["comm_time"][-1])
            self.idle_time = float(ms["idle_time"][-1])
            self.bytes_sent = float(ms["bytes_sent"][-1])
            self._scan_round0 += Rg
            self.round_idx += Rg
        if ref_mat is not None:
            self._ref_mat = ref_mat if valid else None

    # ------------------------------------------------------------------
    # full-state serialization (ExperimentSession.checkpoint/restore)
    # ------------------------------------------------------------------
    def state_dict(self) -> dict:
        """Everything a bit-identical resume needs, as host (picklable)
        values: parameters (arena matrix or pytree), the θ reference,
        every numpy Generator position (engine, loaders, selector), the
        control statistics of BOTH control planes (host EMAs and the
        scanned ``ControlState``), error-feedback buffers, fault/ckpt
        bookkeeping, accounting accumulators and round history. The
        training data itself is NOT stored — it is rebuilt
        deterministically from the spec's seed."""
        dev = jax.device_get
        return {
            "round_idx": self.round_idx,
            "rng": self.rng.bit_generator.state,
            "loaders": (self.loaders.state_dict() if self._lazy_world
                        else [{"batch_size": l.batch_size,
                               "rng": l.rng.bit_generator.state}
                              for l in self.loaders]),
            "selector": {
                "rng": self.selector.rng.bit_generator.state,
                "records": {cid: dataclasses.asdict(r)
                            for cid, r in self.selector.records.items()}},
            "batch_assignment": dict(self.batch_ctrl.assignment),
            "client_lr_scale": np.array(self.client_lr_scale),
            "grad_norms": np.array(self.grad_norms),
            "failure_log": list(self.failure_log),
            "checkpoints": dict(self.checkpoints),
            "ckpt_interval": float(self.ckpt_interval),
            "ef_state": {cid: dev(t) for cid, t in self._ef_state.items()},
            "ef_arena": (None if self._ef_arena is None
                         else dev(self._ef_arena)),
            "wire_bytes": self._wire_bytes,
            "params_mat": (dev(self._params_mat) if self.megastep
                           else None),
            "params_tree": (None if self.megastep
                            else dev(self._params_tree)),
            "ref_mat": (None if self._ref_mat is None
                        else dev(self._ref_mat)),
            "ref_sign": (None if self.ref_sign is None
                         else dev(self.ref_sign)),
            "world_state": (None if self.scenario is None
                            else dev(self._world_state)),
            "topology": (None if self._topo is None
                         else dev(self._topo_state)),
            "scan": {
                "ctl": (None if self._scan_ctl is None
                        else dev(self._scan_ctl)),
                "ref_valid": dev(self._scan_ref_valid),
                "round0": int(self._scan_round0),
                "key": dev(self._scan_key)},
            "sim_time": self.sim_time, "comm_time": self.comm_time,
            "idle_time": self.idle_time, "bytes_sent": self.bytes_sent,
            "server_step": self.server_step,
            "dispatches": self.dispatches,
            "overlapped_dispatches": self.overlapped_dispatches,
            "expert_rows": self.expert_rows,
            "history": [dataclasses.asdict(m) for m in self.history],
        }

    def load_state_dict(self, state: dict) -> None:
        """Restore :meth:`state_dict` output into a freshly-constructed
        simulation (same cfg/strategy/world/seed spec)."""
        def _gen(saved):
            g = np.random.default_rng(0)
            g.bit_generator.state = saved
            return g

        self.round_idx = state["round_idx"]
        self.rng = _gen(state["rng"])
        saved_loaders = state["loaders"]
        saved_lazy = (isinstance(saved_loaders, dict)
                      and saved_loaders.get("lazy"))
        if self._lazy_world != bool(saved_lazy):
            raise ValueError(
                "checkpoint world residency mismatch: saved "
                f"{'lazy' if saved_lazy else 'eager'} loaders, this "
                f"world is {'lazy' if self._lazy_world else 'eager'}")
        if self._lazy_world:
            self.loaders.load_state_dict(saved_loaders)
        else:
            if len(saved_loaders) != len(self.loaders):
                raise ValueError(
                    f"checkpoint has {len(saved_loaders)} client "
                    f"loaders, this world has {len(self.loaders)}")
            for l, s in zip(self.loaders, saved_loaders):
                l.batch_size = s["batch_size"]
                l.rng = _gen(s["rng"])
        self.selector.rng = _gen(state["selector"]["rng"])
        from repro.core.selection import ClientRecord
        self.selector.records = {
            cid: ClientRecord(**r)
            for cid, r in state["selector"]["records"].items()}
        self.batch_ctrl.assignment = dict(state["batch_assignment"])
        self.client_lr_scale = np.array(state["client_lr_scale"])
        self.grad_norms = np.array(state["grad_norms"])
        self.failure_log = list(state["failure_log"])
        self.checkpoints = dict(state["checkpoints"])
        self.ckpt_interval = state["ckpt_interval"]
        self._ef_state = {cid: jax.tree.map(jnp.asarray, t)
                          for cid, t in state["ef_state"].items()}
        self._ef_arena = (None if state["ef_arena"] is None
                          else jnp.asarray(state["ef_arena"]))
        self._wire_bytes = state["wire_bytes"]
        if self.megastep:
            self._params_mat = jnp.asarray(state["params_mat"])
            self._params_tree = None
        else:
            self._params_tree = jax.tree.map(jnp.asarray,
                                             state["params_tree"])
        self._ref_mat = (None if state["ref_mat"] is None
                         else jnp.asarray(state["ref_mat"]))
        self.ref_sign = (None if state["ref_sign"] is None
                         else jax.tree.map(jnp.asarray, state["ref_sign"]))
        if state.get("world_state") is not None:
            self._world_state = jax.tree.map(jnp.asarray,
                                             state["world_state"])
            self._world_view = scenario_mod.host_view(self._world_state)
        if state.get("topology") is not None:
            if self._topo is None:
                raise ValueError("checkpoint carries topology state but "
                                 "this simulation has no topology")
            self._topo_state = jax.tree.map(jnp.asarray, state["topology"])
        scan = state["scan"]
        if scan["ctl"] is not None:
            self._scan_setup()        # rebuild the device world and shapes
            self._scan_ctl = jax.tree.map(jnp.asarray, scan["ctl"])
        self._scan_ref_valid = jnp.asarray(scan["ref_valid"])
        self._scan_round0 = scan["round0"]
        self._scan_key = jnp.asarray(scan["key"])
        self.sim_time = state["sim_time"]
        self.comm_time = state["comm_time"]
        self.idle_time = state["idle_time"]
        self.bytes_sent = state["bytes_sent"]
        self.server_step = state["server_step"]
        self.dispatches = state["dispatches"]
        self.overlapped_dispatches = state.get("overlapped_dispatches", 0)
        self.expert_rows = state.get("expert_rows", 0)
        self.history = [RoundMetrics(**m) for m in state["history"]]

    def client_pass_rates(self) -> np.ndarray:
        """(num_clients,) θ pass-rate EMAs the server has learned — the
        device ControlState on the scanned path, the host selector
        records otherwise. Diagnostics surface (the differential
        harness's byzantine-rejection assert reads it through
        ``ExperimentSession.client_pass_rates``)."""
        if self._scan_ctl is not None:
            return np.asarray(self._scan_ctl.pass_rate)
        return np.array([self.selector.records[c].pass_rate
                         for c in range(self.num_clients)])

    def run(self, num_rounds: int,
            eval_final: bool = True) -> List[RoundMetrics]:
        if self.rounds_per_dispatch:
            return self._run_scanned(num_rounds, eval_final=eval_final)
        first = self.round_idx          # absolute: resumes keep numbering
        for r in range(first, first + num_rounds):
            # eval_every > 1 skips the eval dispatch on off-rounds (the
            # previous accuracy is carried forward); the final round is
            # evaluated too (unless eval_final=False — session streaming
            # chunks) so ``result.final`` stays meaningful
            evaluate = ((r % self.eval_every == 0)
                        or (eval_final and r == first + num_rounds - 1))
            self.run_round(r, evaluate=evaluate)
        return self.history


# ---------------------------------------------------------------------------
# profile factories
# ---------------------------------------------------------------------------

def heterogeneous_profile_arrays(n: int, seed: int = 0,
                                 dropout_p: float = 0.0,
                                 speed_sigma: float = 0.6) -> dict:
    """Array-backed profile fields (the million-client spelling): the
    SAME Generator draws, in the same order, as the historical
    ``heterogeneous_profiles`` list — one dict of four (n,) arrays
    instead of n dataclass instances."""
    rng = np.random.default_rng(seed)
    speeds = rng.lognormal(0.0, speed_sigma, size=n)
    lats = rng.uniform(0.01, 0.2, size=n)
    mems = rng.uniform(0.4, 1.0, size=n)
    return {"speed": speeds, "net_latency": lats,
            "dropout_p": np.full(n, float(dropout_p)), "memory": mems}


def uniform_profile_arrays(n: int, dropout_p: float = 0.0) -> dict:
    return {"speed": np.ones(n), "net_latency": np.zeros(n),
            "dropout_p": np.full(n, float(dropout_p)),
            "memory": np.ones(n)}


class ProfileView:
    """Sequence[ClientProfile] over per-field arrays.

    ``view[cid]`` builds one dataclass per ACCESS instead of holding one
    per client — at 1M clients the list is hundreds of MB of Python
    objects, the four float arrays ~32 MB. Duck-types the profile lists
    everywhere the engine indexes or iterates them."""

    def __init__(self, arrays: dict):
        self._a = arrays

    def __len__(self) -> int:
        return len(self._a["speed"])

    def field(self, name: str) -> np.ndarray:
        return self._a[name]

    def __getitem__(self, cid):
        if isinstance(cid, slice):
            return [self[i] for i in range(*cid.indices(len(self)))]
        a = self._a
        return ClientProfile(speed=float(a["speed"][cid]),
                             net_latency=float(a["net_latency"][cid]),
                             dropout_p=float(a["dropout_p"][cid]),
                             memory=float(a["memory"][cid]))


def heterogeneous_profiles(n: int, seed: int = 0, dropout_p: float = 0.0,
                           speed_sigma: float = 0.6) -> List[ClientProfile]:
    """Lognormal speeds (stragglers!), uniform latencies."""
    a = heterogeneous_profile_arrays(n, seed=seed, dropout_p=dropout_p,
                                     speed_sigma=speed_sigma)
    return [ClientProfile(speed=float(s), net_latency=float(l),
                          dropout_p=dropout_p, memory=float(m))
            for s, l, m in zip(a["speed"], a["net_latency"], a["memory"])]


def uniform_profiles(n: int, dropout_p: float = 0.0) -> List[ClientProfile]:
    return [ClientProfile(speed=1.0, net_latency=0.0, dropout_p=dropout_p,
                          memory=1.0) for _ in range(n)]
