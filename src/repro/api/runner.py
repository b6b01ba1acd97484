"""Engine drivers behind the experiment API.

``run_experiment(spec)`` is now a thin wrapper over
``ExperimentSession`` (api/session.py) — open, run to the spec's round
budget, collect the result. The engine-specific machinery lives here:

``build_simulation(spec)``  — the event-driven ``FederatedSimulation``
    (heterogeneous timing, dropout, async/semi-async quorum,
    checkpointing — the paper's apparatus), constructed from a spec.

``SpmdDriver``              — stepping driver for the compiled
    ``fl_step`` path: one jitted step per round over a (C, B, ...)
    cohort batch, with the SAME CommModel applied analytically for
    sync-barrier timing and byte accounting, so both engines emit the
    normalized ``RoundRecord`` schema. Exposes ``run_rounds`` /
    ``state_dict`` / ``load_state_dict`` for session streaming and
    bit-exact checkpoint/resume.

``run_spmd_seed_batch``     — the vectorized multi-seed path used by
    ``run_sweep``: same-shape replicas over S seeds advance as ONE
    vmapped, seed-stacked ``FLState`` (the seed axis folded into the
    cohort dispatch), so an S-seed sweep pays one compiled dispatch per
    round instead of S.

Degenerate parity: with uniform profiles, zero latency, theta=None and
one local step (``max_samples_per_round == batch_size``), the two engines
produce identical round records — the sim runs one SGD step per client
and FedAvg-averages the resulting parameters, which equals the spmd
path's SGD step on the client-mean gradient (momentum is reset per round
in the sim's local runs, so the spmd engine uses momentum=0).
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.result import ExperimentResult, RoundRecord
from repro.api.spec import ExperimentSpec
from repro.core import async_engine as ae
from repro.core import compression, fl_step
from repro.core import megastep as megastep_mod
from repro.core import records as records_mod
from repro.core import scenario as scenario_mod
from repro.data.loader import ArrayLoader
from repro.kernels import arena as arena_mod
from repro.models import api as model_api
from repro.optim import adamw as optim_mod


def run_experiment(spec: ExperimentSpec) -> ExperimentResult:
    """One-shot facade: open a session, run ``spec.rounds``, return the
    normalized result. For streaming, callbacks, checkpoint/resume or
    sweeps use ``ExperimentSession`` / ``run_sweep`` directly."""
    from repro.api.session import ExperimentSession

    session = ExperimentSession.open(spec)
    session.run(spec.rounds)
    return session.result()


# ---------------------------------------------------------------------------
# engine="sim"
# ---------------------------------------------------------------------------

def build_simulation(spec: ExperimentSpec) -> "ae.FederatedSimulation":
    """The event-driven simulation an ``engine='sim'`` spec describes."""
    cfg = spec.resolve_model()
    world = spec.build_world()
    return ae.FederatedSimulation(cfg, world.client_arrays,
                                  world.eval_arrays,
                                  spec.resolve_strategy(), world.profiles,
                                  comm=spec.resolve_comm(), seed=spec.seed,
                                  eval_fn=spec.eval_fn,
                                  eval_every=spec.eval_every,
                                  megastep=spec.megastep,
                                  rounds_per_dispatch=spec.rounds_per_dispatch,
                                  schedule=spec.resolve_schedule(),
                                  scenario=spec.resolve_scenario(),
                                  candidate_frac=spec.candidate_frac,
                                  candidate_shards=spec.candidate_shards,
                                  topology=spec.resolve_topology(),
                                  fused_eval=spec.fused_eval)


def record_from_metrics(m: "ae.RoundMetrics") -> RoundRecord:
    return RoundRecord(round=m.round, sim_time=m.sim_time,
                       comm_time=m.comm_time, idle_time=m.idle_time,
                       bytes_sent=m.bytes_sent,
                       updates_applied=m.updates_applied,
                       accept_rate=m.accept_rate, accuracy=m.accuracy,
                       loss=m.loss, expert_rows=m.expert_rows)


# ---------------------------------------------------------------------------
# engine="spmd"
# ---------------------------------------------------------------------------

def _resolve_optimizer(spec: ExperimentSpec, st):
    opt = spec.optimizer
    if opt is None or opt == "sgd":
        # momentum=0 mirrors the simulator's per-round optimizer reset,
        # which is what makes the degenerate sim/spmd parity exact
        return optim_mod.sgd(st.lr, momentum=0.0)
    if isinstance(opt, str):
        if opt == "adamw":
            return optim_mod.adamw(st.lr)
        if opt == "adafactor":
            return optim_mod.adafactor(st.lr)
        raise ValueError(f"unknown optimizer {opt!r}; expected "
                         "'sgd', 'adamw', 'adafactor' or an Optimizer")
    return opt


def _spmd_control_plane(spec: ExperimentSpec, st, world,
                        round_time_hint=()) -> "fl_step.ControlPlane":
    """Device control-plane options for the compiled path: selection,
    dropout, per-client LR and wire quantization as cohort masking."""
    C = world.num_clients if world is not None else spec.world.num_clients
    k = C
    if st.grad_norm_selection or (st.selection and st.select_fraction < 1.0):
        k = max(1, int(st.select_fraction * C))
    dropout = ()
    if world is not None and any(p.dropout_p > 0 for p in world.profiles):
        dropout = tuple(float(p.dropout_p) for p in world.profiles)
    elif spec.world.dropout_p > 0:
        dropout = (float(spec.world.dropout_p),) * C
    return fl_step.ControlPlane(
        num_clients=C, select_k=k,
        candidate_frac=spec.candidate_frac,
        candidate_shards=spec.candidate_shards,
        grad_norm_selection=st.grad_norm_selection,
        dropout_p=dropout, quantize=st.quantize_updates,
        per_client_lr=st.per_client_lr,
        round_time_hint=tuple(float(t) for t in round_time_hint),
        seed=spec.seed)


def build_spmd_components(spec: ExperimentSpec, world=None,
                          round_time_hint=()):
    """(cfg, strategy, optimizer, state, jitted step) for custom loops —
    the supported way to reach the compiled path from user code (used by
    examples/hierarchical_pods.py). Strategies that use selection /
    dropout / quantized updates / per-client LR get the device control
    plane attached automatically (fl_step.ControlPlane)."""
    cfg = spec.resolve_model()
    st = spec.resolve_strategy()
    comm = spec.resolve_comm()
    opt = _resolve_optimizer(spec, st)
    cp = _spmd_control_plane(spec, st, world, round_time_hint)
    C = cp.num_clients
    if not cp.active():
        cp = None
    scn = spec.resolve_scenario()
    dirs = None
    if scn is not None and scn.drift is not None:
        dirs = scenario_mod.drift_directions(scn.drift, cfg.num_classes,
                                             cfg.num_features)
    topo = spec.resolve_topology()
    state = fl_step.init_state(jax.random.PRNGKey(spec.seed), cfg, opt,
                               control_plane=cp, scenario=scn,
                               num_clients=C, topology=topo, comm=comm)
    # donate the previous FLState through the compiled step — without it
    # every dispatch copies the full parameter arena (the driver rebinds
    # self.state from the step output, so the input buffers are dead)
    step = fl_step.build_fl_train_step(cfg, opt, theta=st.theta,
                                       lr_schedule=spec.lr_schedule,
                                       donate=donate_default(),
                                       beacon_bytes=comm.beacon_bytes,
                                       control_plane=cp,
                                       scenario=scn, drift_dirs=dirs,
                                       topology=topo, comm=comm,
                                       num_clients=C)
    return cfg, st, opt, state, step


def donate_default() -> bool:
    """Donate input buffers to compiled steps wherever the platform
    honors donation (CPU silently ignores it with a warning). Every
    driver below rebinds its state from the step's output before any
    other use, and checkpointing reads the live post-step state
    (``jax.device_get`` in ``state_dict``), so donation is safe."""
    return jax.default_backend() != "cpu"


def _build_eval(cfg, eval_fn):
    if eval_fn is not None:
        return jax.jit(eval_fn)
    return model_api.build_default_eval(cfg)


def _account_comm_round(profiles, comm, steps, n_samples, mask,
                        participating, payload_bytes, acc,
                        lat_scale=None, bw_scale=None) -> None:
    """One sync round's analytic CommModel arithmetic, shared by the
    per-seed driver and the vmapped seed batch: each participating
    client pays train time + transfer (full payload if its update
    passed the mask, else the 1-bit skip beacon); the round advances at
    the barrier (slowest arrival), idle time is the spread below it.
    ``lat_scale``/``bw_scale`` are this round's per-client link-quality
    multipliers (scenario link walks; None -> static links).
    Accumulates into ``acc``'s sim/comm/idle time entries."""
    arrivals = []
    for cid, prof in enumerate(profiles):
        if not participating[cid]:
            continue        # unselected / dropped / churned: silent
        t_train = (steps * comm.t_launch
                   + n_samples * comm.t_sample) / max(prof.speed, 1e-3)
        payload = payload_bytes if mask[cid] > 0 else comm.beacon_bytes
        lat = prof.net_latency * (float(lat_scale[cid])
                                  if lat_scale is not None else 1.0)
        bw = comm.bandwidth * (float(bw_scale[cid])
                               if bw_scale is not None else 1.0)
        transfer = lat + payload / bw
        acc["comm_time"] += transfer
        arrivals.append(t_train + transfer)
    barrier = max(arrivals) if arrivals else 0.0
    acc["sim_time"] += barrier
    acc["idle_time"] += sum(barrier - a for a in arrivals)


def _spmd_loaders(spec: ExperimentSpec, st, world) -> List[ArrayLoader]:
    loaders = [ArrayLoader(arrays, st.batch_size, seed=spec.seed + cid)
               for cid, arrays in enumerate(world.client_arrays)]
    sizes = {l.batch_size for l in loaders}
    if len(sizes) > 1:
        raise ValueError(
            f"engine='spmd' needs one cohort batch shape, but client shard "
            f"sizes clamp batch_size to {sorted(sizes)}; lower "
            f"strategy batch_size or raise data.n_samples")
    return loaders


class SpmdDriver:
    """Stepping driver for the compiled spmd engine.

    Owns the compiled step, the per-client host loaders (the only
    stochastic state outside ``FLState``), and the analytic CommModel
    accounting. ``run_rounds(n)`` advances n rounds and returns their
    ``RoundRecord``s; ``state_dict``/``load_state_dict`` serialize
    (FLState, loader RNG positions, accumulators) so a restored driver
    continues bit-identically to an uninterrupted one.
    """

    def __init__(self, spec: ExperimentSpec):
        self.spec = spec
        self.comm = spec.resolve_comm()
        st = spec.resolve_strategy()
        self.world = spec.build_world()
        self.num_clients = self.world.num_clients
        self.loaders = _spmd_loaders(spec, st, self.world)
        bs = self.loaders[0].batch_size
        # union of the simulator's local steps as ONE cohort gradient
        # step; min across clients keeps the (C, steps*bs, ...) batch
        # rectangular
        self.steps = min(ae.local_step_count(l.n, bs, st)
                         for l in self.loaders)
        self.n_samples = self.steps * bs

        # analytic per-client round time (train + transfer) — the control
        # plane's timeliness signal for reliability-scored selection
        hint = [(self.steps * self.comm.t_launch
                 + self.n_samples * self.comm.t_sample)
                / max(p.speed, 1e-3) + p.net_latency
                for p in self.world.profiles]
        self.cfg, self.st, self._opt, self.state, self.step = \
            build_spmd_components(spec, world=self.world,
                                  round_time_hint=hint)
        self.evaluate = _build_eval(self.cfg, spec.eval_fn)
        self.eval_dev = jax.tree.map(jnp.asarray, self.world.eval_arrays)
        self.param_bytes = sum(x.size * x.dtype.itemsize
                               for x in jax.tree.leaves(self.state.params))
        self.payload_bytes = (compression.arena_wire_bytes(
            arena_mod.ParamArena(self.state.params))
            if self.st.quantize_updates else self.param_bytes)
        scn = spec.resolve_scenario()
        self._has_link_walks = scn is not None and scn.links is not None
        self.round_idx = 0
        self.acc = {"sim_time": 0.0, "comm_time": 0.0, "idle_time": 0.0,
                    "bytes_sent": 0.0}
        self._last_accuracy = float("nan")

    # ------------------------------------------------------------------
    @property
    def params(self):
        return self.state.params

    @property
    def eval_arrays(self):
        return self.world.eval_arrays

    def _draw_batch(self):
        per_client = []
        for loader in self.loaders:
            draws = [loader.sample() for _ in range(self.steps)]
            per_client.append({k: np.concatenate([d[k] for d in draws])
                               for k in draws[0]})
        return {k: jnp.asarray(np.stack([c[k] for c in per_client]))
                for k in per_client[0]}

    def _account(self, rnd: int, m, evaluate: bool) -> RoundRecord:
        mask = np.asarray(m["mask"])
        selected = np.asarray(m["selected"])
        delivered = np.asarray(m["delivered"])
        lat_scale = bw_scale = None
        if self._has_link_walks:
            # the world the compiled step just ran under (FLState.world
            # is post-transition): link walks re-price this round's
            # transfer; churned-out clients already have delivered=0,
            # and without link walks the scales are all-ones — skip the
            # per-round device->host fetch entirely
            wv = scenario_mod.host_view(self.state.world)
            lat_scale, bw_scale = wv["lat_scale"], wv["bw_scale"]
        acc = self.acc
        _account_comm_round(self.world.profiles, self.comm, self.steps,
                            self.n_samples, mask,
                            participating=(selected * delivered) > 0,
                            payload_bytes=self.payload_bytes, acc=acc,
                            lat_scale=lat_scale, bw_scale=bw_scale)
        acc["bytes_sent"] += float(m["bytes_sent"])

        if evaluate:
            self._last_accuracy = float(
                self.evaluate(self.state.params, self.eval_dev))
        return RoundRecord(
            round=rnd, sim_time=acc["sim_time"],
            comm_time=acc["comm_time"], idle_time=acc["idle_time"],
            bytes_sent=acc["bytes_sent"],
            # the COUNT of client updates applied this round (the sim
            # engine's semantics), not a 0/1 any-update flag
            updates_applied=int(mask.sum()),
            accept_rate=float(m["accept_rate"]),
            accuracy=self._last_accuracy, loss=float(m["loss"]))

    def run_rounds(self, n: int, eval_final: bool = True
                   ) -> List[RoundRecord]:
        """Advance n rounds. Evaluation follows the ABSOLUTE eval_every
        cadence; ``eval_final`` additionally evaluates the batch's last
        round (so a completed run's ``result.final`` is measured) —
        session streaming passes False on intermediate chunks to keep
        the accuracy series identical to a single-batch run."""
        records = []
        first, last = self.round_idx, self.round_idx + n - 1
        for rnd in range(first, last + 1):
            batch = self._draw_batch()
            self.state, m = self.step(self.state, batch)
            evaluate = ((rnd % self.spec.eval_every == 0)
                        or (eval_final and rnd == last))
            records.append(self._account(rnd, m, evaluate))
        self.round_idx = last + 1
        return records

    def client_pass_rates(self) -> np.ndarray:
        """(num_clients,) θ pass-rate EMAs from the device control
        plane (see FederatedSimulation.client_pass_rates)."""
        if self.state.control is None:
            raise ValueError(
                "the spmd control plane is inactive (no selection / "
                "dropout / quantize / per-client LR), so no pass-rate "
                "EMAs are tracked")
        return np.asarray(self.state.control.pass_rate)

    # ------------------------------------------------------------------
    # serialization (ExperimentSession.checkpoint/restore)
    # ------------------------------------------------------------------
    def state_dict(self) -> dict:
        return {
            "round_idx": self.round_idx,
            "fl_state": jax.device_get(self.state),
            "loaders": [l.rng.bit_generator.state for l in self.loaders],
            "acc": dict(self.acc),
            "last_accuracy": self._last_accuracy,
        }

    def load_state_dict(self, state: dict) -> None:
        self.round_idx = state["round_idx"]
        self.state = jax.tree.map(jnp.asarray, state["fl_state"])
        if len(state["loaders"]) != len(self.loaders):
            raise ValueError(
                f"checkpoint has {len(state['loaders'])} client loaders, "
                f"this world has {len(self.loaders)}")
        for l, s in zip(self.loaders, state["loaders"]):
            g = np.random.default_rng(0)
            g.bit_generator.state = s
            l.rng = g
        self.acc = dict(state["acc"])
        self._last_accuracy = state["last_accuracy"]

    def result(self, records, wall_time: float = 0.0) -> ExperimentResult:
        return ExperimentResult(
            engine="spmd", strategy=self.spec.strategy_name(),
            rounds=len(records), seed=self.spec.seed, records=list(records),
            cfg=self.cfg, params=self.state.params,
            eval_arrays=self.world.eval_arrays,
            num_clients=self.num_clients, param_bytes=self.param_bytes,
            wall_time=wall_time)


# ---------------------------------------------------------------------------
# vectorized multi-seed execution (run_sweep's spmd fast path)
# ---------------------------------------------------------------------------

def seed_vectorizable(spec: ExperimentSpec, st=None) -> bool:
    """True when same-shape multi-seed replicas of ``spec`` can advance
    as ONE vmapped seed-stacked state: the compiled spmd path with an
    INACTIVE control plane (selection / dropout / quantization /
    per-client LR draw from a per-run PRNG whose seed is compile-time
    static, so replicas would share draws — those sweeps run serially)."""
    if spec.engine != "spmd":
        return False
    st = st or spec.resolve_strategy()
    if st.grad_norm_selection or (st.selection and st.select_fraction < 1.0):
        return False
    if st.quantize_updates or st.per_client_lr:
        return False
    if spec.world.dropout_p > 0:
        return False
    if spec.resolve_scenario() is not None:
        return False        # dynamic worlds run serially (FLState.world)
    if spec.resolve_topology() is not None:
        return False        # per-seed TopologyState: no stacked fast path
    return True


def run_spmd_seed_batch(spec: ExperimentSpec,
                        seeds: Sequence[int]) -> List[ExperimentResult]:
    """Execute ``spec`` at every seed as ONE vmapped seed-stacked run.

    Per-seed worlds (data, partition, eval split) are built on the host
    and stacked along a leading seed axis; parameters and optimizer
    state initialize per seed and advance through
    ``fl_step.build_seed_batched_step`` — one compiled dispatch per
    round for ALL seeds. Requires :func:`seed_vectorizable` specs and
    identical cohort shapes across seeds. Each returned result's
    ``wall_time`` is the whole batch's wall clock (the dispatches are
    shared, so per-seed attribution is meaningless).
    """
    t0 = time.time()
    st = spec.resolve_strategy()
    if not seed_vectorizable(spec, st):
        raise ValueError(
            "spec is not seed-vectorizable (needs engine='spmd' with an "
            "inactive control plane); run the seeds serially instead")
    specs = [dataclasses.replace(spec, seed=int(s)).validate()
             for s in seeds]
    cfg = spec.resolve_model()
    comm = spec.resolve_comm()
    opt = _resolve_optimizer(spec, st)
    worlds = [s.build_world() for s in specs]
    C = worlds[0].num_clients
    loaders = [_spmd_loaders(s, st, w) for s, w in zip(specs, worlds)]
    steps_per_seed = {min(ae.local_step_count(l.n, ls[0].batch_size, st)
                          for l in ls) for ls in loaders}
    if len(steps_per_seed) > 1:
        raise ValueError(
            f"seeds produce different cohort shapes (local steps "
            f"{sorted(steps_per_seed)}); the vmapped sweep needs one — "
            f"raise data.n_samples or run serially")
    steps = steps_per_seed.pop()
    bs = loaders[0][0].batch_size
    n_samples = steps * bs

    state = fl_step.init_seed_batched_state(
        [s.seed for s in specs], cfg, opt)
    vstep = fl_step.build_seed_batched_step(
        cfg, opt, theta=st.theta, lr_schedule=spec.lr_schedule,
        beacon_bytes=comm.beacon_bytes)
    evaluate = _build_eval(cfg, spec.eval_fn)
    veval = jax.jit(jax.vmap(evaluate))
    eval_dev = jax.tree.map(
        lambda *xs: jnp.asarray(np.stack(xs)),
        *[w.eval_arrays for w in worlds])
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(state.params)) // len(specs)

    S = len(specs)
    acc = [{"sim_time": 0.0, "comm_time": 0.0, "idle_time": 0.0,
            "bytes_sent": 0.0} for _ in range(S)]
    last_acc = [float("nan")] * S
    records: List[List[RoundRecord]] = [[] for _ in range(S)]
    # the round loop keeps every metric ON DEVICE — a per-round
    # np.asarray would block the dispatch stream on the transfer (the
    # paper's per-round sync anti-pattern); buffers drain in ONE
    # device_get after the last round, and the dispatch count stays
    # exactly (rounds + eval rounds) — asserted below
    dispatches = 0
    metric_buf, acc_buf = [], {}
    eval_rounds = [rnd for rnd in range(spec.rounds)
                   if (rnd % spec.eval_every == 0)
                   or (rnd == spec.rounds - 1)]
    for rnd in range(spec.rounds):
        stacked = []
        for ls in loaders:
            per_client = []
            for loader in ls:
                draws = [loader.sample() for _ in range(steps)]
                per_client.append({k: np.concatenate([d[k] for d in draws])
                                   for k in draws[0]})
            stacked.append({k: np.stack([c[k] for c in per_client])
                            for k in per_client[0]})
        batch = {k: jnp.asarray(np.stack([s[k] for s in stacked]))
                 for k in stacked[0]}
        state, m = vstep(state, batch)
        dispatches += 1
        metric_buf.append(m)
        if rnd in eval_rounds:
            acc_buf[rnd] = veval(state.params, eval_dev)
            dispatches += 1
    assert dispatches == spec.rounds + len(eval_rounds), \
        "buffered readback must not change the dispatch count"
    metric_buf, acc_buf = jax.device_get((metric_buf, acc_buf))

    for rnd, m in enumerate(metric_buf):
        mask = np.asarray(m["mask"])                       # (S, C)
        for i in range(S):
            a = acc[i]
            # seed_vectorizable guarantees no selection/dropout (all
            # clients participate) and no quantization (full payload)
            _account_comm_round(worlds[i].profiles, comm, steps,
                                n_samples, mask[i],
                                participating=np.ones(C, bool),
                                payload_bytes=param_bytes, acc=a)
            a["bytes_sent"] += float(m["bytes_sent"][i])
            if rnd in acc_buf:
                last_acc[i] = float(acc_buf[rnd][i])
            records[i].append(RoundRecord(
                round=rnd, sim_time=a["sim_time"],
                comm_time=a["comm_time"], idle_time=a["idle_time"],
                bytes_sent=a["bytes_sent"],
                updates_applied=int(mask[i].sum()),
                accept_rate=float(m["accept_rate"][i]),
                accuracy=last_acc[i],
                loss=float(m["loss"][i])))

    elapsed = time.time() - t0
    out = []
    for i, s in enumerate(specs):
        params_i = jax.tree.map(lambda x: x[i], state.params)
        out.append(ExperimentResult(
            engine="spmd", strategy=s.strategy_name(), rounds=s.rounds,
            seed=s.seed, records=records[i], cfg=cfg, params=params_i,
            eval_arrays=worlds[i].eval_arrays, num_clients=C,
            param_bytes=param_bytes, wall_time=elapsed))
    return out


# ---------------------------------------------------------------------------
# vectorized multi-seed execution of the SCANNED sim engine
# ---------------------------------------------------------------------------

def run_scanned_seed_batch(spec: ExperimentSpec,
                           seeds: Sequence[int]) -> List[ExperimentResult]:
    """Execute the scanned sim engine at every seed as ONE vmapped
    dispatch stream (the whole-experiment-fusion analogue of
    :func:`run_spmd_seed_batch`).

    Eval is fused into the scan carry (``fused_eval`` is forced on), so
    an S-seed sweep cell of N rounds costs ``ceil(N / R)`` compiled
    dispatches TOTAL — no per-seed, per-dispatch eval readback breaks
    the stream; per-round metrics buffer on device and drain in one
    ``device_get`` at the end. Per-seed worlds (record tables, profiles,
    control state, PRNG keys) stack along a leading seed axis; each
    seed's record table pads with zero rows to the longest, which never
    changes a trajectory because a seed's batch rows lie inside its own
    records. Requires every seed to resolve the same scanned
    trace shape (select_k, steps_phys, batch_phys).
    """
    t0 = time.time()
    if spec.engine != "sim" or not spec.rounds_per_dispatch:
        raise ValueError(
            "run_scanned_seed_batch vectorizes the scanned sim engine — "
            "the spec needs engine='sim' and rounds_per_dispatch")
    specs = [dataclasses.replace(spec, seed=int(s),
                                 fused_eval=True).validate()
             for s in seeds]
    sims = [build_simulation(s) for s in specs]
    for sim in sims:
        sim._scan_setup()
    shapes = {sim._scan_shapes() for sim in sims}
    if len(shapes) > 1:
        raise ValueError(
            f"seeds resolve different scanned trace shapes "
            f"{sorted(shapes)} (select_k, steps_phys, batch_phys must "
            "agree); equalize data sizes across seeds or run serially")
    sim0 = sims[0]
    R = sim0.rounds_per_dispatch
    S = len(sims)

    # --- stack the per-seed device worlds along a leading seed axis ---
    data = records_mod.stack([sim._scan_world[0] for sim in sims])
    sizes, speed, latency, dropout_p = (
        jnp.stack([sim._scan_world[1 + a] for sim in sims])
        for a in range(4))
    stack = lambda xs: jax.tree.map(lambda *ls: jnp.stack(ls), *xs)
    ctl = stack([sim._scan_ctl for sim in sims])
    ws = stack([sim._world_state for sim in sims])
    topo = (stack([sim._topo_state for sim in sims])
            if sim0._topo_state is not None else None)
    params_mat = jnp.stack([sim._params_mat for sim in sims])
    blank_ref = jnp.where(jnp.asarray(sim0._arena.valid_mask()),
                          jnp.int8(0), jnp.int8(-2))
    ref_mat = jnp.stack([blank_ref] * S)
    ref_valid = jnp.stack([sim._scan_ref_valid for sim in sims])
    base_key = jnp.stack([sim._scan_key for sim in sims])
    eval_data = stack([sim._eval_dev for sim in sims])
    acc = jnp.zeros((S, 4), jnp.float32)
    prev_acc = jnp.full((S,), jnp.nan, jnp.float32)

    # --- one jitted vmap of the raw scanned run per chunk width -------
    k_sel, steps_phys, batch_phys = sim0._scan_shapes()
    vruns = {}

    def vrun(Rg):
        if Rg not in vruns:
            raw = megastep_mod.build_scanned_rounds(
                sim0.cfg, sim0.opt, sim0._arena, sim0.strategy, sim0.comm,
                num_clients=sim0.num_clients, select_k=k_sel,
                steps_phys=steps_phys, batch_phys=batch_phys,
                rounds_per_dispatch=Rg, param_bytes=sim0.param_bytes,
                wire_bytes=sim0._wire_bytes,
                recovery_time=sim0.recovery_time,
                restart_time=sim0.restart_time,
                schedule=sim0.schedule, scenario=sim0.scenario,
                drift_dirs=sim0._drift_dirs,
                drift_label=sim0._drift_label or "y",
                candidate_frac=sim0.candidate_frac,
                candidate_shards=sim0.candidate_shards,
                topology=sim0._topo,
                eval_fn=sim0._eval, eval_every=sim0.eval_every,
                jit=False)
            axes = (0, 0, 0, 0, 0, (0 if topo is not None else None),
                    0, 0, 0, 0, 0, 0, None, 0, 0, None, 0)
            vruns[Rg] = jax.jit(
                jax.vmap(raw, in_axes=axes),
                donate_argnums=megastep_mod.scan_donate_argnums(
                    fused=True))
        return vruns[Rg]

    ms_buf = []
    round0 = 0
    while round0 < spec.rounds:
        Rg = min(R, spec.rounds - round0)
        mark = (spec.rounds - 1 if round0 + Rg == spec.rounds else -1)
        carry, ms = vrun(Rg)(
            params_mat, ref_mat, ref_valid, ctl, ws, topo,
            data, sizes, speed, latency, dropout_p, base_key,
            jnp.int32(round0), acc, prev_acc, jnp.int32(mark), eval_data)
        (params_mat, ref_mat, ref_valid, ctl, ws, topo, acc,
         prev_acc) = carry
        ms_buf.append(ms)            # device-side; one readback below
        round0 += Rg
    ms_buf, params_final = jax.device_get((ms_buf, params_mat))

    records: List[List[RoundRecord]] = [[] for _ in range(S)]
    rnd = 0
    for ms in ms_buf:
        Rg = ms["loss"].shape[1]
        for j in range(Rg):
            for i in range(S):
                records[i].append(RoundRecord(
                    round=rnd + j,
                    sim_time=float(ms["sim_time"][i, j]),
                    comm_time=float(ms["comm_time"][i, j]),
                    idle_time=float(ms["idle_time"][i, j]),
                    bytes_sent=float(ms["bytes_sent"][i, j]),
                    updates_applied=int(ms["updates_applied"][i, j]),
                    accept_rate=float(ms["accept_rate"][i, j]),
                    accuracy=float(ms["accuracy"][i, j]),
                    loss=float(ms["loss"][i, j])))
        rnd += Rg

    elapsed = time.time() - t0
    out = []
    for i, (s, sim) in enumerate(zip(specs, sims)):
        out.append(ExperimentResult(
            engine="sim", strategy=s.strategy_name(), rounds=s.rounds,
            seed=s.seed, records=records[i], cfg=sim.cfg,
            params=sim._arena.unpack(jnp.asarray(params_final[i])),
            eval_arrays=sim.eval_arrays, num_clients=sim.num_clients,
            param_bytes=sim.param_bytes, wall_time=elapsed))
    return out
