"""Compiled cohort megastep: one dispatch per simulated round.

The event-driven simulator used to train selected clients one at a time —
a separate jitted dispatch, host→device batch transfer, and per-leaf
``float(jnp.vdot(...))`` sync for EVERY client EVERY round: exactly the
per-tensor launch storm the paper profiles away (Tables V-VI). This
module collapses all of it into two compiled dispatches per round:

``build_cohort_step``  — stacks the cohort's fixed-shape batches into
    ``(C, steps, B, ...)`` and runs one jitted vmap-of-scan that returns,
    in a single call: per-client parameter deltas already packed into the
    flat ``(C, rows, LANE)`` arena, mean losses, sign-alignment ratios vs
    the reference direction, update L2 norms, and the updated batched
    error-feedback arena (int8 wire compression, when enabled). The only
    host transfer per round is the small (C,) metric vectors.

``build_apply_update`` — server aggregation as one weighted sum over the
    arena (Pallas ``masked_agg`` on TPU, jnp oracle on CPU): both sync
    FedAvg over the senders and FedBuff-style staleness-discounted async
    buffering are ``w_g ← w_anchor + Σ_i w_i·Δ_i`` for host-chosen
    weights, so one kernel serves both modes. Also returns the new
    reference sign (-2 padding sentinel) for the next round's θ filter.

Timing, selection, dropout and byte accounting stay event-driven in
Python, consuming these batched device results (core/async_engine.py).

``build_scanned_rounds`` goes one step further (the device-resident
control plane): selection, dynamic batch adaptation, dropout, timing and
staleness-weighted aggregation ALL run as pure-JAX state transitions
(core/control.py), so ``rounds_per_dispatch`` rounds execute inside ONE
jitted ``lax.scan`` — dispatches per simulated round drop from O(1)
toward O(1/R). Selection is a masked fixed-width cohort: a stable top-k
+ ε-greedy pick on device, with per-client arena slabs (error-feedback
buffers) fetched by a one-hot gather (Pallas kernel on TPU, jnp oracle
on CPU — kernels/gather.py via kernels/arena.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import aggregation, alignment, compression, control
from repro.kernels import arena as arena_ops
from repro.models import api


# a cohort whose training state (parameters, momentum and gradients, f32,
# one copy per slot) would pass this many bytes trains one slot after
# another instead of as one vmap
VMAP_COHORT_BYTES = 1 << 30


def cohort_is_sequential(arena, cohort: int) -> bool:
    """True when the cohort trains slot by slot: its vmapped training
    state, ``cohort`` × 3 f32 copies of the arena's parameters, would
    pass ``VMAP_COHORT_BYTES``. The detectors stay far below (a 100-slot
    fleet cohort holds ~60 MB); a model of 10^8 parameters and more
    does not fit a vmap of two on a 16 GB chip."""
    return cohort * 3 * 4 * arena.n > VMAP_COHORT_BYTES


def _train_cohort(cfg, opt, arena, params_mat, batches, lr_scale):
    """The cohort's local training — shared by the per-round cohort step
    and the scanned control plane so the two compiled paths can never
    drift apart. Small models train as ONE vmap-of-scan; a model whose
    cohort would not fit that way (``cohort_is_sequential``) trains one
    slot after another: each slot unpacks the globals into its own
    training state and packs its delta as soon as it is done. Returns
    the per-client deltas packed into the (C, rows, lane) arena, (C,)
    mean losses, and the model's counters (``api.loss_and_stats``)
    summed over slots and steps."""

    def train_one(params, client_batches, scale):
        opt_state = opt.init(params)

        def step(carry, batch):
            p, s = carry
            (loss, stats), grads = jax.value_and_grad(
                lambda q: api.loss_and_stats(q, batch, cfg),
                has_aux=True)(p)
            grads = jax.tree.map(lambda g: g * scale, grads)
            p, s = opt.update(grads, s, p)
            return (p, s), (loss, stats)

        (p, _), (losses, stats) = jax.lax.scan(
            step, (params, opt_state), client_batches)
        return p, losses.mean(), jax.tree.map(jnp.sum, stats)

    if cohort_is_sequential(arena, lr_scale.shape[0]):
        mat, deltas, losses, stats = params_mat, [], [], []
        for c in range(lr_scale.shape[0]):
            p, loss, counted = train_one(
                arena.unpack(mat), jax.tree.map(lambda b: b[c], batches),
                lr_scale[c])
            # the next slot unpacks its globals only once this delta is
            # done, so one slot's copy lives at a time; pack(globals) is
            # params_mat bit for bit
            d, mat = jax.lax.optimization_barrier(
                (arena.pack(p) - params_mat, params_mat))
            deltas.append(d)
            losses.append(loss)
            stats.append(counted)
        deltas, losses = jnp.stack(deltas), jnp.stack(losses)
        stats = jax.tree.map(lambda *xs: sum(xs), *stats)
    else:
        params = arena.unpack(params_mat)
        new_params, losses, stats = jax.vmap(
            lambda b, s: train_one(params, b, s))(batches, lr_scale)
        deltas = arena.pack_cohort(jax.tree.map(
            lambda n, o: (n - o).astype(jnp.float32), new_params, params))
    return deltas, losses, jax.tree.map(jnp.sum, stats)


def build_cohort_step(cfg, opt, arena, theta=None, quantize: bool = False):
    """Returns jitted ``step(params_mat, batches, lr_scale, byz, ref_mat,
    ef, idx, *, has_ref) -> (deltas, losses, ratios, norms, new_ef)``.

    params_mat: (rows, lane) f32 arena of the round-start globals.
    batches:    pytree, leaves (C, steps, B, ...) — the stacked cohort.
    lr_scale:   (C,) per-client LR scaling (FedL2P personalization).
    byz:        (C,) per-client update multipliers (byzantine scenario
                clients: ±scale; None -> everyone honest), applied BEFORE
                wire compression and θ scoring — the server receives the
                corrupted update.
    ref_mat:    (rows, lane) int8 reference sign (None until it exists).
    ef, idx:    (N, rows, lane) EF arena + (C,) client ids (quantize only).
    has_ref:    static — round 0 has no reference direction; ratios are 1.
    """
    @functools.partial(jax.jit, static_argnames=("has_ref",))
    def cohort_step(params_mat, batches, lr_scale, byz, ref_mat, ef, idx, *,
                    has_ref):
        deltas, losses, _ = _train_cohort(cfg, opt, arena, params_mat,
                                          batches, lr_scale)
        if byz is not None:
            deltas = deltas * byz[:, None, None]
        new_ef = ef
        if quantize:
            restored, residual = compression.compress_cohort(
                deltas, jnp.take(ef, idx, axis=0))
            new_ef = ef.at[idx].set(residual)
            deltas = restored

        norms = jnp.sqrt(jnp.sum(deltas * deltas, axis=(1, 2)))
        if has_ref and theta is not None:
            ratios = alignment.cohort_alignment(deltas, ref_mat, arena.n)
        else:
            ratios = jnp.ones(deltas.shape[:1], jnp.float32)
        return deltas, losses, ratios, norms, new_ef

    return cohort_step


def build_apply_update(arena):
    """Returns jitted ``apply(params_mat, deltas_groups, weight_groups) ->
    (new_params_mat, new_ref_mat)``.

    ``deltas_groups`` / ``weight_groups`` are tuples (one entry per batch
    shape group this round — heterogeneous step counts quantize to a few
    power-of-two groups); weights are host-computed: mask/|S| for sync,
    α(τ)/N for async senders, 0 for filtered clients.
    """

    @jax.jit
    def apply_update(params_mat, deltas_groups, weight_groups):
        agg = None
        for d, w in zip(deltas_groups, weight_groups):
            part = arena_ops.weighted_sum(d, w)
            agg = part if agg is None else agg + part
        new_mat = params_mat + agg
        return new_mat, arena.sign_ref(new_mat, params_mat)

    return apply_update


# ---------------------------------------------------------------------------
# device-resident control plane: R rounds per dispatch (lax.scan)
# ---------------------------------------------------------------------------

def build_scanned_rounds(cfg, opt, arena, st, comm, *, num_clients: int,
                         select_k: int, steps_phys: int, batch_phys: int,
                         rounds_per_dispatch: int, param_bytes: float,
                         wire_bytes=None, epsilon: float = 0.1,
                         ema: float = 0.8, recovery_time: float = 0.2,
                         restart_time: float = 1.0, schedule=None,
                         scenario=None, drift_dirs=None,
                         drift_label: str = "y", candidate_frac=None,
                         candidate_shards: int = 8, topology=None,
                         eval_fn=None, eval_every: int = 1,
                         jit: bool = True, donate=None):
    """Compile ``rounds_per_dispatch`` full FL rounds — {select → train
    cohort → θ-filter → staleness-weighted arena aggregate → control
    update} — into one jitted ``lax.scan``.

    The entire server control plane lives in a ``ControlState`` of
    ``(N,)`` device arrays (core/control.py); selection produces a FIXED
    width-``select_k`` cohort (top-k + ε-greedy on device), so
    dropout-varying rounds reuse a single trace and the per-round launch
    + transfer overhead the paper profiles (Tables V-VI) is amortized
    over R rounds. Event accounting (arrival times, quorum clock,
    barrier idle, bytes) is computed with the same formulas the
    event-driven engine uses, as vector arithmetic inside the scan.

    Semantics vs the event-driven reference (documented deviations):
      * batch sampling / dropout / ε-exploration draw from a JAX PRNG
        (per-round ``fold_in`` keys, so trajectories are independent of
        the dispatch grouping R — ``rounds_per_dispatch=8`` is
        bit-identical to ``=1``), not the host numpy Generators;
      * every cohort client trains on the static (steps_phys,
        batch_phys) shape; ``dynamic_batch`` adapts the ControlState's
        power-of-two assignments and drives the simulated straggler
        timing exactly (the §IV-A effect), while gradient math keeps the
        fixed physical shape — the price of a single trace;
      * the Weibull checkpoint-interval refit (which never feeds back
        into the trajectory) is skipped; failures are counted per round.

    Returns ``run(params_mat, ref_mat, ref_valid, ctl, ws, topo, data,
    sizes, speed, latency, dropout_p, base_key, round0, acc) ->
    (carry, metrics)`` where ``metrics`` is a dict of ``(R,)`` per-round
    series and ``carry`` the updated ``(params_mat, ref_mat, ref_valid,
    ctl, ws, topo, acc)``. ``ws`` is the dynamic-world
    ``scenario.WorldState`` (the 0-width placeholder when no scenario is
    attached — it passes through untouched); its transitions fold keys
    from the absolute round index, so world trajectories are independent
    of the dispatch grouping R. ``topo`` is the hierarchical
    ``topology.TopologyState`` carry (None when ``topology`` — a
    ``TopologyRuntime`` — is not attached); its sync cadence is a closed
    form on the absolute round index, so it is likewise R-independent.
    ``acc`` is the (sim_time, comm_time, idle_time, bytes_sent) f32
    accumulator vector. ``data`` is the population's records, one
    ``records.RecordTable``: a round reads its cohort batch from it
    with one row gather.

    Whole-experiment fusion (``eval_fn`` not None): evaluation joins the
    scan carry instead of breaking the dispatch stream. ``eval_fn`` must
    be a traceable ``(params_tree, eval_data) -> accuracy`` function; the
    carry gains a ``prev_acc`` f32 scalar (NaN before the first eval) and
    ``run`` three trailing arguments ``(prev_acc, eval_mark, eval_data)``
    — ``eval_mark`` is the absolute round index forced to evaluate (the
    engine's eval_final semantics; -1 disables) and ``eval_data`` the
    device-resident eval batch, passed explicitly (not closed over) so
    the whole ``run`` can be vmapped over a seed axis with per-seed eval
    arrays. Rounds where ``r % eval_every == 0`` (or ``r == eval_mark``)
    evaluate inside a ``lax.cond`` — the untaken branch costs nothing —
    and every round's metrics carry the latest accuracy (the loop
    engine's carry-forward semantics). Eval keys off the absolute round
    index, so fused accuracy is independent of the dispatch grouping R.

    ``jit=False`` returns the raw python callable (for a caller-side
    ``jax.jit(jax.vmap(run, ...))`` over seeds); ``donate`` controls
    buffer donation of the carry operands through the jitted path —
    default: donate whenever the platform honors donation (not CPU).
    """
    from repro.core import scenario as scenario_mod
    from repro.core.schedule import ScheduleSpec
    sched = schedule if schedule is not None else ScheduleSpec.from_strategy(st)
    scn = scenario if scenario_mod.is_active(scenario) else None
    dirs = (jnp.asarray(drift_dirs)
            if (scn is not None and scn.drift is not None) else None)
    N, K, R = int(num_clients), int(select_k), int(rounds_per_dispatch)
    E = int(eval_every)
    theta_on = st.theta is not None
    payload = float(wire_bytes if (st.quantize_updates and wire_bytes)
                    else param_bytes)
    beacon = float(comm.beacon_bytes)

    def round_body(carry, r, data, sizes, speed, latency, dropout_p,
                   base_key, eval_mark=None, eval_data=None):
        if eval_fn is not None:
            (params_mat, ref_mat, ref_valid, ctl, ws, topo, acc,
             prev_acc) = carry
        else:
            params_mat, ref_mat, ref_valid, ctl, ws, topo, acc = carry
        sim_t, comm_t, idle_t, bytes_s = acc
        # each phase runs under a named scope (fl_*): op metadata that the
        # device trace carries with every op, so a trace splits the round's
        # device time by phase; it changes no op (see repro/obs.py)
        with jax.named_scope("fl_select"):
            key = jax.random.fold_in(base_key, r)
            k_eps, k_pick, k_drop, k_data = jax.random.split(key, 4)

            # --- dynamic world: this round's WorldState -----------------
            if scn is not None:
                ws = scenario_mod.world_step(ws, r, scn, N)

            # --- selection: fixed-width top-k cohort --------------------
            # (churned-out clients score -inf so they are only picked when
            # fewer than K clients are live; those slots carry zero weight)
            if st.grad_norm_selection:
                gn = (ctl.grad_norm if scn is None
                      else jnp.where(ws.live, ctl.grad_norm, -jnp.inf))
                cohort = jnp.argsort(-gn, stable=True)[:K]
            elif st.selection and K < N:
                scores = control.score(ctl)
                if scn is not None:
                    scores = jnp.where(ws.live, scores, -jnp.inf)
                # two-stage: the sharded candidate pre-filter runs on the
                # live-masked scores (candidate_frac=None -> single-stage,
                # 1.0 -> all-True mask, bit-identical either way)
                cohort = control.two_stage_select(
                    scores, K, candidate_frac=candidate_frac,
                    candidate_shards=candidate_shards, epsilon=epsilon,
                    eps_u=jax.random.uniform(k_eps, (K,)),
                    pick_u=jax.random.uniform(k_pick, (K,)),
                    live=None if scn is None else ws.live)
            else:
                cohort = jnp.arange(K)
            live_c = (jnp.ones((K,), bool) if scn is None else ws.live[cohort])
            # --- dropout draws (§IV-C fault model) ----------------------
            drop_p = dropout_p[cohort]
            if scn is not None and scn.dropout is not None:
                drop_p = drop_p * ws.dropout_scale
            failed = jax.random.uniform(k_drop, (K,)) < drop_p
            if scn is not None:
                failed = failed & live_c      # absent clients cannot fail
            if st.checkpointing:
                active = live_c
                delay = jnp.where(
                    failed, jnp.where(ctl.has_ckpt[cohort],
                                      jnp.float32(recovery_time),
                                      jnp.float32(restart_time)), 0.0)
            else:
                active = ~failed & live_c
                delay = jnp.zeros((K,), jnp.float32)

        with jax.named_scope("fl_gather"):
            # --- cohort batches: index sampling + one row gather from
            # the packed record table (core/records.py) ------------------
            sz = sizes[cohort]
            idx = jax.random.randint(k_data, (K, steps_phys, batch_phys), 0,
                                     sz[:, None, None])
            batch = data.take(cohort, idx)
            if dirs is not None:
                batch = scenario_mod.apply_drift(batch, ws.drift_amp, dirs,
                                                 drift_label)

        with jax.named_scope("fl_train"):
            # --- local training: the cohort's vmap-of-scan (slot by slot
            # for a model too large to vmap) ----------------------------
            lr_scale = (ctl.lr_scale[cohort] if st.per_client_lr
                        else jnp.ones((K,), jnp.float32))
            deltas, losses, counters = _train_cohort(
                cfg, opt, arena, params_mat, batch, lr_scale)
            if scn is not None and scn.byzantine is not None:
                # corruption BEFORE wire compression and θ scoring
                deltas = deltas * ws.byz_factor[cohort][:, None, None]
        with jax.named_scope("fl_align"):
            new_ef = ctl.ef
            if st.quantize_updates:
                ef_cohort = arena_ops.cohort_gather(ctl.ef, cohort)
                restored, residual = compression.compress_cohort(
                    deltas, ef_cohort)
                new_ef = ctl.ef.at[cohort].set(
                    jnp.where(active[:, None, None], residual, ef_cohort))
                deltas = restored
            ctl = ctl._replace(ef=new_ef)

            norms = jnp.sqrt(jnp.sum(deltas * deltas, axis=(1, 2)))
            if theta_on:
                ratios = alignment.cohort_alignment(deltas, ref_mat, arena.n)
                passed = jnp.where(ref_valid, ratios >= st.theta, True)
            else:
                passed = jnp.ones((K,), bool)
            sent = active & passed

        with jax.named_scope("fl_aggregate"):
            # --- event accounting (the engine's timing model, vectorized) ---
            b_eff = jnp.minimum(
                (ctl.batch[cohort] if st.dynamic_batch else batch_phys), sz)
            steps_t = control.local_steps(sz, b_eff, st.local_epochs,
                                          st.max_samples_per_round)
            b_eff = b_eff.astype(jnp.float32)
            steps_f = steps_t.astype(jnp.float32)
            train_t = ((steps_f * comm.t_launch
                        + steps_f * b_eff * comm.t_sample)
                       / jnp.maximum(speed[cohort], 1e-3))
            msg_bytes = jnp.where(sent, payload, beacon)
            if scn is not None and scn.links is not None:
                # link-quality walk re-prices this round's transfer
                transfer = (latency[cohort] * ws.lat_scale[cohort]
                            + msg_bytes / (comm.bandwidth
                                           * ws.bw_scale[cohort]))
            else:
                transfer = latency[cohort] + msg_bytes / comm.bandwidth
            arrive = delay + train_t + transfer          # rel. to round start
            n_active = active.sum().astype(jnp.int32)
            n_sent = sent.sum().astype(jnp.int32)
            comm_t = comm_t + jnp.sum(jnp.where(active, transfer, 0.0))
            bytes_s = bytes_s + jnp.sum(jnp.where(active, msg_bytes, 0.0))

            # --- aggregation weights: sync barrier / async quorum -------
            if sched.is_sync:
                barrier = jnp.max(jnp.where(active, arrive, -jnp.inf))
                sim_t = jnp.where(n_active > 0, sim_t + barrier, sim_t)
                idle_t = idle_t + jnp.sum(
                    jnp.where(active, barrier - arrive, 0.0))
                w = sent.astype(jnp.float32) \
                    / jnp.maximum(n_sent.astype(jnp.float32), 1.0)
                updates_applied = n_sent
            else:
                t_act = jnp.where(active, arrive, jnp.inf)
                q_idx = jnp.maximum(
                    0, jnp.ceil(sched.quorum * n_active.astype(jnp.float32))
                    .astype(jnp.int32) - 1)
                sim_t = jnp.where(n_active > 0,
                                  sim_t + jnp.sort(t_act)[q_idx], sim_t)
                rank = jnp.argsort(jnp.argsort(t_act, stable=True),
                                   stable=True)
                tau = jnp.maximum(0, rank - q_idx)
                alphas = aggregation.staleness_weight(tau, sched.alpha0)
                applied_mask = sent
                if sched.max_staleness is not None:
                    # semi-async: bounded staleness — arrivals beyond the
                    # cutoff transmitted (bytes already charged) but dropped
                    applied_mask = sent & (tau <= sched.max_staleness)
                n_applied = applied_mask.sum().astype(jnp.int32)
                w = jnp.where(applied_mask, alphas, 0.0) \
                    / jnp.maximum(n_applied.astype(jnp.float32), 1.0)
                updates_applied = n_applied

            # --- one weighted arena sum applies the round --------------
            new_mat = params_mat + arena_ops.weighted_sum(deltas, w)
            applied = updates_applied > 0
            if theta_on:
                sref = arena.sign_ref(new_mat, params_mat)
                ref_mat = jnp.where(applied, sref, ref_mat)
                ref_valid = ref_valid | applied
            params_mat = new_mat

            # --- hierarchical topology: leaf accumulation + due syncs ---
            if topology is not None:
                topo = topology.step(topo, r, deltas, w,
                                     topology.pod_of[cohort])

        with jax.named_scope("fl_control"):
            # --- control-plane transitions (core/control.py) ------------
            ctl = control.observe_round(ctl, cohort, failed=failed,
                                        active=active, passed=sent,
                                        round_time=arrive, ema=ema)
            ctl = control.grad_norm_update(ctl, cohort, norms, active)
            if st.per_client_lr:
                ctl = control.lr_scale_update(ctl, cohort, norms, active)
            if st.dynamic_batch:
                ctl = control.batch_feedback(ctl, cohort, arrive, active)
            if st.checkpointing:
                ctl = control.checkpoint_update(ctl, cohort, active)
            ctl = control.staleness_update(ctl, cohort, sent)

            loss_mean = (jnp.sum(jnp.where(active, losses, 0.0))
                         / jnp.maximum(n_active.astype(jnp.float32), 1.0))
            # under churn the paper's acceptance-rate denominator is the
            # participating cohort (the host engines' len(selected)), not
            # the static cohort width
            denom = (jnp.float32(K) if scn is None or scn.churn is None
                     else jnp.maximum(live_c.sum().astype(jnp.float32), 1.0))
            metrics = {
                "sim_time": sim_t, "comm_time": comm_t, "idle_time": idle_t,
                "bytes_sent": bytes_s,
                "updates_applied": updates_applied,
                "accept_rate": (n_sent.astype(jnp.float32) / denom),
                "loss": loss_mean,
                "n_failures": failed.sum().astype(jnp.int32),
                # the model's counters over the whole cohort's training
                # (expert_rows; none for the detectors)
                **counters,
            }
            acc = jnp.stack([sim_t, comm_t, idle_t, bytes_s])

        # --- fused eval: accuracy joins the scan carry ------------------
        if eval_fn is not None:
            with jax.named_scope("fl_eval"):
                do = (r % E == 0) | (r == eval_mark)
                prev_acc = jax.lax.cond(
                    do,
                    lambda m: jnp.asarray(
                        eval_fn(arena.unpack(m), eval_data), jnp.float32),
                    lambda m: prev_acc,
                    params_mat)
            metrics["accuracy"] = prev_acc
            return (params_mat, ref_mat, ref_valid, ctl, ws, topo, acc,
                    prev_acc), metrics
        return (params_mat, ref_mat, ref_valid, ctl, ws, topo, acc), metrics

    if eval_fn is None:
        def run_impl(params_mat, ref_mat, ref_valid, ctl, ws, topo, data,
                     sizes, speed, latency, dropout_p, base_key, round0,
                     acc):
            body = functools.partial(round_body, data=data, sizes=sizes,
                                     speed=speed, latency=latency,
                                     dropout_p=dropout_p, base_key=base_key)
            rounds = round0 + jnp.arange(R, dtype=jnp.int32)
            carry0 = (params_mat, ref_mat, ref_valid, ctl, ws, topo, acc)
            return jax.lax.scan(lambda c, r: body(c, r), carry0, rounds)
    else:
        def run_impl(params_mat, ref_mat, ref_valid, ctl, ws, topo, data,
                     sizes, speed, latency, dropout_p, base_key, round0,
                     acc, prev_acc, eval_mark, eval_data):
            body = functools.partial(round_body, data=data, sizes=sizes,
                                     speed=speed, latency=latency,
                                     dropout_p=dropout_p, base_key=base_key,
                                     eval_mark=eval_mark,
                                     eval_data=eval_data)
            rounds = round0 + jnp.arange(R, dtype=jnp.int32)
            carry0 = (params_mat, ref_mat, ref_valid, ctl, ws, topo, acc,
                      prev_acc)
            return jax.lax.scan(lambda c, r: body(c, r), carry0, rounds)

    if not jit:
        return run_impl
    return jax.jit(run_impl, donate_argnums=scan_donate_argnums(
        fused=eval_fn is not None, donate=donate))


def scan_donate_argnums(*, fused: bool, donate=None):
    """Donation set for the scanned ``run``: the carry operands
    (arena, reference sign, control state, world/topology state, the
    accounting accumulator — plus ``prev_acc`` when eval is fused) are
    consumed and rebound from the scan output by every caller, so their
    input buffers can be reused in place. The read-only population
    (record table, sizes, speed, latency, dropout_p) and the PRNG key are
    never donated. CPU ignores donation with a warning, so the default
    donates only where the platform honors it.
    """
    if donate is None:
        donate = jax.default_backend() != "cpu"
    if not donate:
        return ()
    nums = (0, 1, 2, 3, 4, 5, 13)          # carry operands
    return nums + ((14,) if fused else ())  # + prev_acc
