"""Device control plane (core/control.py) pinned to the host oracles:
``AdaptiveClientSelector`` (selection EMAs + ε-greedy top-k),
``BatchSizeController`` (power-of-two straggler feedback),
``local_step_count`` and the unified staleness weight."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation, control
from repro.core.async_engine import StrategyConfig, local_step_count
from repro.core.batchsize import BatchSizeController, ClientMetrics
from repro.core.selection import AdaptiveClientSelector

N = 8


def _obs_stream(regime: str, rounds: int = 40, seed: int = 0):
    """Seeded per-round observation batches mimicking each engine config:
    'sync' (everyone delivers, barrier times), 'async' (quorum spread +
    dropouts), 'theta' (filter failures -> passed=False observations)."""
    rng = np.random.default_rng(seed)
    for _ in range(rounds):
        k = int(rng.integers(2, N + 1))
        cohort = rng.choice(N, size=k, replace=False)
        if regime == "sync":
            delivered = np.ones(k, bool)
            passed = np.ones(k, bool)
        elif regime == "async":
            delivered = rng.random(k) > 0.3
            passed = np.ones(k, bool)
        else:                                  # theta
            delivered = rng.random(k) > 0.1
            passed = rng.random(k) > 0.4
        times = rng.uniform(0.1, 5.0, size=k)
        yield cohort, delivered, passed, times


@pytest.mark.parametrize("regime", ["sync", "async", "theta"])
def test_observe_and_score_match_selector_oracle(regime):
    sel = AdaptiveClientSelector(N, epsilon=0.0, seed=0)
    ctl = control.init_control(N)
    for cohort, delivered, passed, times in _obs_stream(regime):
        for c, d, p, t in zip(cohort, delivered, passed, times):
            sel.observe(int(c), delivered=bool(d), passed=bool(p),
                        round_time=float(t))
        ctl = control.observe(ctl, jnp.asarray(cohort),
                              mask=jnp.ones(len(cohort), bool),
                              delivered=jnp.asarray(delivered),
                              passed=jnp.asarray(passed),
                              round_time=jnp.asarray(times, jnp.float32))
    host = np.array([[sel.records[c].availability, sel.records[c].pass_rate,
                      sel.records[c].round_time] for c in range(N)])
    dev = np.stack([np.asarray(ctl.avail), np.asarray(ctl.pass_rate),
                    np.asarray(ctl.round_time)], axis=1)
    np.testing.assert_allclose(dev, host, rtol=2e-5, atol=2e-6)
    host_scores = np.array([sel.score(c) for c in range(N)])
    np.testing.assert_allclose(np.asarray(control.score(ctl)), host_scores,
                               rtol=2e-5, atol=2e-6)


def test_two_phase_observation_matches_recovered_client():
    """A dropped-then-checkpoint-recovered client is observed twice
    (delivered=False, then delivered=True) — observe_round must match."""
    sel = AdaptiveClientSelector(4, seed=0)
    ctl = control.init_control(4)
    cohort = jnp.asarray([0, 1, 2, 3])
    failed = jnp.asarray([True, False, True, False])
    active = jnp.asarray([True, True, False, True])   # 2 failed, no ckpt
    passed = jnp.asarray([True, False, False, True])
    times = jnp.asarray([2.0, 1.0, 9.9, 0.5], jnp.float32)
    for c in (0, 2):
        sel.observe(c, delivered=False)
    for c, p, t in ((0, True, 2.0), (1, False, 1.0), (3, True, 0.5)):
        sel.observe(c, delivered=True, passed=p, round_time=t)
    ctl = control.observe_round(ctl, cohort, failed=failed, active=active,
                                passed=passed, round_time=times)
    host = np.array([[sel.records[c].availability, sel.records[c].pass_rate,
                      sel.records[c].round_time] for c in range(4)])
    dev = np.stack([np.asarray(ctl.avail), np.asarray(ctl.pass_rate),
                    np.asarray(ctl.round_time)], axis=1)
    np.testing.assert_allclose(dev, host, rtol=1e-6, atol=1e-7)


def test_select_topk_matches_oracle_without_exploration():
    sel = AdaptiveClientSelector(N, epsilon=0.0, seed=3)
    ctl = control.init_control(N)
    for cohort, delivered, passed, times in _obs_stream("theta", seed=3):
        for c, d, p, t in zip(cohort, delivered, passed, times):
            sel.observe(int(c), delivered=bool(d), passed=bool(p),
                        round_time=float(t))
        ctl = control.observe(ctl, jnp.asarray(cohort),
                              mask=jnp.ones(len(cohort), bool),
                              delivered=jnp.asarray(delivered),
                              passed=jnp.asarray(passed),
                              round_time=jnp.asarray(times, jnp.float32))
    for k in (1, 3, 5, N):
        host = sel.select(k)
        dev = list(np.asarray(
            control.select_topk_epsilon(control.score(ctl), k)))
        assert host == dev, (k, host, dev)


def _host_select_with_draws(scores, k, epsilon, eps_u, pick_u):
    """The AdaptiveClientSelector.select algorithm with the randomness
    injected (uniforms instead of Generator calls) — python reference."""
    order = list(np.argsort(-np.asarray(scores), kind="stable"))
    chosen = order[:k]
    chosen_set = set(chosen)
    pool = [c for c in range(len(scores)) if c not in chosen_set]
    for i in range(k):
        if pool and eps_u[i] < epsilon:
            j = int(pick_u[i] * len(pool))
            chosen[i] = pool.pop(min(j, len(pool) - 1))
    return chosen


def test_select_topk_epsilon_decision_function():
    rng = np.random.default_rng(7)
    for trial in range(20):
        scores = rng.uniform(0.0, 1.0, size=N).astype(np.float32)
        k = int(rng.integers(1, N))
        eps_u = rng.random(k).astype(np.float32)
        pick_u = rng.random(k).astype(np.float32)
        host = _host_select_with_draws(scores, k, 0.6, eps_u, pick_u)
        dev = list(np.asarray(control.select_topk_epsilon(
            jnp.asarray(scores), k, 0.6, eps_u=jnp.asarray(eps_u),
            pick_u=jnp.asarray(pick_u))))
        assert host == dev, (trial, host, dev)


def test_select_topk_explores_beyond_topk():
    scores = jnp.asarray(np.linspace(1.0, 0.1, N), jnp.float32)
    picks = set()
    for s in range(30):
        key = jax.random.PRNGKey(s)
        picks.update(np.asarray(
            control.select_topk(scores, 3, key=key, epsilon=1.0)).tolist())
    assert len(picks) > 3, "epsilon-greedy must explore beyond the top-k"


def test_batch_feedback_matches_controller_oracle():
    rng = np.random.default_rng(1)
    ctrl = BatchSizeController()
    sizes = []
    for cid in range(N):
        m = ClientMetrics(compute=float(rng.uniform(0.2, 4.0)),
                          memory=float(rng.uniform(0.3, 1.0)),
                          latency=float(rng.uniform(0.0, 0.3)))
        sizes.append(ctrl.initial(cid, m))
    ctl = control.init_control(N, batch_sizes=sizes)
    for _ in range(30):
        k = int(rng.integers(1, N + 1))
        cohort = np.sort(rng.choice(N, size=k, replace=False))
        times = rng.uniform(0.05, 8.0, size=k)
        ctrl.feedback({int(c): float(t) for c, t in zip(cohort, times)})
        ctl = control.batch_feedback(
            ctl, jnp.asarray(cohort), jnp.asarray(times, jnp.float32),
            jnp.ones(k, bool))
        host = [ctrl.assignment[c] for c in range(N)]
        assert np.asarray(ctl.batch).tolist() == host


def test_local_steps_matches_host():
    st = StrategyConfig(local_epochs=2, max_samples_per_round=4096)
    ns, bs = [], []
    host = []
    for n in (17, 100, 640, 5000, 20000):
        for b in (32, 64, 128, 512, 1024):
            ns.append(n)
            bs.append(b)
            host.append(local_step_count(n, b, st))
    dev = control.local_steps(jnp.asarray(ns), jnp.asarray(bs),
                              st.local_epochs, st.max_samples_per_round)
    assert np.asarray(dev).tolist() == host


def test_staleness_weight_unified_over_tau():
    """Regression: one implementation serves host + device for τ∈{0..8}."""
    for alpha0 in (0.6, 1.0):
        for tau in range(9):
            closed = np.float32(alpha0) * np.float32(1.0 + tau) \
                ** np.float32(-0.5)
            one = float(aggregation.staleness_weight(tau, alpha0))
            host = aggregation.staleness_weight_host(tau, alpha0)
            vec = aggregation.staleness_weights_np(np.arange(9), alpha0)
            np.testing.assert_allclose(one, closed, rtol=1e-6)
            np.testing.assert_allclose(host, one, rtol=0)    # same impl
            np.testing.assert_allclose(vec[tau], one, rtol=0)


def test_grad_norm_and_lr_scale_rules():
    ctl = control.init_control(4)
    cohort = jnp.asarray([0, 1, 2, 3])
    norms = jnp.asarray([0.5, 2.0, 0.5, 2.0], jnp.float32)
    valid = jnp.asarray([True, True, False, False])
    ctl = control.grad_norm_update(ctl, cohort, norms, valid)
    np.testing.assert_allclose(np.asarray(ctl.grad_norm),
                               [0.75, 1.5, 1.0, 1.0])
    ctl = control.lr_scale_update(ctl, cohort, norms, valid)
    np.testing.assert_allclose(np.asarray(ctl.lr_scale),
                               [1.05, 0.9, 1.0, 1.0])


def test_staleness_and_checkpoint_counters():
    ctl = control.init_control(4)
    cohort = jnp.asarray([0, 2])
    ctl = control.staleness_update(ctl, cohort,
                                   jnp.asarray([True, False]))
    assert np.asarray(ctl.staleness).tolist() == [0, 1, 1, 1]
    ctl = control.checkpoint_update(ctl, cohort,
                                    jnp.asarray([True, False]))
    assert np.asarray(ctl.has_ckpt).tolist() == [True, False, False, False]


@pytest.mark.parametrize("state", ["control", "empty_world", "world"])
def test_initial_states_can_be_donated(state):
    """The scanned run donates ControlState and WorldState whole; two
    fields sharing one buffer would be refused ("donate the same buffer
    twice") on every platform that honors donation."""
    from repro.core import scenario
    n = 6
    built = {"control": lambda: control.init_control(n),
             "empty_world": scenario.empty_world,
             "world": lambda: scenario.init_world(
                 scenario.resolve_scenario("churn+flaky-links"), n)}[state]()
    same = jax.jit(lambda s: s, donate_argnums=0)(built)
    jax.block_until_ready(same)
