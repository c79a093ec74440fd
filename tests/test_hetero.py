"""Closed-loop heterogeneity subsystem tests (repro.hetero).

Covers: cost models + availability traces, the scenario registry,
controller trace-safety (telemetry→mask steps under a traced round
index), the bit-exact PolicyConfig shim, the staleness bound, the
pinned closed-loop time-to-accuracy win on the pareto-straggler
scenario, engine parity with controller state in the scan carry (all
four engines), and — in the slow subprocess leg — the 8-device
scenario matrix plus the one-param-sized-psum-per-round HLO invariant
under a controller.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro
from repro.launch.mesh import make_mesh
from repro.core import (PolicyConfig, ensure_coverage, make_quadratic,
                        sample_masks)
from repro.hetero import (CostModel, PolicyController,
                          ResourceProportionalController,
                          StalenessBoundedController, Telemetry, available,
                          as_controller, capacity, dirichlet_weights,
                          initial_telemetry, make_controller, make_scenario,
                          next_telemetry, pareto_cost, scenario_problem,
                          time_to_target, uniform_cost, with_availability,
                          worker_times)

KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------------------
# cost models
# --------------------------------------------------------------------------

def test_uniform_cost_times_are_work():
    cost = uniform_cost(4)
    work = jnp.array([0.0, 10.0, 30.0, 5.0])
    t = worker_times(cost, work, 3)
    np.testing.assert_allclose(np.asarray(t), np.asarray(work))
    assert float(t.max()) == 30.0
    # idle workers cost nothing even with per-round overhead; bandwidth
    # divides uplink BYTES (default wire model: 4 bytes per masked float)
    cost_oh = CostModel(compute_rate=jnp.ones(4), bandwidth=jnp.ones(4),
                        overhead=7.0)
    t2 = np.asarray(worker_times(cost_oh, work, 0))
    assert t2[0] == 0.0
    np.testing.assert_allclose(t2[1:], 7.0 + 5 * np.asarray(work)[1:])
    # explicit uplink_bytes (a compressed wire) override the default
    t3 = np.asarray(worker_times(cost_oh, work, 0, work))
    np.testing.assert_allclose(t3[1:], 7.0 + 2 * np.asarray(work)[1:])


def test_pareto_cost_is_heavy_tailed_and_bounded():
    cost = pareto_cost(KEY, 512, alpha=1.2)
    r = np.asarray(cost.compute_rate)
    assert (r > 0).all() and (r <= 1.0).all()
    assert r.min() < 0.3 < r.max()       # stragglers AND near-full-speed


def test_availability_static_default_is_all_true():
    cost = uniform_cost(8)
    assert bool(available(cost, KEY, 5).all())
    np.testing.assert_allclose(np.asarray(capacity(cost, 5)), 1.0)


def test_dropout_availability_rate_and_determinism():
    cost = with_availability(uniform_cost(2000), dropout_prob=0.3)
    a1 = available(cost, KEY, 4)
    a2 = available(cost, KEY, 4)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    frac = float(jnp.mean(a1))
    assert abs(frac - 0.7) < 0.05


def test_churn_rotates_cohorts_deterministically():
    cost = with_availability(uniform_cost(8), churn_period=3,
                             churn_cohorts=4)
    for t in range(12):
        a = np.asarray(available(cost, KEY, t))
        offline = (t // 3) % 4
        want = (np.arange(8) % 4) != offline
        np.testing.assert_array_equal(a, want)
        assert a.sum() == 6                 # one cohort (2 of 8) offline


def test_diurnal_capacity_bounds_and_phase_stagger():
    cost = with_availability(uniform_cost(8), diurnal_period=20,
                             diurnal_amplitude=0.8)
    caps = np.stack([np.asarray(capacity(cost, t)) for t in range(40)])
    assert caps.min() >= 0.05 and caps.max() <= 1.8 + 1e-6
    # staggered phases: not all workers peak at the same round
    assert len(set(caps.argmax(axis=0).tolist())) > 1


def test_time_to_target_cumulative_and_inf():
    trace = np.array([100.0, 10.0, 1.0, 0.1, 0.01])   # x0, x1, rounds 1..3
    times = np.array([5.0, 7.0, 9.0])
    assert time_to_target(trace, times, 1.0) == 5.0
    assert time_to_target(trace, times, 0.05) == 5.0 + 7.0 + 9.0
    assert time_to_target(trace, times, 1e-9) == float("inf")


# --------------------------------------------------------------------------
# scenarios
# --------------------------------------------------------------------------

def test_scenario_registry_names_and_params():
    for name in ("uniform", "pareto-stragglers", "dropout", "churn",
                 "diurnal", "dirichlet"):
        s = make_scenario(name, KEY, 8)
        assert s.name == name and s.cost.num_workers == 8
    s = make_scenario("dropout:p=0.4,alpha=1.5", KEY, 8)
    assert s.cost.dropout_prob == 0.4
    assert float(s.cost.compute_rate.min()) < 1.0   # pareto rates rode along
    s = make_scenario("churn:period=7,cohorts=3", KEY, 9)
    assert s.cost.churn_period == 7 and s.cost.churn_cohorts == 3
    assert make_scenario("dirichlet:alpha=0.1", KEY, 4).dirichlet_alpha == 0.1
    with pytest.raises(ValueError, match="unknown scenario"):
        make_scenario("gamma-stragglers", KEY, 8)
    with pytest.raises(ValueError, match="key=value"):
        make_scenario("dropout:0.4", KEY, 8)


def test_dirichlet_weights_and_scenario_problem():
    w = dirichlet_weights(KEY, 16, 0.3)
    assert w.shape == (16,)
    np.testing.assert_allclose(float(w.mean()), 1.0, rtol=1e-5)
    assert float(w.min()) >= 0.0
    scen = make_scenario("dirichlet:alpha=0.3", KEY, 8)
    prob = scenario_problem(scen, KEY, kind="quadratic", num_workers=8,
                            dim=16, kappa=10.0, coupling=0.0)
    res = repro.run(prob, KEY, num_rounds=5, num_regions=4)
    assert np.isfinite(np.asarray(res.dist_sq)).all()
    # non-IID shards genuinely spread the per-worker optima
    spread = float(jnp.abs(prob.b - prob.b.mean(axis=0)).max())
    uni = scenario_problem(make_scenario("uniform", KEY, 8), KEY,
                           kind="quadratic", num_workers=8, dim=16,
                           kappa=10.0, coupling=0.0)
    assert spread > float(jnp.abs(uni.b - uni.b.mean(axis=0)).max())
    with pytest.raises(ValueError, match="unknown problem kind"):
        scenario_problem(scen, KEY, kind="svm")


# --------------------------------------------------------------------------
# controllers
# --------------------------------------------------------------------------

def test_policy_shim_is_bit_exact():
    """The PolicyController shim must reproduce the policy path of every
    engine bit-for-bit — old configs ARE controllers."""
    prob = make_quadratic(KEY, num_workers=8, dim=32, kappa=50.0,
                          coupling=0.0, num_regions=4, grad_noise=0.1)
    pol = PolicyConfig(keep_prob=0.5, tau_star=1)
    kw = dict(num_rounds=10, num_regions=4)
    a = repro.run(prob, KEY, policy=pol, **kw)
    b = repro.run(prob, KEY, controller=PolicyController(pol), **kw)
    np.testing.assert_array_equal(np.asarray(a.xs), np.asarray(b.xs))
    np.testing.assert_array_equal(np.asarray(a.round_time),
                                  np.asarray(b.round_time))
    np.testing.assert_array_equal(np.asarray(a.max_stale),
                                  np.asarray(b.max_stale))
    ref = repro.run(prob, KEY, engine="reference", policy=pol, **kw)
    refc = repro.run(prob, KEY, engine="reference", controller=PolicyController(pol),
                              **kw)
    np.testing.assert_array_equal(np.asarray(ref.xs), np.asarray(refc.xs))


def test_as_controller_and_parser():
    pol = PolicyConfig(keep_prob=0.3)
    assert as_controller(pol) == PolicyController(pol)
    rc = ResourceProportionalController()
    assert as_controller(rc) is rc
    with pytest.raises(TypeError):
        as_controller("resource")
    c = make_controller("resource:keep=0.4,tau=2,ema=0.3,min_keep=0.1")
    assert c == ResourceProportionalController(keep_prob=0.4, tau_star=2,
                                               ema=0.3, min_keep=0.1)
    c = make_controller("staleness-bounded:s=3,keep=0.2")
    assert isinstance(c, StalenessBoundedController)
    assert c.max_stale == 3 and c.base.keep_prob == 0.2
    c = make_controller("policy:name=roundrobin")
    assert c.policy.name == "roundrobin"
    assert make_controller(pol) == PolicyController(pol)
    with pytest.raises(ValueError, match="unknown controller"):
        make_controller("bandit")
    with pytest.raises(ValueError, match="key=value"):
        make_controller("resource:0.4")


@pytest.mark.parametrize("ctrl", [
    PolicyController(PolicyConfig(keep_prob=0.5, tau_star=1)),
    ResourceProportionalController(keep_prob=0.5, tau_star=1),
    StalenessBoundedController(base=PolicyConfig(keep_prob=0.3), max_stale=2),
])
def test_controller_step_trace_safe_in_scan(ctrl):
    """Controller steps with a traced ``t`` inside lax.scan must be
    bit-identical to eager steps at the same concrete rounds, with the
    state threading through the carry."""
    N, Q = 8, 6
    telem = Telemetry(times=jnp.linspace(0.0, 3.0, N),
                      work=jnp.arange(N, dtype=jnp.float32) * 4,
                      count_q=jnp.array([3, 0, 1, 2, 0, 4], jnp.int32),
                      stale_q=jnp.array([0, 5, 0, 1, 2, 0], jnp.int32))

    def body(state, t):
        m, state = ctrl.step(state, telem, jax.random.fold_in(KEY, t), t,
                             N, Q)
        return state, m

    _, scanned = jax.lax.scan(body, ctrl.init_state(N, Q),
                              jnp.arange(1, 6))
    state = ctrl.init_state(N, Q)
    for i, t in enumerate(range(1, 6)):
        eager, state = ctrl.step(state, telem, jax.random.fold_in(KEY, t),
                                 t, N, Q)
        np.testing.assert_array_equal(np.asarray(scanned[i]),
                                      np.asarray(eager))


def test_resource_controller_learns_throughput_order():
    """After observed rounds, the EMA throughput estimates order the
    workers like the true compute rates, and the keep allocation follows."""
    N, Q = 8, 8
    rates = jnp.array([0.05, 0.1, 0.2, 0.4, 0.6, 0.8, 0.9, 1.0])
    cost = CostModel(compute_rate=rates, bandwidth=jnp.full((N,), jnp.inf))
    ctrl = ResourceProportionalController(keep_prob=0.5, tau_star=1,
                                          ema=0.5)
    state = ctrl.init_state(N, Q)
    telem = initial_telemetry(N, Q)
    for t in range(1, 8):
        m, state = ctrl.step(state, telem, jax.random.fold_in(KEY, t), t,
                             N, Q)
        work = (m * 4).sum(axis=1).astype(jnp.float32)
        times = worker_times(cost, work, t)
        telem = next_telemetry(telem, m.sum(axis=0), work, times)
    thr = np.asarray(state)
    # estimates converge to the true rates (work/time == rate exactly here)
    observed = thr[np.asarray(telem.work) > 0]
    want = np.asarray(rates)[np.asarray(telem.work) > 0]
    assert (np.argsort(observed) == np.argsort(want)).all()
    # allocation follows: the fastest worker trains more than the slowest
    m, _ = ctrl.step(state, telem, jax.random.fold_in(KEY, 99), 99, N, Q)
    assert int(m[-1].sum()) >= int(m[0].sum())


def test_staleness_bounded_controller_caps_staleness():
    """No region goes more than max_stale rounds untrained, while the
    unbounded base policy starves regions far longer."""
    prob = make_quadratic(KEY, num_workers=4, dim=32, kappa=50.0,
                          coupling=0.0, num_regions=8)
    base = PolicyConfig(keep_prob=0.08, tau_star=0, heterogeneous=False)
    unbounded = repro.run(prob, KEY, num_rounds=40, num_regions=8,
                         policy=base)
    assert int(np.asarray(unbounded.max_stale).max()) > 4
    for s in (2, 4):
        ctrl = StalenessBoundedController(base=base, max_stale=s)
        res = repro.run(prob, KEY, num_rounds=40, num_regions=8,
                       controller=ctrl)
        trace = np.asarray(res.max_stale)
        assert trace.max() <= s, (s, trace)
        assert trace.max() == s          # the bound binds (base starves)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 8), st.integers(1, 10), st.integers(0, 10_000))
def test_ensure_coverage_per_region_tau(n, q, seed):
    """Array-τ ensure_coverage: per-region targets met (clamped at N) and
    coverage is never removed — the contract the staleness-bounded
    controller's forced coverage relies on."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    m = jax.random.uniform(ks[0], (n, q)) < 0.2
    tau_q = jax.random.randint(ks[1], (q,), 0, n + 3)
    fixed = ensure_coverage(m, tau_q)
    want = np.minimum(np.asarray(tau_q), n)
    assert (np.asarray(fixed.sum(axis=0)) >= want).all()
    assert bool(jnp.all(fixed | ~m))                 # only ever adds


# --------------------------------------------------------------------------
# engines: closed loop end to end
# --------------------------------------------------------------------------

def test_closed_loop_reference_parity():
    """The compiled engine's controller/cost threading must match the
    host-loop oracle running the same closed loop eagerly."""
    N = 8
    prob = make_quadratic(KEY, num_workers=N, dim=32, kappa=50.0,
                          coupling=0.0, num_regions=4, grad_noise=0.1)
    scen = make_scenario("pareto-stragglers", jax.random.PRNGKey(7), N)
    ctrl = ResourceProportionalController(keep_prob=0.5, tau_star=1)
    kw = dict(num_rounds=10, num_regions=4, controller=ctrl,
              cost=scen.cost)
    res = repro.run(prob, KEY, **kw)
    ref = repro.run(prob, KEY, engine="reference", **kw)
    np.testing.assert_allclose(np.asarray(res.xs), np.asarray(ref.xs),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(res.comm_floats),
                                  np.asarray(ref.comm_floats))
    np.testing.assert_allclose(np.asarray(res.round_time),
                               np.asarray(ref.round_time), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(res.max_stale),
                                  np.asarray(ref.max_stale))
    assert res.tau_star == ref.tau_star


def test_closed_loop_batch_engine():
    """The batch engine threads per-seed controller state/telemetry; rows
    match per-seed single runs."""
    N = 8
    prob = make_quadratic(KEY, num_workers=N, dim=32, kappa=50.0,
                          coupling=0.0, num_regions=4)
    scen = make_scenario("pareto-stragglers", jax.random.PRNGKey(7), N)
    ctrl = ResourceProportionalController(keep_prob=0.5, tau_star=1)
    keys = jax.random.split(KEY, 3)
    kw = dict(num_rounds=8, num_regions=4, controller=ctrl, cost=scen.cost)
    bat = repro.run(prob, keys, engine="batch", **kw)
    assert bat.round_time.shape == (3, 8)
    assert bat.max_stale.shape == (3, 8)
    for b in range(3):
        single = repro.run(prob, keys[b], **kw)
        np.testing.assert_allclose(np.asarray(bat.xs[b]),
                                   np.asarray(single.xs), atol=2e-4)
        np.testing.assert_array_equal(np.asarray(bat.round_time[b]),
                                      np.asarray(single.round_time))


def test_closed_loop_sharded_engines_single_device_parity():
    """Controller + cost + availability dynamics through the sharded
    engines on degenerate meshes: parity with the scan engine, and the
    double-buffered overlap loop exactly equal to sequential (controller
    state rides the rotated carry)."""
    N = 8
    prob = make_quadratic(KEY, num_workers=N, dim=48, kappa=50.0,
                          coupling=0.0, num_regions=6, grad_noise=0.1)
    scen = make_scenario("churn:period=3,cohorts=4,alpha=1.2",
                         jax.random.PRNGKey(3), N)
    ctrl = ResourceProportionalController(keep_prob=0.5, tau_star=1)
    kw = dict(num_rounds=10, num_regions=6, controller=ctrl,
              cost=scen.cost)
    ref = repro.run(prob, KEY, **kw)
    mesh = make_mesh((1,), ("data",))
    sh = repro.run(prob, KEY, engine="sharded", mesh=mesh, **kw)
    assert np.abs(np.asarray(sh.xs) - np.asarray(ref.xs)).max() <= 1e-6
    np.testing.assert_array_equal(np.asarray(sh.comm_floats),
                                  np.asarray(ref.comm_floats))
    np.testing.assert_array_equal(np.asarray(sh.round_time),
                                  np.asarray(ref.round_time))
    np.testing.assert_array_equal(np.asarray(sh.max_stale),
                                  np.asarray(ref.max_stale))
    ov = repro.run(prob, KEY, engine="sharded", mesh=mesh, overlap=True, **kw)
    np.testing.assert_array_equal(np.asarray(ov.xs), np.asarray(sh.xs))
    np.testing.assert_array_equal(np.asarray(ov.round_time),
                                  np.asarray(sh.round_time))
    mesh2 = make_mesh((1, 1), ("data", "model"))
    for curv in ("dense", "diag"):
        ref2 = repro.run(prob, KEY, curvature=curv,
                        use_kernel=(curv == "diag"),
                        projection="ns" if curv == "dense" else "eigh",
                        **kw)
        sh2 = repro.run(prob, KEY, engine="sharded2d", mesh=mesh2, curvature=curv,
                                 **kw)
        assert np.abs(np.asarray(sh2.xs)
                      - np.asarray(ref2.xs)).max() <= 1e-5, curv
        np.testing.assert_array_equal(np.asarray(sh2.comm_floats),
                                      np.asarray(ref2.comm_floats))
        np.testing.assert_array_equal(np.asarray(sh2.round_time),
                                      np.asarray(ref2.round_time))
        ov2 = repro.run(prob, KEY, engine="sharded2d", mesh=mesh2, curvature=curv,
                                 overlap=True, **kw)
        np.testing.assert_array_equal(np.asarray(ov2.xs),
                                      np.asarray(sh2.xs))


def test_closed_loop_beats_static_on_pareto_stragglers():
    """The acceptance pin: on the pareto-straggler scenario the
    resource-proportional controller reaches the target loss in
    measurably less SIMULATED wall-clock than static bernoulli (same
    mean keep fraction, same τ*, same seed; damped Newton so convergence
    takes ~13 rounds and per-round times integrate)."""
    N = 16
    prob = make_quadratic(KEY, num_workers=N, dim=64, kappa=100.0,
                          coupling=0.0, num_regions=8)
    scen = make_scenario("pareto-stragglers", jax.random.PRNGKey(101), N)
    pol = PolicyConfig(keep_prob=0.5, tau_star=1, heterogeneous=True)
    ctrl = make_controller("resource:keep=0.5,tau=1")
    kw = dict(num_rounds=60, num_regions=8, lr=0.5, cost=scen.cost)
    static = repro.run(prob, KEY, policy=pol, **kw)
    closed = repro.run(prob, KEY, controller=ctrl, **kw)
    target = 1e-8 * float(static.dist_sq[0])
    t_static = time_to_target(static.dist_sq, static.round_time, target)
    t_closed = time_to_target(closed.dist_sq, closed.round_time, target)
    assert np.isfinite(t_static) and np.isfinite(t_closed)
    assert t_closed < 0.8 * t_static, (t_closed, t_static)
    # the win is allocation, not less total work: mean keep stays ~0.5
    assert 0.35 < float(np.asarray(closed.comm_floats).mean()
                        / (N * prob.dim)) < 0.65


def test_dropout_scenario_engages_memory_fallback():
    """Dropout knocks workers out AFTER coverage repair, so regions go
    uncovered (tau_star=0) and the memory fallback carries the round —
    the Bernoulli-aggregation regime, now observable end to end."""
    N = 4
    prob = make_quadratic(KEY, num_workers=N, dim=32, kappa=20.0,
                          coupling=0.0, num_regions=4)
    scen = make_scenario("dropout:p=0.6", jax.random.PRNGKey(5), N)
    res = repro.run(prob, KEY, num_rounds=20, num_regions=4,
                   policy=PolicyConfig(keep_prob=0.4, tau_star=1),
                   cost=scen.cost)
    assert res.tau_star == 0                   # some region went uncovered
    assert int(np.asarray(res.max_stale).max()) >= 1
    assert np.isfinite(np.asarray(res.dist_sq)).all()
    assert float(res.dist_sq[-1]) < float(res.dist_sq[0])


# --------------------------------------------------------------------------
# 8 emulated devices (subprocess, the CI scenario-matrix leg)
# --------------------------------------------------------------------------

def _run_subprocess(code: str, timeout: int = 560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_scenario_matrix_sharded_8dev_and_hlo_invariant():
    """Stragglers + churn scenarios, controller-driven, on an 8-device
    ("data",) mesh: parity with the single-device closed loop, and the
    compiled HLO still issues exactly ONE param-sized all-reduce per
    round with controller state + telemetry in the scan carry."""
    code = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import json
import jax
import jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_mesh
assert jax.device_count() == 8, jax.devices()
KEY = jax.random.PRNGKey(0)
import repro
from repro.core import PolicyConfig, make_quadratic
from repro.hetero import make_controller, make_scenario
from repro.launch.hlo_analysis import collect_collectives

N = 8
prob = make_quadratic(KEY, num_workers=N, dim=48, kappa=80.0, coupling=0.0,
                      num_regions=6, grad_noise=0.1, hess_noise=0.1)
ctrl = make_controller('resource:keep=0.5,tau=1')
out = {"parity": {}}
for scen_spec in ('pareto-stragglers', 'churn:period=3,cohorts=4,alpha=1.2'):
    scen = make_scenario(scen_spec, jax.random.PRNGKey(3), N)
    kw = dict(num_rounds=12, num_regions=6, controller=ctrl, cost=scen.cost)
    ref = repro.run(prob, KEY, **kw)
    for ndev in (1, 8):
        mesh = make_mesh((ndev,), ('data',))
        for ov in (False, True):
            sh = repro.run(prob, KEY, engine="sharded", mesh=mesh, overlap=ov, **kw)
            out["parity"]["%s_%d_%s" % (scen.name, ndev, ov)] = {
                "xs_err": float(np.abs(np.asarray(sh.xs)
                                       - np.asarray(ref.xs)).max()),
                "comm_eq": bool((np.asarray(sh.comm_floats)
                                 == np.asarray(ref.comm_floats)).all()),
                "rt_eq": bool((np.asarray(sh.round_time)
                               == np.asarray(ref.round_time)).all()),
                "stale_eq": bool((np.asarray(sh.max_stale)
                                  == np.asarray(ref.max_stale)).all()),
                "tau_eq": bool(sh.tau_star == ref.tau_star),
            }

# HLO invariant with controller state in the carry: still exactly ONE
# param-sized all-reduce per scanned round
D, T = 512, 7
prob_h = make_quadratic(KEY, num_workers=N, dim=D, kappa=10.0,
                        coupling=0.0, num_regions=8)
mesh8 = make_mesh((8,), ('data',))
scen = make_scenario('pareto-stragglers', jax.random.PRNGKey(3), N)
out["hlo"] = {}
for leg, ov in (("seq", False), ("overlap", True)):
    txt = repro.lower(prob_h, KEY, engine="sharded", mesh=mesh8, num_rounds=T,
                             num_regions=8, controller=ctrl,
                             cost=scen.cost,
                             overlap=ov).compile().as_text()
    recs = collect_collectives(txt, default_trip=1)
    in_loop = [r for r in recs if r.kind == 'all-reduce' and r.multiplier > 1]
    param_sized = [r for r in in_loop if r.operand_bytes >= D * 4]
    out["hlo"][leg] = {
        "n_param_sized_in_loop": len(param_sized),
        "param_sized_multipliers": [r.multiplier for r in param_sized],
        "param_sized_bytes_slack": [r.operand_bytes - D * 4
                                    for r in param_sized],
        "small_in_loop_bytes": [r.operand_bytes for r in in_loop
                                if r.operand_bytes < D * 4],
        "rounds": T,
    }
print(json.dumps(out))
"""
    res = _run_subprocess(code)
    for name, r in res["parity"].items():
        assert r["xs_err"] <= 1e-6, (name, res)
        assert r["comm_eq"] and r["rt_eq"] and r["stale_eq"] \
            and r["tau_eq"], (name, res)
    for leg in ("seq", "overlap"):
        hlo = res["hlo"][leg]
        assert hlo["n_param_sized_in_loop"] == 1, (leg, hlo)
        assert hlo["param_sized_multipliers"] == [hlo["rounds"]], (leg, hlo)
        assert all(0 <= s <= 256 for s in hlo["param_sized_bytes_slack"]), \
            (leg, hlo)
        assert all(b <= 256 for b in hlo["small_in_loop_bytes"]), (leg, hlo)


# --------------------------------------------------------------------------
# satellite: generalized staleness policy regions
# --------------------------------------------------------------------------

def test_staleness_policy_custom_regions():
    """stale_regions generalizes the hardcoded region 0: the named
    regions are gated on the period, every other region is untouched,
    and the default (0,) reproduces the historical behavior."""
    pol_multi = PolicyConfig(name="staleness", keep_prob=0.9,
                             stale_period=3, stale_regions=(1, 3),
                             heterogeneous=False)
    starved = {1, 3}
    for t in range(1, 9):
        m = np.asarray(sample_masks(pol_multi, KEY, t, 8, 6))
        gate = (t % 4) == 3
        for q in starved:
            if not gate:
                assert not m[:, q].any(), (t, q)
    # un-starved columns keep the plain bernoulli draw
    pol_plain = PolicyConfig(name="bernoulli", keep_prob=0.9,
                             heterogeneous=False)
    m_stale = np.asarray(sample_masks(pol_multi, KEY, 1, 8, 6))
    m_plain = np.asarray(sample_masks(pol_plain, KEY, 1, 8, 6))
    keep = [q for q in range(6) if q not in starved]
    np.testing.assert_array_equal(m_stale[:, keep], m_plain[:, keep])
    # default config still gates region 0 only
    pol_default = PolicyConfig(name="staleness", keep_prob=0.9,
                               stale_period=3, heterogeneous=False)
    m = np.asarray(sample_masks(pol_default, KEY, 1, 8, 6))
    assert not m[:, 0].any() and m[:, 1:].any()
    # naming a region beyond Q raises
    with pytest.raises(ValueError, match="region 9"):
        sample_masks(PolicyConfig(name="staleness", stale_regions=(9,)),
                     KEY, 1, 8, 6)
    # routed through the controller shim it drives the staleness trace
    prob = make_quadratic(KEY, num_workers=8, dim=32, kappa=20.0,
                          coupling=0.0, num_regions=4)
    res = repro.run(prob, KEY, num_rounds=8, num_regions=4,
                   controller=PolicyController(PolicyConfig(
                       name="staleness", stale_period=3,
                       stale_regions=(0, 2))))
    assert int(np.asarray(res.max_stale).max()) >= 3
