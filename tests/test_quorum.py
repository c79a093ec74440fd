"""Semi-synchronous quorum aggregation: the bounded-delay commit rule,
the staleness-damped late fold, engine parity/degeneration, and the
pinned time-to-target win over the synchronous resource-proportional
controller (the acceptance bound: <= 0.8x simulated wall-clock on the
pareto-stragglers AND churn scenarios).

Slow leg (``-m slow``): the compiled-HLO proof that the quorum path adds
NO extra param-sized collective on an 8-emulated-device mesh — the late
buffer rides the scan carry and folds into the round's one existing
param psum, for both the sequential and the overlapped loop.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import (PolicyConfig, late_fold_updates, make_quadratic,
                        quorum_aggregate, server_aggregate,
                        staleness_weights)
from repro.hetero import (CostModel, make_controller, make_scenario,
                          quorum_deadline, quorum_split, time_to_target,
                          uniform_cost)

KEY = jax.random.PRNGKey(0)


def _problem(num_workers=8, dim=32, num_regions=4, **kw):
    return make_quadratic(KEY, num_workers=num_workers, dim=dim,
                          kappa=50.0, coupling=0.0,
                          num_regions=num_regions, **kw)


# ----------------------------------------------------- quorum_split units

def test_quorum_split_kth_order_statistic():
    """4 workers, 2 regions; with quorum=1.0, tau=1 the round commits
    once each region has ONE on-time coverer — the 2nd order statistic
    here — and the stragglers get ceil(t/deadline)-1 rounds of delay."""
    times = jnp.asarray([1.0, 2.0, 7.0, 3.0])
    masks = jnp.asarray([[1, 0], [0, 1], [1, 1], [1, 1]], bool)
    deadline, on_time, delays = quorum_split(times, masks, quorum=1.0,
                                             quorum_tau=1, max_delay=3)
    assert float(deadline) == 2.0            # worker 0 covers r0, 1 covers r1
    np.testing.assert_array_equal(np.asarray(on_time),
                                  [True, True, False, False])
    # worker 2: ceil(7/2)-1 = 3 late; worker 3: ceil(3/2)-1 = 1 late
    np.testing.assert_array_equal(np.asarray(delays), [0, 0, 3, 1])
    assert float(quorum_deadline(times, masks, quorum=1.0,
                                 quorum_tau=1)) == 2.0


def test_quorum_split_half_quorum():
    times = jnp.asarray([1.0, 2.0, 7.0, 3.0])
    masks = jnp.asarray([[1, 0], [0, 1], [1, 1], [1, 1]], bool)
    deadline, on_time, _ = quorum_split(times, masks, quorum=0.5,
                                        quorum_tau=1, max_delay=3)
    assert float(deadline) == 1.0            # one region covered suffices
    np.testing.assert_array_equal(np.asarray(on_time),
                                  [True, False, False, False])


def test_quorum_split_full_sync_degenerates_to_max():
    """quorum=1.0, quorum_tau=None == wait for every participant: the
    deadline is the synchronous max and nobody is ever late."""
    times = jnp.asarray([5.0, 1.0, 9.0, 2.0])
    masks = jnp.ones((4, 2), bool)
    deadline, on_time, delays = quorum_split(times, masks, quorum=1.0,
                                             quorum_tau=None, max_delay=2)
    assert float(deadline) == 9.0
    assert bool(on_time.all()) and int(delays.max()) == 0


def test_quorum_split_ignores_non_participants():
    """An all-False mask row never gates the deadline and reports 0
    delay; a participant-free round commits at time 0."""
    times = jnp.asarray([1.0, 100.0])
    masks = jnp.asarray([[1, 1], [0, 0]], bool)
    deadline, on_time, delays = quorum_split(times, masks, quorum=1.0,
                                             quorum_tau=None, max_delay=2)
    assert float(deadline) == 1.0
    np.testing.assert_array_equal(np.asarray(delays), [0, 0])
    empty = quorum_split(times, jnp.zeros((2, 2), bool), quorum=1.0,
                         quorum_tau=None, max_delay=2)
    assert float(empty[0]) == 0.0


def test_quorum_split_delays_clipped_past_max_delay():
    """delays saturate at max_delay + 1 — "too late to ever fold" is one
    bucket, so no folded contribution is ever staler than max_delay."""
    times = jnp.asarray([1.0, 1.0, 1000.0])
    masks = jnp.asarray([[1, 1], [1, 1], [1, 1]], bool)
    _, _, delays = quorum_split(times, masks, quorum=1.0, quorum_tau=2,
                                max_delay=2)
    assert int(delays[2]) == 3               # clipped, not ceil(1000)-1


# --------------------------------------------------- staleness-damped fold

def test_staleness_weights_bounded_delay():
    s = jnp.asarray([0, 1, 2, 3, 4])
    w = np.asarray(staleness_weights(s, 0.5, 3))
    np.testing.assert_allclose(w, [0.0, 0.5, 0.25, 0.125, 0.0])
    # gamma=0 drops ALL late work; max_stale of any folded term <= max_delay
    assert np.asarray(staleness_weights(s, 0.0, 3)).max() == 0.0
    assert np.asarray(staleness_weights(jnp.arange(100), 0.9, 4)
                      )[5:].max() == 0.0


def test_gamma_one_reconstructs_synchronous_mean():
    """On-time partial sum over the FULL count plus its late arrivals at
    gamma=1 equals the synchronous covered mean exactly — the late fold
    conserves mass."""
    k = jax.random.PRNGKey(3)
    N, d = 6, 12
    G = jax.random.normal(k, (N, d))
    Mx = jax.random.bernoulli(jax.random.fold_in(k, 1), 0.6, (N, d))
    C = jax.random.normal(jax.random.fold_in(k, 2), (N, d))
    on = jnp.asarray([True, True, False, True, False, True])
    delays = jnp.where(on, 0, jnp.asarray([0, 0, 1, 0, 2, 0]))
    sync_g, sync_C = server_aggregate(G * Mx, Mx, C)
    buf = jnp.zeros((2, d))
    g, new_C, buf = quorum_aggregate(G * Mx, Mx, C, on, delays, buf,
                                     gamma=1.0, max_delay=2)
    # covered coordinates: on-time partial + the scheduled late mass
    total = g + buf.sum(axis=0)
    count_on = (Mx & on[:, None]).sum(axis=0)
    cov = np.asarray(count_on > 0) & np.asarray(Mx.sum(axis=0) > 0)
    np.testing.assert_allclose(np.asarray(total)[cov],
                               np.asarray(sync_g)[cov], rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(new_C), np.asarray(sync_C))


def test_gamma_zero_drops_late_work_entirely():
    k = jax.random.PRNGKey(4)
    N, d = 6, 12
    G = jax.random.normal(k, (N, d))
    Mx = jax.random.bernoulli(jax.random.fold_in(k, 1), 0.6, (N, d))
    C = jax.random.normal(jax.random.fold_in(k, 2), (N, d))
    on = jnp.asarray([True, False, False, True, True, False])
    delays = jnp.where(on, 0, 1)
    g, _, buf = quorum_aggregate(G * Mx, Mx, C, on, delays,
                                 jnp.zeros((2, d)), gamma=0.0, max_delay=2)
    assert float(jnp.abs(buf).max()) == 0.0      # nothing ever folds
    m = Mx.astype(G.dtype)
    on_partial = ((G * m) * on.astype(G.dtype)[:, None]).sum(axis=0) \
        / jnp.maximum(m.sum(axis=0), 1.0)
    count_on = (Mx & on[:, None]).sum(axis=0)
    expect = jnp.where(count_on > 0, on_partial, C.mean(axis=0))
    np.testing.assert_allclose(np.asarray(g), np.asarray(expect),
                               rtol=1e-6)


def test_dropped_worker_does_not_refresh_memory():
    k = jax.random.PRNGKey(5)
    N, d = 4, 8
    G = jax.random.normal(k, (N, d))
    Mx = jnp.ones((N, d), bool)
    C = jnp.zeros((N, d))
    on = jnp.asarray([True, True, True, False])
    delays = jnp.asarray([0, 0, 0, 3])           # > max_delay=2: dropped
    _, new_C, buf = quorum_aggregate(G, Mx, C, on, delays,
                                     jnp.zeros((2, d)), gamma=0.5,
                                     max_delay=2)
    assert float(jnp.abs(new_C[3]).max()) == 0.0  # C row untouched
    np.testing.assert_array_equal(np.asarray(new_C[:3]),
                                  np.asarray(G[:3]))
    assert float(jnp.abs(buf).max()) == 0.0       # and nothing scheduled


def test_late_fold_slot_scheduling():
    """A contribution s rounds late lands in buffer row s-1 (due in round
    t+s) with weight gamma**s over the full-count denominator."""
    G = jnp.asarray([[2.0, 0.0], [0.0, 4.0]])
    Mx = jnp.ones((2, 2), bool)
    adds = late_fold_updates(G, Mx, jnp.asarray([2.0, 2.0]),
                             jnp.asarray([1, 2]), gamma=0.5, max_delay=3)
    np.testing.assert_allclose(
        np.asarray(adds),
        [[0.5 * 2.0 / 2, 0.0],                    # s=1: gamma^1 / count
         [0.0, 0.25 * 4.0 / 2],                   # s=2: gamma^2 / count
         [0.0, 0.0]])


# ------------------------------------------------- engine-level behavior

def test_quorum_one_is_bit_exact_synchronous():
    """quorum=1.0, quorum_tau=None degenerates to the synchronous engine
    BIT-EXACTLY (the static branch keeps the late buffer all-zero)."""
    prob = _problem()
    pol = PolicyConfig(keep_prob=0.5, tau_star=1, heterogeneous=True)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    for engine, kw in [("scan", {}), ("reference", {}),
                       ("sharded", {"mesh": mesh})]:
        sync = repro.run(prob, KEY, engine=engine, num_rounds=8,
                         num_regions=4, policy=pol, **kw)
        q1 = repro.run(prob, KEY, engine=engine, num_rounds=8,
                       num_regions=4, policy=pol, quorum=1.0,
                       quorum_tau=None, **kw)
        np.testing.assert_array_equal(np.asarray(sync.xs),
                                      np.asarray(q1.xs), err_msg=engine)
        np.testing.assert_array_equal(np.asarray(sync.round_time),
                                      np.asarray(q1.round_time),
                                      err_msg=engine)


def test_quorum_scan_matches_reference():
    """The compiled scan quorum branch against the eager host-loop oracle
    — same PRNG stream, same split/fold decisions (round_time and
    staleness telemetry exact), trajectories to the repo's standard
    compiled-vs-eager 1e-6."""
    prob = _problem(num_workers=8, dim=24)
    scen = make_scenario("pareto-stragglers", jax.random.PRNGKey(11), 8)
    kw = dict(num_rounds=10, num_regions=4, lr=0.8, cost=scen.cost,
              quorum=0.75, quorum_tau=1, gamma=0.5, max_delay=2,
              policy=PolicyConfig(keep_prob=0.5, tau_star=1,
                                  heterogeneous=True))
    a = repro.run(prob, KEY, engine="scan", **kw)
    b = repro.run(prob, KEY, engine="reference", **kw)
    np.testing.assert_allclose(np.asarray(a.xs), np.asarray(b.xs),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(a.round_time),
                               np.asarray(b.round_time), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(a.max_stale),
                                  np.asarray(b.max_stale))


def test_quorum_engine_parity_sharded_and_batch():
    prob = _problem(num_workers=8, dim=24)
    scen = make_scenario("pareto-stragglers", jax.random.PRNGKey(11), 8)
    kw = dict(num_rounds=10, num_regions=4, lr=0.8, cost=scen.cost,
              quorum=0.75, quorum_tau=1, gamma=0.5, max_delay=2,
              policy=PolicyConfig(keep_prob=0.5, tau_star=1,
                                  heterogeneous=True))
    scan = repro.run(prob, KEY, engine="scan", **kw)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))
    for overlap in (False, True):
        sh = repro.run(prob, KEY, engine="sharded", mesh=mesh,
                       overlap=overlap, **kw)
        np.testing.assert_allclose(np.asarray(sh.xs),
                                   np.asarray(scan.xs), atol=1e-6)
        np.testing.assert_array_equal(np.asarray(sh.round_time),
                                      np.asarray(scan.round_time))
    mesh2 = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                              ("data", "model"))
    two = repro.run(prob, KEY, engine="sharded2d", mesh=mesh2,
                    curvature="diag", **kw)
    ref_diag = repro.run(prob, KEY, engine="scan",
                         **{**kw, "curvature": "diag"})
    np.testing.assert_allclose(np.asarray(two.xs),
                               np.asarray(ref_diag.xs), atol=1e-6)
    batch = repro.run(prob, KEY[None], engine="batch", **kw)
    np.testing.assert_allclose(np.asarray(batch.xs[0]),
                               np.asarray(scan.xs), atol=2e-6)


def test_quorum_round_time_is_deadline_and_comm_is_full():
    """Under quorum the reported round_time is the commit deadline (k-th
    order statistic < synchronous max on a straggler cluster) while
    comm_floats still counts the FULL uplink — late traffic is delayed,
    not saved."""
    prob = _problem(num_workers=8, dim=32)
    rates = jnp.asarray([1.0] * 7 + [0.05])
    cost = CostModel(compute_rate=rates,
                     bandwidth=jnp.full((8,), np.inf))
    pol = PolicyConfig(keep_prob=0.6, tau_star=1, heterogeneous=True)
    kw = dict(num_rounds=8, num_regions=4, policy=pol, cost=cost)
    sync = repro.run(prob, KEY, **kw)
    q = repro.run(prob, KEY, quorum=0.75, quorum_tau=1, gamma=0.5,
                  max_delay=2, **kw)
    assert float(np.asarray(q.round_time).sum()) \
        < float(np.asarray(sync.round_time).sum())
    np.testing.assert_array_equal(np.asarray(q.comm_floats),
                                  np.asarray(sync.comm_floats))
    # staleness telemetry stays live under quorum (regions with no
    # on-time coverer ride the memory fallback and age)
    assert int(np.asarray(q.max_stale).max()) >= 0


# ---------------------------------------------------- the acceptance pin

def _pin_win(scenario_name):
    N = 16
    prob = make_quadratic(KEY, num_workers=N, dim=64, kappa=100.0,
                          coupling=0.0, num_regions=8)
    scen = make_scenario(scenario_name, jax.random.PRNGKey(101), N)
    ctrl = make_controller("resource:keep=0.5,tau=1")
    kw = dict(num_rounds=60, num_regions=8, lr=0.5, cost=scen.cost,
              controller=ctrl)
    sync = repro.run(prob, KEY, **kw)
    q = repro.run(prob, KEY, quorum=0.75, quorum_tau=1, gamma=0.5,
                  max_delay=4, **kw)
    target = 1e-8 * float(sync.dist_sq[0])
    t_sync = time_to_target(sync.dist_sq, sync.round_time, target)
    t_q = time_to_target(q.dist_sq, q.round_time, target)
    assert np.isfinite(t_sync) and np.isfinite(t_q), (t_sync, t_q)
    assert t_q <= 0.8 * t_sync, (scenario_name, t_q, t_sync)
    # bounded delay held: no folded contribution staler than max_delay,
    # and uncovered-region staleness stayed finite
    assert int(np.asarray(q.max_stale).max()) <= 2 * 4


def test_quorum_beats_sync_resource_on_pareto_stragglers():
    """The acceptance pin, straggler leg: quorum=0.75/tau=1, gamma=0.5,
    max_delay=4 over the SAME resource-proportional controller reaches
    the target loss in <= 0.8x the synchronous simulated wall-clock."""
    _pin_win("pareto-stragglers")


def test_quorum_beats_sync_resource_on_churn():
    """The acceptance pin, churn leg (the churn-stragglers scenario:
    rotating cohorts on pareto compute rates)."""
    _pin_win("churn-stragglers")


# ------------------------------------------------------------- slow: HLO

def _run_subprocess(code, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_quorum_adds_no_param_sized_collective():
    """The HLO proof on an 8-emulated-device ("data",) mesh: with quorum
    enabled (late buffer in the scan carry, per-round late folds) the
    compiled round loop still contains EXACTLY ONE param-sized in-loop
    all-reduce, sequential and overlapped alike."""
    code = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import json
import jax
import jax.numpy as jnp
import numpy as np
from repro.launch.mesh import make_mesh
assert jax.device_count() == 8, jax.devices()
import repro
from repro.core import PolicyConfig, make_quadratic
from repro.hetero import make_scenario
from repro.analysis import engine_contract, verify_contract

KEY = jax.random.PRNGKey(0)
D, T = 512, 7
prob = make_quadratic(KEY, num_workers=16, dim=D, kappa=80.0,
                      coupling=0.0, num_regions=8)
scen = make_scenario("pareto-stragglers", jax.random.PRNGKey(3), 16)
mesh = make_mesh((8,), ('data',))
pol = PolicyConfig(keep_prob=0.5, tau_star=1, heterogeneous=True)
out = {}
for overlap in (False, True):
    opts = repro.RanlOptions(num_rounds=T, num_regions=8, policy=pol,
                             overlap=overlap, quorum=0.75, quorum_tau=1,
                             gamma=0.5, max_delay=2, curvature="diag")
    low = repro.lower(prob, KEY, engine="sharded", mesh=mesh,
                      options=opts, cost=scen.cost)
    # the quorum contract is IDENTICAL to the synchronous one: the late
    # buffer and per-round fold ride the same single param-sized psum
    comm, mem = engine_contract("sharded", opts, dim=D, num_workers=16,
                                mesh_shape=(8,), mesh_axes=("data",))
    out[f"overlap={overlap}"] = verify_contract(low, comm, mem).to_json()
print(json.dumps(out))
"""
    out = _run_subprocess(code)
    for leg, rec in out.items():
        assert rec["ok"], (leg, rec)
        assert len(rec["facts"]["budgets"][0]["matched"]) == 1, (leg, rec)
