"""The unified dispatcher surface: ``repro.run`` / ``repro.lower`` and
``RanlOptions`` construction-time validation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core import PolicyConfig, make_quadratic
from repro.hetero import PolicyController, QuorumController

KEY = jax.random.PRNGKey(0)


def _problem(num_workers=8, dim=32, num_regions=4):
    return make_quadratic(KEY, num_workers=num_workers, dim=dim,
                          kappa=50.0, coupling=0.0,
                          num_regions=num_regions)


def _mesh1d():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",))


def _mesh2d():
    return jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))


def _same_result(a, b):
    for name in ("xs", "dist_sq", "losses", "coverage", "comm_floats",
                 "round_time", "max_stale"):
        va, vb = np.asarray(getattr(a, name)), np.asarray(getattr(b, name))
        np.testing.assert_array_equal(va, vb, err_msg=name)


# ---------------------------------------------------------------- options

def test_options_validate_at_construction():
    with pytest.raises(ValueError, match="curvature"):
        repro.RanlOptions(curvature="block")
    with pytest.raises(ValueError, match="projection"):
        repro.RanlOptions(projection="cholesky")
    with pytest.raises(ValueError, match="record_every"):
        repro.RanlOptions(record_every=0)
    with pytest.raises(ValueError, match="quorum="):
        repro.RanlOptions(quorum=1.5)
    with pytest.raises(ValueError, match="quorum="):
        repro.RanlOptions(quorum=0.0)
    with pytest.raises(ValueError, match="quorum_tau"):
        repro.RanlOptions(quorum=0.75, quorum_tau=0)
    with pytest.raises(ValueError, match="quorum_tau is set"):
        repro.RanlOptions(quorum_tau=2)
    with pytest.raises(ValueError, match="gamma"):
        repro.RanlOptions(gamma=1.5)
    with pytest.raises(ValueError, match="max_delay"):
        repro.RanlOptions(max_delay=0)
    with pytest.raises(TypeError, match="PolicyConfig"):
        repro.RanlOptions(policy={"keep_prob": 0.5})


def test_options_hashable_and_merged():
    a = repro.RanlOptions(num_rounds=5)
    assert hash(a) == hash(repro.RanlOptions(num_rounds=5))
    b = a.merged(quorum=0.75, quorum_tau=1)
    assert b.quorum == 0.75 and a.quorum is None
    with pytest.raises(TypeError, match="unknown RanlOptions field"):
        a.merged(rounds=5)
    spec = b.quorum_spec()
    assert (spec.quorum, spec.quorum_tau) == (0.75, 1)
    assert a.quorum_spec() is None


def test_run_engine_validation():
    prob = _problem()
    with pytest.raises(ValueError, match="unknown engine"):
        repro.run(prob, KEY, engine="fast")
    with pytest.raises(ValueError, match="needs a mesh"):
        repro.run(prob, KEY, engine="sharded")
    with pytest.raises(ValueError, match="takes no mesh"):
        repro.run(prob, KEY, engine="scan", mesh=_mesh1d())
    with pytest.raises(ValueError, match="overlap"):
        repro.run(prob, KEY, engine="scan", overlap=True)
    with pytest.raises(ValueError, match="reference"):
        repro.run(prob, KEY, engine="reference", curvature="diag")
    with pytest.raises(ValueError, match="reference"):
        repro.run(prob, KEY, engine="reference", projection="ns")
    with pytest.raises(TypeError, match="RanlOptions"):
        repro.run(prob, KEY, options={"num_rounds": 3})
    with pytest.raises(ValueError, match="no lowering surface"):
        repro.lower(prob, KEY, engine="scan")


def test_sharded2d_dense_rejects_eigh():
    prob = _problem()
    with pytest.raises(ValueError, match="d×d|dxd|NS|ns"):
        repro.run(prob, KEY, engine="sharded2d", mesh=_mesh2d(),
                  num_rounds=2, num_regions=4, projection="eigh")


def test_projection_uniform_across_engines():
    """The drift fix: projection= and ns_iters now reach every engine —
    scan/batch with projection='ns' matches the 2-D dense engine's
    default (the same matmul-only Newton–Schulz projection)."""
    prob = _problem()
    opts = repro.RanlOptions(num_rounds=6, num_regions=4,
                             projection="ns", ns_iters=40)
    scan = repro.run(prob, KEY, engine="scan", options=opts)
    twod = repro.run(prob, KEY, engine="sharded2d", mesh=_mesh2d(),
                     options=repro.RanlOptions(num_rounds=6,
                                               num_regions=4))
    np.testing.assert_allclose(np.asarray(scan.xs), np.asarray(twod.xs),
                               atol=2e-5)


def test_record_every_on_all_engines():
    """record_every thins the iterate traces (rounds 0, 1, every k-th,
    final) on every engine; per-round diagnostics stay full length."""
    prob = _problem()
    T, k = 7, 3
    kept = 2 + len({3, 6, 7})                      # x0, x1, rounds 3,6,7
    for engine, kw in [("scan", {}), ("reference", {}),
                       ("sharded", {"mesh": _mesh1d()}),
                       ("sharded2d", {"mesh": _mesh2d()})]:
        res = repro.run(prob, KEY, engine=engine, num_rounds=T,
                        num_regions=4, record_every=k, **kw)
        assert res.xs.shape == (kept, prob.dim), engine
        assert res.dist_sq.shape == (kept,), engine
        assert res.coverage.shape == (T,), engine
    batch = repro.run(prob, jax.random.split(KEY, 3), engine="batch",
                      num_rounds=T, num_regions=4, record_every=k)
    assert batch.xs.shape == (3, kept, prob.dim)
    full = repro.run(prob, KEY, num_rounds=T, num_regions=4)
    thin = repro.run(prob, KEY, num_rounds=T, num_regions=4,
                     record_every=k)
    np.testing.assert_array_equal(np.asarray(full.xs)[[0, 1, 4, 7, 8]],
                                  np.asarray(thin.xs))


# ----------------------------------------------------------- controllers

def test_quorum_controller_unwraps_onto_options():
    prob = _problem(num_workers=8)
    qc = QuorumController(inner=PolicyController(
        PolicyConfig(keep_prob=0.5, tau_star=1, heterogeneous=True)),
        quorum=0.75, quorum_tau=1, gamma=0.5, max_delay=2)
    wrapped = repro.run(prob, KEY, num_rounds=8, num_regions=4,
                        controller=qc)
    direct = repro.run(prob, KEY, num_rounds=8, num_regions=4,
                       controller=qc.inner, quorum=0.75, quorum_tau=1,
                       gamma=0.5, max_delay=2)
    _same_result(wrapped, direct)


def test_quorum_controller_double_set_conflict():
    prob = _problem()
    with pytest.raises(ValueError, match="configured twice"):
        repro.run(prob, KEY, controller=QuorumController(),
                  quorum=0.9)


def test_make_controller_quorum_spec():
    from repro.hetero import make_controller
    c = make_controller("quorum:q=0.8,gamma=0.25,delay=3,tau=2,"
                        "inner=resource;keep=0.5;tau=1")
    assert isinstance(c, QuorumController)
    assert (c.quorum, c.gamma, c.max_delay, c.quorum_tau) == \
        (0.8, 0.25, 3, 2)
    assert type(c.inner).__name__ == "ResourceProportionalController"
    assert make_controller("quorum:tau=none").quorum_tau is None
