"""Unit + property tests for the paper-faithful core (Algorithm 1)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import repro
from repro.launch.mesh import make_mesh
from repro.core import (PolicyConfig, blocked_cho_solve, blocked_cholesky,
                        ensure_coverage, expand_mask,
                        contiguous_regions, fisher_diag, make_quadratic,
                        project_psd, project_psd_ns, project_psd_sharded,
                        region_sizes, rounds_to_tol, run_gd,
                        run_newton_zero, sample_masks,
                        server_aggregate, solve_projected)
from repro.core.masks import worker_keep_probs

KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------------------
# Definition 4 projection
# --------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(2, 12), st.floats(0.01, 2.0), st.integers(0, 10_000))
def test_projection_floor_and_symmetry(d, mu, seed):
    a = jax.random.normal(jax.random.PRNGKey(seed), (d, d))
    p = project_psd(a, mu)
    w = np.linalg.eigvalsh(np.asarray(p))
    assert w.min() >= mu - 1e-4          # μI ⪯ [A]_μ
    np.testing.assert_allclose(p, p.T, atol=1e-5)


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 10), st.floats(0.05, 1.0), st.integers(0, 10_000))
def test_projection_idempotent(d, mu, seed):
    a = jax.random.normal(jax.random.PRNGKey(seed), (d, d))
    p1 = project_psd(a, mu)
    p2 = project_psd(p1, mu)
    np.testing.assert_allclose(p1, p2, atol=1e-4)


def test_projection_lemma1_contraction():
    """Lemma 1: ‖[H]_μ − H*‖_F ≤ ‖H − H*‖_F for H* ⪰ μI."""
    d, mu = 16, 0.5
    for seed in range(10):
        k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
        h = jax.random.normal(k1, (d, d))
        h = 0.5 * (h + h.T)
        hstar = project_psd(jax.random.normal(k2, (d, d)), mu)
        lhs = jnp.linalg.norm(project_psd(h, mu) - hstar)
        rhs = jnp.linalg.norm(0.5 * (h + h.T) - hstar)
        assert float(lhs) <= float(rhs) + 1e-5


def _straddling_matrix(d: int, mu: float, seed: int, *, gap: float = 1e-3,
                       top: float = 4.0):
    """Symmetric matrix with eigenvalues on BOTH sides of μ, including one
    exactly at μ and clusters ``gap`` away — the projection's interesting
    regime (everything strictly above μ is a no-op, everything below
    clamps)."""
    q, _ = jnp.linalg.qr(jax.random.normal(jax.random.PRNGKey(seed),
                                           (d, d)))
    lo = jnp.linspace(mu - top / 2, mu - gap, d // 2)
    hi = jnp.linspace(mu + gap, mu + top, d - d // 2 - 1)
    w = jnp.concatenate([lo, jnp.array([mu]), hi])
    return (q * w) @ q.T


def test_project_psd_ns_matches_eigh_across_regimes():
    """The matmul-only Newton–Schulz projection must agree with the eigh
    oracle to <= 1e-5 on matrices whose eigenvalues straddle μ — wide
    spreads, tight gaps (|λ−μ| = 1e-3), an eigenvalue exactly at μ, and
    asymmetric inputs (both symmetrize first)."""
    for d, mu, seed in ((8, 0.5, 0), (33, 1.0, 1), (64, 0.3, 2)):
        a = _straddling_matrix(d, mu, seed)
        ref = project_psd(a, mu)
        ns = project_psd_ns(a, mu)
        assert float(jnp.abs(ns - ref).max()) <= 1e-5, (d, mu)
        # the floor really holds
        w = np.linalg.eigvalsh(np.asarray(ns))
        assert w.min() >= mu - 1e-4
        # tol early-exit returns the same operator
        ns_tol = project_psd_ns(a, mu, tol=1e-7)
        assert float(jnp.abs(ns_tol - ref).max()) <= 1e-5
    # ill-conditioned: eigenvalues hugging mu at 1e-4 from both sides
    a = _straddling_matrix(32, 1.0, 3, gap=1e-4, top=10.0)
    assert float(jnp.abs(project_psd_ns(a, 1.0)
                         - project_psd(a, 1.0)).max()) <= 1e-5
    # asymmetric input goes through sym() exactly like project_psd
    r = jax.random.normal(KEY, (16, 16))
    assert float(jnp.abs(project_psd_ns(r, 0.4)
                         - project_psd(r, 0.4)).max()) <= 1e-5
    # all-zero input projects to exactly mu*I
    z = project_psd_ns(jnp.zeros((6, 6)), 0.7)
    np.testing.assert_allclose(z, 0.7 * jnp.eye(6), atol=1e-6)


def test_project_psd_ns_auto_iters_matches_fixed():
    """``ns_iters="auto"`` (the Frobenius-prescaled spectral bound) must
    match the conservative fixed-60 path and the eigh oracle across the
    same straddling regimes, with a genuinely smaller count at moderate d
    — and never a larger one."""
    from repro.core.hessian import ns_auto_iters, resolve_ns_iters
    for d in (8, 48, 64, 512):
        auto = ns_auto_iters(d)
        assert 10 <= auto <= 60, (d, auto)
    assert ns_auto_iters(64) < 60          # the point: fewer matmuls
    assert resolve_ns_iters("auto", 64) == ns_auto_iters(64)
    assert resolve_ns_iters(25, 64) == 25
    for d, mu, seed in ((8, 0.5, 0), (33, 1.0, 1), (64, 0.3, 2)):
        a = _straddling_matrix(d, mu, seed)
        ref = project_psd(a, mu)
        fixed = project_psd_ns(a, mu)                       # 60 iters
        auto = project_psd_ns(a, mu, num_iters="auto")
        assert float(jnp.abs(auto - ref).max()) <= 1e-5, (d, mu)
        assert float(jnp.abs(auto - fixed).max()) <= 1e-5, (d, mu)
    # hard case: eigenvalues hugging mu at 1e-4 on both sides
    a = _straddling_matrix(32, 1.0, 3, gap=1e-4, top=10.0)
    assert float(jnp.abs(project_psd_ns(a, 1.0, num_iters="auto")
                         - project_psd(a, 1.0)).max()) <= 1e-5
    # the auto knob flows through the engine entry points
    prob = make_quadratic(KEY, num_workers=4, dim=32, kappa=20.0,
                          coupling=0.0, num_regions=4)
    r_auto = repro.run(prob, KEY, num_rounds=4, num_regions=4,
                      projection="ns", ns_iters="auto")
    r_fix = repro.run(prob, KEY, num_rounds=4, num_regions=4,
                     projection="ns")
    np.testing.assert_allclose(np.asarray(r_auto.xs),
                               np.asarray(r_fix.xs), atol=1e-5)


def test_project_psd_sharded_single_device_matches_oracles():
    """On a 1-device mesh the panel-sharded projection must match the
    single-device NS oracle (same iteration, degenerate psums) and the
    eigh oracle to NS tolerance.  (The non-dividing-dim guard needs a
    multi-device model axis and is exercised in tests/test_multidevice.py
    alongside the engine's divisibility guards.)"""
    mesh = make_mesh((1,), ("model",))
    a = _straddling_matrix(24, 0.6, 4)
    sh = project_psd_sharded(a, 0.6, mesh=mesh)
    assert float(jnp.abs(sh - project_psd_ns(a, 0.6)).max()) <= 1e-6
    assert float(jnp.abs(sh - project_psd(a, 0.6)).max()) <= 1e-5


def test_solve_projected_matches_inverse():
    a = project_psd(jax.random.normal(KEY, (8, 8)), 0.3)
    g = jax.random.normal(jax.random.fold_in(KEY, 1), (8,))
    np.testing.assert_allclose(solve_projected(a, g),
                               jnp.linalg.solve(a, g), rtol=2e-4)


@pytest.mark.parametrize("d", [1, 5, 37, 48, 63])
@pytest.mark.parametrize("block", [1, 7, 16, 64])
def test_blocked_cholesky_matches_jax_scipy(d, block):
    """Blocked right-looking factorization + blocked triangular solves ==
    the jax.scipy dense path, across odd / non-divisible d and block
    sizes (incl. block > d) — the schedule the dimension-sharded engine
    distributes over the model axis."""
    a = project_psd(jax.random.normal(jax.random.fold_in(KEY, 13 * d), (d, d)),
                    0.4)
    L = blocked_cholesky(a, block)
    np.testing.assert_allclose(np.asarray(L),
                               np.asarray(jnp.linalg.cholesky(a)),
                               rtol=2e-4, atol=1e-5)
    g = jax.random.normal(jax.random.fold_in(KEY, d), (d,))
    x = blocked_cho_solve(L, g, block)
    ref = jax.scipy.linalg.cho_solve(jax.scipy.linalg.cho_factor(a), g)
    np.testing.assert_allclose(np.asarray(x), np.asarray(ref),
                               rtol=2e-4, atol=1e-5)
    # the factor is genuinely lower triangular (no junk above the diagonal)
    assert np.allclose(np.triu(np.asarray(L), 1), 0.0)


def test_blocked_cholesky_edge_blocks():
    """Explicit edge regimes: block_size=1 degenerates to the scalar
    right-looking algorithm; block_size > d factors in one shot equal to
    the library call; block_size < 1 is rejected by factor AND solve."""
    d = 9
    a = project_psd(jax.random.normal(KEY, (d, d)), 0.5)
    g = jax.random.normal(jax.random.fold_in(KEY, 2), (d,))
    ref_l = jnp.linalg.cholesky(a)
    ref_x = jax.scipy.linalg.cho_solve(jax.scipy.linalg.cho_factor(a), g)
    # block_size = 1: d scalar pivots, still the exact factor
    L1 = blocked_cholesky(a, 1)
    np.testing.assert_allclose(np.asarray(L1), np.asarray(ref_l),
                               rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(blocked_cho_solve(L1, g, 1)),
                               np.asarray(ref_x), rtol=2e-4, atol=1e-5)
    # block_size > d: single block, bitwise the library factorization
    Lbig = blocked_cholesky(a, d + 5)
    np.testing.assert_array_equal(np.asarray(Lbig), np.asarray(ref_l))
    np.testing.assert_allclose(
        np.asarray(blocked_cho_solve(Lbig, g, d + 5)), np.asarray(ref_x),
        rtol=2e-4, atol=1e-5)
    # mixed block sizes between factor and solve compose fine
    np.testing.assert_allclose(
        np.asarray(blocked_cho_solve(L1, g, d + 5)), np.asarray(ref_x),
        rtol=2e-4, atol=1e-5)
    for bad in (0, -3):
        with pytest.raises(ValueError, match="block_size"):
            blocked_cholesky(a, bad)
        with pytest.raises(ValueError, match="block_size"):
            blocked_cho_solve(ref_l, g, bad)


def test_fisher_diag_matches_manual_mean_of_squared_grads():
    """fisher_diag == mean over keys of elementwise-squared grads, with
    the params pytree structure preserved (previously untested)."""
    params = {"w": jnp.arange(6.0).reshape(2, 3), "b": jnp.ones(4)}

    def grad_fn(p, key):
        k1, k2 = jax.random.split(key)
        return {"w": p["w"] * jax.random.normal(k1, p["w"].shape),
                "b": p["b"] + jax.random.normal(k2, p["b"].shape)}

    keys = jax.random.split(KEY, 5)
    out = fisher_diag(grad_fn, params, keys)
    assert set(out) == {"w", "b"}
    assert out["w"].shape == (2, 3) and out["b"].shape == (4,)
    want_w = np.mean([np.asarray(grad_fn(params, k)["w"]) ** 2
                      for k in keys], axis=0)
    want_b = np.mean([np.asarray(grad_fn(params, k)["b"]) ** 2
                      for k in keys], axis=0)
    np.testing.assert_allclose(np.asarray(out["w"]), want_w, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out["b"]), want_b, rtol=1e-5)


def test_fisher_diag_accepts_key_list_and_is_nonnegative():
    """``keys`` may be any stackable sequence; the estimate is a mean of
    squares, so it is elementwise >= 0, and a single key reproduces that
    key's squared gradient exactly."""
    params = (jnp.array([1.0, -2.0, 3.0]),)

    def grad_fn(p, key):
        return (p[0] * jax.random.rademacher(key, p[0].shape,
                                             dtype=p[0].dtype),)

    keys = [jax.random.fold_in(KEY, i) for i in range(3)]
    out = fisher_diag(grad_fn, params, keys)
    assert (np.asarray(out[0]) >= 0).all()
    # rademacher^2 == 1, so the fisher diagonal is exactly params^2
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.asarray(params[0]) ** 2, rtol=1e-6)
    one = fisher_diag(grad_fn, params, [KEY])
    g = grad_fn(params, KEY)[0]
    np.testing.assert_allclose(np.asarray(one[0]), np.asarray(g) ** 2,
                               rtol=1e-6)


# --------------------------------------------------------------------------
# regions / masks
# --------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(1, 64), st.integers(1, 16))
def test_region_partition_covers_every_coordinate(d, q):
    q = min(q, d)
    ids = contiguous_regions(d, q)
    assert ids.shape == (d,)
    assert int(ids.min()) == 0 and int(ids.max()) == q - 1
    sizes = np.asarray(region_sizes(ids, q))
    assert sizes.sum() == d and sizes.min() >= 1
    assert (np.diff(np.asarray(ids)) >= 0).all()   # contiguous


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 8), st.integers(1, 12), st.integers(1, 6),
       st.integers(0, 1000))
def test_ensure_coverage_guarantees_tau(n, q, tau, seed):
    tau = min(tau, n)
    m = jax.random.uniform(jax.random.PRNGKey(seed), (n, q)) < 0.2
    fixed = ensure_coverage(m, tau)
    assert (np.asarray(fixed.sum(axis=0)) >= tau).all()
    # repair only adds coverage, never removes
    assert bool(jnp.all(fixed | ~m))


def test_ensure_coverage_rejects_impossible_tau():
    """tau_star > N is unsatisfiable: the old code silently capped the
    repair at N (counts of 3 for tau_star=5, N=3) — it must raise."""
    m = jnp.zeros((3, 4), bool)
    with pytest.raises(ValueError, match="tau_star=5 exceeds num_workers=3"):
        ensure_coverage(m, 5)
    # boundary: tau_star == N is fine and fully covers
    full = ensure_coverage(m, 3)
    assert (np.asarray(full.sum(axis=0)) == 3).all()


def test_worker_keep_probs_mean_is_base():
    """Docstring promise: the heterogeneous draw has mean ``base`` for all
    base in (0, 1] — the old one-sided clip at 1.0 biased base > 2/3 low."""
    n = 40_000
    for base in (0.2, 0.5, 0.7, 0.8, 0.9, 0.95, 1.0):
        probs = np.asarray(worker_keep_probs(KEY, n, base, True))
        assert (probs >= 0.0).all() and (probs <= 1.0).all(), base
        width = min(base / 2, 1.0 - base)        # uniform on base +- width
        tol = 3 * (2 * width) / np.sqrt(12 * n) + 1e-6
        assert abs(probs.mean() - base) < tol, (base, probs.mean())
    # homogeneous path: exactly base
    assert (np.asarray(worker_keep_probs(KEY, 8, 0.9, False)) == 0.9).all()


def test_mask_policies_shapes_and_determinism():
    for name in ("bernoulli", "fixed_k", "roundrobin", "full", "staleness"):
        pol = PolicyConfig(name=name, keep_prob=0.5, keep_k=2,
                           stale_period=2)
        m1 = sample_masks(pol, KEY, 3, 8, 6)
        m2 = sample_masks(pol, KEY, 3, 8, 6)
        assert m1.shape == (8, 6) and m1.dtype == jnp.bool_
        np.testing.assert_array_equal(m1, m2)       # deterministic in key
    full = sample_masks(PolicyConfig(name="full"), KEY, 0, 4, 5)
    assert bool(full.all())


# --------------------------------------------------------------------------
# server aggregation (Algorithm 1 lines 15–22)
# --------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(1, 8), st.integers(1, 40), st.integers(0, 10_000))
def test_full_coverage_equals_plain_mean(n, d, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    g = jax.random.normal(k1, (n, d))
    c = jax.random.normal(k2, (n, d))
    masks = jnp.ones((n, d), bool)
    out, c_new = server_aggregate(g, masks, c)
    np.testing.assert_allclose(out, g.mean(axis=0), rtol=2e-5, atol=1e-6)
    np.testing.assert_array_equal(c_new, g)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 8), st.integers(2, 40), st.integers(0, 10_000))
def test_uncovered_regions_use_memory_mean(n, d, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    g = jax.random.normal(k1, (n, d))
    c = jax.random.normal(k2, (n, d))
    masks = jnp.zeros((n, d), bool)
    out, c_new = server_aggregate(g * 0.0, masks, c)
    np.testing.assert_allclose(out, c.mean(axis=0), rtol=2e-5, atol=1e-6)
    np.testing.assert_array_equal(c_new, c)        # memory untouched


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 8), st.integers(4, 32), st.integers(0, 10_000),
       st.floats(0.1, 0.9))
def test_aggregation_per_coordinate_semantics(n, d, seed, p):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    g = jax.random.normal(ks[0], (n, d))
    c = jax.random.normal(ks[1], (n, d))
    masks = jax.random.uniform(ks[2], (n, d)) < p
    gm = jnp.where(masks, g, 0.0)
    out, c_new = server_aggregate(gm, masks, c)
    gn, cn, outn = map(np.asarray, (gm, c, out))
    mn = np.asarray(masks)
    for j in range(d):
        cov = mn[:, j]
        if cov.any():
            exp = gn[cov, j].mean()
        else:
            exp = cn[:, j].mean()
        assert abs(outn[j] - exp) < 1e-4
    np.testing.assert_array_equal(np.asarray(c_new),
                                  np.where(mn, gn, cn))


# --------------------------------------------------------------------------
# convergence claims (Theorem 1)
# --------------------------------------------------------------------------

def test_ranl_linear_convergence_region_aligned():
    prob = make_quadratic(KEY, num_workers=8, dim=64, kappa=100.0,
                          coupling=0.0, num_regions=8)
    res = repro.run(prob, KEY, num_rounds=40, num_regions=8,
                   policy=PolicyConfig(keep_prob=0.5, tau_star=1,
                                       heterogeneous=False))
    assert float(res.dist_sq[-1]) < 1e-9 * float(res.dist_sq[0])


def test_ranl_condition_number_independence():
    rounds = {}
    for kappa in (10.0, 1000.0):
        prob = make_quadratic(KEY, num_workers=8, dim=32, kappa=kappa,
                              coupling=0.0, num_regions=4)
        res = repro.run(prob, KEY, num_rounds=60, num_regions=4,
                       policy=PolicyConfig(keep_prob=0.7, tau_star=1,
                                           heterogeneous=False))
        rounds[kappa] = rounds_to_tol(res.dist_sq, 1e-8)
        _, dg = run_gd(prob, KEY, num_rounds=60)
        if kappa >= 1000:
            assert rounds_to_tol(dg, 1e-8) >= 59    # GD stalls at high κ
    assert abs(rounds[10.0] - rounds[1000.0]) <= 10


def test_ranl_full_mask_matches_newton_zero():
    """RANL with full masks must be exactly NewtonZero (same seeds)."""
    prob = make_quadratic(KEY, num_workers=8, dim=32, kappa=50.0,
                          hess_noise=0.1, grad_noise=0.05)
    res = repro.run(prob, KEY, num_rounds=10, num_regions=4,
                   policy=PolicyConfig(name="full"))
    d = np.asarray(res.dist_sq)
    _, dz = run_newton_zero(prob, KEY, num_rounds=10)
    dz = np.asarray(dz)
    # identical init phase (same seeds, full masks == no pruning)
    np.testing.assert_allclose(d[1], dz[1], rtol=1e-5)
    # both settle at the same stochastic floor (Δ > 0 here)
    assert d[-1] < 1e-4 * d[0]
    assert dz[-1] < 1e-4 * dz[0]


def test_sample_masks_trace_safe_in_scan():
    """Masks drawn with a traced round index inside lax.scan must be
    bit-identical to eager sampling at the same concrete round."""
    for name in ("bernoulli", "fixed_k", "roundrobin", "full", "staleness"):
        pol = PolicyConfig(name=name, keep_prob=0.5, keep_k=2,
                           stale_period=2, tau_star=1)

        def body(c, t):
            return c, sample_masks(pol, jax.random.fold_in(KEY, t), t, 8, 6)

        _, scanned = jax.lax.scan(body, 0, jnp.arange(1, 6))
        for i, t in enumerate(range(1, 6)):
            eager = sample_masks(pol, jax.random.fold_in(KEY, t), t, 8, 6)
            np.testing.assert_array_equal(np.asarray(scanned[i]),
                                          np.asarray(eager))


# --------------------------------------------------------------------------
# scan-compiled engine vs the host-loop reference driver
# --------------------------------------------------------------------------

def test_scan_engine_reproduces_reference_trajectory():
    """The compiled engine must reproduce the seed host-loop trajectory on
    a fixed key (dense path; allclose atol 1e-6, diagnostics exact)."""
    prob = make_quadratic(KEY, num_workers=8, dim=48, kappa=80.0,
                          coupling=0.0, num_regions=6, grad_noise=0.1,
                          hess_noise=0.1)
    for pol in (PolicyConfig(keep_prob=0.5, tau_star=1,
                             heterogeneous=False),
                PolicyConfig(name="roundrobin"),
                PolicyConfig(name="full"),
                PolicyConfig(name="staleness", keep_prob=0.6,
                             stale_period=2),
                PolicyConfig(name="fixed_k", keep_k=2)):
        res = repro.run(prob, KEY, num_rounds=12, num_regions=6, policy=pol)
        ref = repro.run(prob, KEY, engine="reference", num_rounds=12, num_regions=6,
                                 policy=pol)
        np.testing.assert_allclose(res.xs, ref.xs, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(res.dist_sq, ref.dist_sq,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(res.losses, ref.losses,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(res.comm_floats),
                                      np.asarray(ref.comm_floats))
        np.testing.assert_allclose(res.coverage, ref.coverage, atol=1e-7)
        assert res.tau_star == ref.tau_star


def test_batch_engine_matches_single_runs():
    """batch-engine rows match per-seed scan runs (same compiled math up
    to float32 solve accuracy) and carry per-seed diagnostics."""
    prob = make_quadratic(KEY, num_workers=8, dim=32, kappa=50.0,
                          coupling=0.0, num_regions=4, grad_noise=0.1)
    pol = PolicyConfig(keep_prob=0.5, tau_star=1)
    keys = jax.random.split(KEY, 4)
    bat = repro.run(prob, keys, engine="batch", num_rounds=10, num_regions=4,
                         policy=pol)
    assert bat.xs.shape == (4, 12, 32)
    assert bat.coverage.shape == (4, 10)
    for b in range(4):
        single = repro.run(prob, keys[b], num_rounds=10, num_regions=4,
                          policy=pol)
        np.testing.assert_allclose(bat.xs[b], single.xs, atol=2e-4)
        np.testing.assert_array_equal(np.asarray(bat.comm_floats[b]),
                                      np.asarray(single.comm_floats))
        assert int(bat.tau_star[b]) == single.tau_star


def test_diag_curvature_kernel_matches_oracle_path():
    """curvature='diag' through the fused Pallas kernel equals the pure-jnp
    oracle path, and converges linearly on a coordinate-diagonal problem
    (where the Hutchinson diagonal is exact)."""
    prob = make_quadratic(KEY, num_workers=8, dim=32, kappa=50.0,
                          coupling=0.0, num_regions=32)
    pol = PolicyConfig(keep_prob=0.5, tau_star=1)
    res_k = repro.run(prob, KEY, num_rounds=30, num_regions=8,
                     curvature="diag", use_kernel=True, policy=pol)
    res_o = repro.run(prob, KEY, num_rounds=30, num_regions=8,
                     curvature="diag", use_kernel=False, policy=pol)
    np.testing.assert_allclose(res_k.xs, res_o.xs, rtol=1e-6, atol=1e-6)
    assert float(res_k.dist_sq[-1]) < 1e-9 * float(res_k.dist_sq[0])


def test_diag_batch_runs_under_vmap():
    """The Pallas update kernel stays vmappable: batched diag runs work."""
    prob = make_quadratic(KEY, num_workers=4, dim=16, kappa=10.0,
                          coupling=0.0, num_regions=16)
    keys = jax.random.split(KEY, 3)
    bat = repro.run(prob, keys, engine="batch", num_rounds=5, num_regions=4,
                         curvature="diag")
    assert bat.xs.shape == (3, 7, 16)
    assert np.isfinite(np.asarray(bat.dist_sq)).all()


def test_tau_star_zero_when_region_goes_uncovered():
    """Regression (confirmed repro): uncovered regions used to map to N in
    the per-round min, so tau_star reported >= 1 even while 6/8 staleness
    rounds left region 0 with zero coverage.  tau_star must be 0 the
    moment ANY region goes uncovered; tau_covered keeps the covered-only
    (memory-fallback) min."""
    prob = make_quadratic(KEY, num_workers=4, dim=32, kappa=20.0,
                          coupling=0.0, num_regions=4)
    pol = PolicyConfig(name="staleness", stale_period=3)
    res = repro.run(prob, KEY, num_rounds=8, num_regions=4, policy=pol)
    cov = np.asarray(res.coverage)
    assert (cov < 1.0).any(), "staleness policy must uncover region 0"
    assert res.tau_star == 0
    assert res.tau_covered >= 1            # covered regions stayed covered
    # engine agreement: host-loop reference and batch engine report the same
    ref = repro.run(prob, KEY, engine="reference", num_rounds=8, num_regions=4,
                             policy=pol)
    assert ref.tau_star == 0 and ref.tau_covered == res.tau_covered
    bat = repro.run(prob, jnp.asarray(KEY)[None], engine="batch", num_rounds=8,
                         num_regions=4, policy=pol)
    assert int(bat.tau_star[0]) == res.tau_star
    assert int(bat.tau_covered[0]) == res.tau_covered
    # fully-covered runs are unchanged: tau_star == tau_covered >= 1
    full = repro.run(prob, KEY, num_rounds=8, num_regions=4,
                    policy=PolicyConfig(name="full"))
    assert full.tau_star == full.tau_covered == 4


def test_staleness_floor_monotone():
    prob = make_quadratic(KEY, num_workers=8, dim=64, kappa=100.0,
                          coupling=0.0, num_regions=8)
    floors = []
    for period in (0, 2, 4):
        res = repro.run(prob, KEY, num_rounds=40, num_regions=8,
                       policy=PolicyConfig(name="staleness", keep_prob=0.5,
                                           stale_period=period,
                                           heterogeneous=False))
        floors.append(float(np.asarray(res.dist_sq)[-5:].mean()))
    assert floors[0] < floors[1] < floors[2]


# --------------------------------------------------------------------------
# the round loop's worker gradients: fused Pallas pass vs vmapped oracle
# --------------------------------------------------------------------------

def _rows_minor(monkeypatch):
    """The CPU lays X out row-major, which the kernel does not read; have
    the layout query answer as the TPU does at the convex cell's shape."""
    from repro.kernels import logistic_grad
    monkeypatch.setattr(logistic_grad, "rows_minor_layout",
                        lambda *a, **kw: True)


def _kernel_in_interpret_mode(monkeypatch):
    """Off a TPU the engines keep the jnp path; ask for the interpret-mode
    kernel the way a test may, by steering the round loop's statics."""
    from repro.core import ranl
    scan_args = ranl._scan_args

    def with_interpret(*a, **kw):
        args, static = scan_args(*a, **kw)
        return args, dict(static, interpret=True)

    monkeypatch.setattr(ranl, "_scan_args", with_interpret)
    _rows_minor(monkeypatch)


def test_scan_engine_fused_logistic_grads_match_vmap(monkeypatch):
    """Dense logistic rounds through the one-pass kernel follow the
    vmapped ``worker_grad`` rounds: iterates within 1e-5 over 10 rounds,
    equal uplink counts; each run records the path it took.  1,100 rows
    per worker make three row tiles, the last one ragged and masked."""
    from repro.core import make_logistic
    from repro.kernels.logistic_grad import BLOCK_N
    prob = make_logistic(KEY, num_workers=4, per_worker=1100, dim=24,
                         lam=0.05, heterogeneity=0.5)
    assert 2 * BLOCK_N < 1100 < 3 * BLOCK_N
    pol = PolicyConfig(keep_prob=0.5, tau_star=1)
    _kernel_in_interpret_mode(monkeypatch)
    fused = repro.run(prob, KEY, num_rounds=10, num_regions=4, policy=pol)
    plain = repro.run(prob, KEY, num_rounds=10, num_regions=4, policy=pol,
                      use_kernel=False)
    assert fused.grad_path == "fused" and plain.grad_path == "vmap"
    np.testing.assert_allclose(fused.xs, plain.xs, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(fused.comm_floats),
                                  np.asarray(plain.comm_floats))


def test_quadratic_round_loop_keeps_vmap_path(monkeypatch):
    """Quadratic problems take the vmapped oracle whatever the kernel
    statics say: the same jaxpr as ``vmap(worker_grad)`` and no
    ``pallas_call`` in the round program."""
    prob = make_quadratic(KEY, num_workers=4, dim=16, kappa=10.0,
                          coupling=0.0, num_regions=4, grad_noise=0.1)
    N, d = prob.num_workers, prob.dim
    xp = jnp.ones((N, d))
    keys = jax.random.split(KEY, N)
    old = jax.make_jaxpr(lambda xp, k: jax.vmap(
        prob.worker_grad, in_axes=(0, 0, 0))(jnp.arange(N), xp, k))(xp, keys)
    new = jax.make_jaxpr(lambda xp, k: prob.pruned_grads(
        xp, k, use_kernel=True, interpret=True))(xp, keys)
    assert str(new) == str(old)

    from repro.core import ranl
    _kernel_in_interpret_mode(monkeypatch)
    opts = repro.RanlOptions(num_rounds=3, num_regions=4)
    args, static = ranl._scan_args(prob, KEY, opts)
    assert static["interpret"] is True and static["use_kernel"]
    jaxpr = jax.make_jaxpr(functools.partial(ranl._scan_rounds,
                                             **static))(*args)
    assert "pallas_call" not in str(jaxpr)
    assert repro.run(prob, KEY, num_rounds=3,
                     num_regions=4).grad_path == "vmap"


def test_batch_engine_keeps_vmap_grads_on_a_seed_sharded_mesh(monkeypatch):
    """XLA cannot partition a Pallas call: the batch engine's program
    holds the kernel when its seeds sit on one device and none when
    they are spread over two, where each device runs the vmapped
    gradients of its own seeds."""
    from jax.sharding import AbstractMesh
    from repro.core import make_logistic
    prob = make_logistic(KEY, num_workers=4, per_worker=300, dim=24,
                         lam=0.05, heterogeneity=0.5)
    keys = jax.random.split(KEY, 2)
    _rows_minor(monkeypatch)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def program(n_dev):
        return str(repro.trace(prob, keys, engine="batch", num_rounds=2,
                               num_regions=4,
                               mesh=AbstractMesh((n_dev,), ("data",))))

    assert "pallas_call" in program(1)
    assert "pallas_call" not in program(2)


def test_logistic_keeps_vmap_path_where_x_is_not_rows_minor():
    """X laid out row-major (the CPU's layout) would need a copy of X per
    call to feed the kernel: the problem keeps the vmapped oracle even
    where the interpret-mode kernel is asked for."""
    from repro.core import make_logistic
    from repro.kernels.logistic_grad import rows_minor_layout
    prob = make_logistic(KEY, num_workers=4, per_worker=300, dim=24,
                         lam=0.05, heterogeneity=0.5)
    assert not rows_minor_layout(prob.X.shape)
    assert prob.grad_path(True, True) == "vmap"
