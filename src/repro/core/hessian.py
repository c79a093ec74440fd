"""Hessian utilities: Definition 4 projection, one-shot estimators.

``project_psd``/``[A]_μ`` projects a symmetric matrix onto
{M : Mᵀ = M, μI ⪯ M} by eigenvalue clamping — exactly the paper's
``[A]_μ := [A − μI]_0 + μI``.  For the scalable (diagonal) path the same
operator specializes to ``max(h, μ)`` elementwise.

``project_psd_ns`` computes the SAME operator without an
eigendecomposition, via the identity

    [A]_μ = (sym(A) + μI + |sym(A) − μI|) / 2,

where the matrix absolute value ``|B| = B·sign(B)`` comes from a
Newton–Schulz polar-sign iteration — nothing but symmetric d×d matmuls.
That makes the projection shardable: ``project_psd_sharded`` runs the
identical iteration over model-axis row panels (per-device
``(d/n_model, d)`` slabs, psums of panel products), so no device ever
materializes a replicated d×d buffer — the piece that turns the
dimension-sharded RANL engine's dense init from a replicated-eigh caveat
into a real at-scale path.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def sym_eigh(a):
    """Repo-wide chokepoint for dense symmetric eigendecompositions.

    ``jnp.linalg.eigh`` is an O(d³) replicated factorization — exactly
    the primitive the dimension-sharded paths must never reach — so the
    repo lint (``repro.analysis.lint``) confines direct calls to this
    module; every other caller routes through here, keeping the
    audit surface one grep wide.
    """
    return jnp.linalg.eigh(a)


def symmetrize(a):
    return 0.5 * (a + a.T)


def project_psd(a, mu: float):
    """[A]_μ (Definition 4): clamp eigenvalues of sym(A) at μ."""
    w, v = jnp.linalg.eigh(symmetrize(a))
    w = jnp.maximum(w, mu)
    return (v * w) @ v.T


def _ns_sign_step(x):
    """One cubic Newton–Schulz step of the matrix sign iteration.

    x ↦ 1.5x − 0.5x³ maps [−1, 1] into itself and drives every eigenvalue
    to sign(λ) (0 stays 0): monotone and safe for ‖X₀‖₂ ≤ 1, unlike the
    tuned higher-order polynomials (Muon-style) that trade a loose ±1
    band for speed — the projection needs the accurate fixed point.

    The iterate is re-symmetrized every step: the sign map amplifies
    ANTIsymmetric rounding drift by 1.5 − 0.5·σᵢσⱼ = 2 per step across
    mixed-sign eigenspaces (σᵢσⱼ = −1), so without this the iteration
    blows up in float32 after ~50 steps whenever the spectrum straddles
    the shift — precisely the projection's interesting case.
    """
    return symmetrize(1.5 * x - 0.5 * (x @ (x @ x)))


def ns_auto_iters(dim: int, dtype=jnp.float32) -> int:
    """Newton–Schulz iteration count from the Frobenius-prescaled
    spectral bound.

    The iterate starts at ``B/‖B‖_F``, and ``‖B‖_F ≤ √d·‖B‖_2``, so every
    eigenvalue the projection must resolve (relative magnitude ≥ rtol of
    the spectral norm, anything smaller contributes ≤ |λ−μ|/2 error by
    construction — see ``project_psd_ns``) starts at ≥ rtol/√d.  The
    linear phase of the cubic sign map grows a small eigenvalue by ×1.5
    per step until it reaches O(1), after which convergence is quadratic
    (a handful of steps).  ``rtol = eps^0.75`` (≈6e-6 in f32) matches the
    ≤1e-5-vs-eigh accuracy the fixed-count tests pin, so

        iters = ceil(log(√d / rtol) / log 1.5) + 6

    replaces the conservative fixed 60 with a d-aware count (e.g. 41 at
    d=48, 44 at d=512), capped at 60 so "auto" is never slower than the
    old default.
    """
    rtol = float(jnp.finfo(dtype).eps) ** 0.75
    linear = math.log(math.sqrt(float(dim)) / rtol) / math.log(1.5)
    return min(60, max(10, math.ceil(linear) + 6))


def resolve_ns_iters(num_iters, dim: int, dtype=jnp.float32) -> int:
    """``"auto"`` -> ``ns_auto_iters(dim)``; anything else -> int."""
    if num_iters == "auto":
        return ns_auto_iters(dim, dtype)
    return int(num_iters)


def project_psd_ns(a, mu: float, *, num_iters: int | str = 60,
                   tol: float | None = None):
    """[A]_μ by matmuls only: Newton–Schulz |·| instead of ``eigh``.

    ``B = sym(a) − μI`` is scaled by its Frobenius norm (≥ spectral, so
    the iterate starts inside the NS basin), ``sign(B)`` is iterated
    ``num_iters`` times, and ``[A]_μ = (B + B·sign(B))/2 + μI``.
    Eigenvalues straddling μ are exactly the easy case (|λ−μ| bounded
    away from 0 converges in a few steps); an eigenvalue AT μ is also
    exact (0 is a fixed point and contributes max(0, 0) = 0).  The only
    slow direction is |λ−μ| ≪ ‖B‖ — there the absolute error is ≤ |λ−μ|/2,
    i.e. small in the same measure, and more ``num_iters`` shrink it
    geometrically (×2/3 per step until convergence turns quadratic).

    ``tol`` (optional) early-exits when the sign iterate moves less than
    ``tol`` in max-norm — same result, fewer matmuls on well-separated
    spectra.  ``num_iters="auto"`` picks the count from the
    Frobenius-prescaled spectral bound (``ns_auto_iters``) instead of the
    conservative fixed 60.  Matches ``project_psd`` to ≤1e-5 in the
    regimes pinned by tests/test_core_ranl.py.
    """
    d = a.shape[0]
    num_iters = resolve_ns_iters(num_iters, d, a.dtype)
    b = symmetrize(a) - mu * jnp.eye(d, dtype=a.dtype)
    s = jnp.sqrt(jnp.sum(b * b)) + jnp.finfo(a.dtype).tiny
    x0 = b / s
    if tol is None:
        x = jax.lax.fori_loop(0, num_iters, lambda _, x: _ns_sign_step(x),
                              x0)
    else:
        def cond(carry):
            k, _, delta = carry
            return jnp.logical_and(k < num_iters, delta > tol)

        def body(carry):
            k, x, _ = carry
            xn = _ns_sign_step(x)
            return k + 1, xn, jnp.max(jnp.abs(xn - x))

        _, x, _ = jax.lax.while_loop(
            cond, body, (0, x0, jnp.asarray(jnp.inf, a.dtype)))
    abs_b = symmetrize(b @ x)                       # |B| = B·sign(B)
    return 0.5 * (b + abs_b) + mu * jnp.eye(d, dtype=a.dtype)


def _panel_products(a_panel, b_panel, *, axis_name: str, n_model: int):
    """Row panels of A @ B for symmetric A, B, both row-paneled.

    Each device holds the ``(p, d)`` row slab of A and B for its model
    shard.  Using Aᵀ = A, the rows of A@B owned by shard j decompose as
    Σᵢ A[blkⱼ, blkᵢ] @ B[blkᵢ, :] = Σᵢ (Aᵢ[:, blkⱼ])ᵀ @ Bᵢ — every term
    is a product of panels the LOCAL device already holds, so the sum
    over i is one ``psum`` of a (p, d) panel product per destination
    shard.  No buffer ever exceeds the (p, d) slab.
    """
    me = jax.lax.axis_index(axis_name)
    p = a_panel.shape[0]
    out = jnp.zeros_like(b_panel)
    for j in range(n_model):
        part = jax.lax.dynamic_slice(a_panel, (0, j * p), (p, p)).T @ b_panel
        tot = jax.lax.psum(part, axis_name)
        out = jnp.where(me == j, tot, out)
    return out


def _panel_transpose(x_panel, *, axis_name: str, n_model: int):
    """Row panels of Xᵀ from row panels of X, psum-only.

    Destination shard j's rows of Xᵀ have column block i equal to
    (X[blkᵢ, blkⱼ])ᵀ — a (p, p) block device i already holds.  Each
    device drops its transposed block into the right column slot of a
    zero (p, d) panel and one psum per destination assembles the rows —
    the symmetrization primitive ``project_psd_ns_panels`` uses to keep
    the NS iterate symmetric without any gather-style collective.
    """
    me = jax.lax.axis_index(axis_name)
    p, d = x_panel.shape
    out = jnp.zeros_like(x_panel)
    for j in range(n_model):
        part = jax.lax.dynamic_slice(x_panel, (0, j * p), (p, p)).T
        contrib = jax.lax.dynamic_update_slice(
            jnp.zeros((p, d), x_panel.dtype), part, (0, me * p))
        tot = jax.lax.psum(contrib, axis_name)
        out = jnp.where(me == j, tot, out)
    return out


def project_psd_ns_panels(h_panel, mu: float, *, axis_name: str,
                          n_model: int, num_iters: int | str = 60):
    """``project_psd_ns`` over model-axis row panels (shard_map-inner).

    ``h_panel``: this device's ``(p, d)`` rows of sym(A).  Same
    Newton–Schulz iteration as the single-device oracle with every matmul
    replaced by ``_panel_products`` and the per-step symmetrization (see
    ``_ns_sign_step``) by ``_panel_transpose`` — per NS step that is
    three rounds of panel psums (X², X²·X, transpose), all (p, d)-sized.
    Returns this device's rows of [A]_μ.
    """
    p, d = h_panel.shape
    num_iters = resolve_ns_iters(num_iters, d, h_panel.dtype)
    row_start = jax.lax.axis_index(axis_name) * p
    eye_panel = (jnp.arange(d)[None, :]
                 == (row_start + jnp.arange(p))[:, None]).astype(
        h_panel.dtype)
    b = h_panel - mu * eye_panel
    s = jnp.sqrt(jax.lax.psum(jnp.sum(b * b), axis_name)) \
        + jnp.finfo(h_panel.dtype).tiny
    pp = functools.partial(_panel_products, axis_name=axis_name,
                           n_model=n_model)
    tp = functools.partial(_panel_transpose, axis_name=axis_name,
                           n_model=n_model)

    def step(_, x):
        xn = 1.5 * x - 0.5 * pp(pp(x, x), x)
        return 0.5 * (xn + tp(xn))

    x = jax.lax.fori_loop(0, num_iters, step, b / s)
    abs_b = pp(b / s, x) * s                        # |B| rows
    return 0.5 * (b + abs_b) + mu * eye_panel


@functools.lru_cache(maxsize=None)
def _sharded_projection_fn(mesh, axis_name: str, n_model: int,
                           num_iters: int):
    """Compiled shard_map'd projection, cached per (mesh, axis, iters) so
    repeated calls (benchmarks, multi-problem sweeps) don't re-trace; μ
    rides as a traced scalar."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def body(a_panel, mu):
        return project_psd_ns_panels(a_panel, mu, axis_name=axis_name,
                                     n_model=n_model, num_iters=num_iters)

    fn = shard_map(body, mesh=mesh, in_specs=(P(axis_name, None), P()),
                   out_specs=P(axis_name, None), check_vma=False)
    return jax.jit(fn)


def project_psd_sharded(a, mu: float, *, mesh, axis_name: str = "model",
                        num_iters: int | str = 60):
    """[A]_μ with the d×d matrix sharded as row panels over ``axis_name``.

    Host-facing wrapper: shard_maps ``project_psd_ns_panels`` over the
    mesh's ``axis_name`` axis and returns the projected matrix with the
    same row sharding.  Requires ``a.shape[0]`` divisible by the axis
    extent.  Equivalent to ``project_psd_ns`` up to psum reduction order
    (parity-pinned in tests), and to ``project_psd`` to NS tolerance.
    """
    n_model = mesh.shape[axis_name]
    if a.shape[0] % n_model:
        raise ValueError(
            f"dim={a.shape[0]} must divide evenly across the {n_model} "
            f"devices of the {axis_name!r} mesh axis")
    fn = _sharded_projection_fn(
        mesh, axis_name, n_model,
        resolve_ns_iters(num_iters, a.shape[0], a.dtype))
    return fn(symmetrize(a), jnp.asarray(mu, a.dtype))


def project_diag(h, mu: float):
    """Diagonal specialization of [·]_μ: elementwise max(h, μ)."""
    return jnp.maximum(h, mu)


def solve_projected(a_mu, g):
    """x-update direction [H]_μ^{-1} g via Cholesky solve (H ⪰ μI > 0)."""
    return jax.scipy.linalg.cho_solve(jax.scipy.linalg.cho_factor(a_mu), g)


def blocked_cholesky(a, block_size: int):
    """Right-looking blocked Cholesky: lower factor L with a = L Lᵀ.

    Processes ``block_size`` columns at a time (Python loop, static
    shapes; ``d`` need not divide evenly — the last block is ragged):
    factor the diagonal block, triangular-solve the panel below it, then
    apply the symmetric trailing update.  This is the schedule the
    dimension-sharded engine distributes over the ``"model"`` axis — each
    step touches one column block plus the trailing submatrix, so no
    participant ever needs the whole d×d matrix at once.  Agrees with
    ``jnp.linalg.cholesky`` to float tolerance (equivalence-pinned in
    tests across odd / non-divisible d).
    """
    d = a.shape[0]
    if not 1 <= block_size:
        raise ValueError(f"need block_size >= 1, got {block_size}")
    L = jnp.zeros_like(a)
    W = a
    for s in range(0, d, block_size):
        e = min(s + block_size, d)
        ljj = jnp.linalg.cholesky(W[s:e, s:e])
        L = L.at[s:e, s:e].set(ljj)
        if e < d:
            # panel solve: L[e:, s:e] = W[e:, s:e] inv(L_jj)ᵀ
            panel = jax.scipy.linalg.solve_triangular(
                ljj, W[e:, s:e].T, lower=True).T
            L = L.at[e:, s:e].set(panel)
            # trailing update (right-looking): W[e:, e:] -= panel panelᵀ
            W = W.at[e:, e:].add(-(panel @ panel.T))
    return L


def blocked_cho_solve(chol_l, b, block_size: int):
    """Solve (L Lᵀ) x = b by blocked forward/backward substitution.

    ``chol_l``: lower Cholesky factor (e.g. from ``blocked_cholesky``).
    Each block step consumes one (block, block) diagonal tile and one
    panel of already-solved entries — the access pattern the sharded
    engine turns into per-device panels plus small broadcasts.
    """
    if not 1 <= block_size:
        raise ValueError(f"need block_size >= 1, got {block_size}")
    d = chol_l.shape[0]
    starts = list(range(0, d, block_size))
    y = jnp.zeros_like(b)
    for s in starts:                               # forward: L y = b
        e = min(s + block_size, d)
        rhs = b[s:e] - chol_l[s:e, :s] @ y[:s]
        y = y.at[s:e].set(jax.scipy.linalg.solve_triangular(
            chol_l[s:e, s:e], rhs, lower=True))
    x = jnp.zeros_like(b)
    for s in reversed(starts):                     # backward: Lᵀ x = y
        e = min(s + block_size, d)
        rhs = y[s:e] - chol_l[e:, s:e].T @ x[e:]
        x = x.at[s:e].set(jax.scipy.linalg.solve_triangular(
            chol_l[s:e, s:e].T, rhs, lower=False))
    return x


def running_mean_hessian(problem, x, hkeys):
    """Mean worker Hessian as a running sum — one Hessian in flight at a
    time (O(d²) peak, not the O(N·d²) of vmap+stack).

    The left-to-right Python-loop fold (NOT lax.scan) is load-bearing:
    every engine and baseline that promises 'identical init phase' parity
    on a fixed key — ``repro.run`` with ``engine="scan"`` vs
    ``engine="reference"``, the newton baselines — must accumulate in this exact order, eagerly, because
    tracing the per-row noise transform under scan shifts it by ~1 ulp
    and the κ-conditioned solve amplifies that past the 1e-6 pins.  This
    is the single shared definition; do not re-inline it.
    """
    N = problem.num_workers
    H = jnp.zeros((problem.dim, problem.dim))
    for i in range(N):
        H = H + problem.worker_hessian(i, x, hkeys[i])
    return H / N


def hutchinson_diag(grad_fn, params, key, num_samples: int = 8):
    """Diagonal Hessian estimate diag(H) ≈ E[z ⊙ (Hz)], z ~ Rademacher.

    grad_fn: params -> grads (pytree).  Uses HVPs via jvp-of-grad.  This is
    the one-shot Newton-Zero curvature used by the deep-net RANL optimizer
    and the scan-compiled convex driver's ``curvature="diag"`` path.  The
    probes are vmapped over samples (one batched HVP, not ``num_samples``
    sequential ones).
    """
    leaves, treedef = jax.tree.flatten(params)

    def hvp(z):
        return jax.jvp(grad_fn, (params,), (z,))[1]

    def one_probe(ks):
        zk = [jax.random.rademacher(jax.random.fold_in(ks, i), l.shape,
                                    dtype=l.dtype)
              for i, l in enumerate(leaves)]
        z = jax.tree.unflatten(treedef, zk)
        hz = jax.tree.leaves(hvp(z))
        return [zi * hi for zi, hi in zip(zk, hz)]

    sample_keys = jax.vmap(lambda s: jax.random.fold_in(key, s))(
        jnp.arange(num_samples))
    probes = jax.vmap(one_probe)(sample_keys)     # leading axis: samples
    diag = [p.mean(axis=0) for p in probes]
    return jax.tree.unflatten(treedef, diag)


def fisher_diag(grad_fn, params, keys):
    """Empirical-Fisher diagonal: mean of squared per-batch grads.

    Cheaper alternative one-shot curvature (no HVPs); grad_fn(params, key).
    ``keys``: stacked PRNG keys (any stackable sequence); the per-key
    gradients are vmapped into one batched evaluation.
    """
    keys = jnp.asarray(keys)
    sq = jax.vmap(
        lambda k: jax.tree.map(jnp.square, grad_fn(params, k)))(keys)
    return jax.tree.map(lambda a: a.mean(axis=0), sq)
