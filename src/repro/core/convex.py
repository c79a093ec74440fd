"""Convex problem zoo for the paper-faithful RANL reproduction.

Each problem exposes per-worker stochastic oracles with *controllable*
constants from the paper's assumptions:
  - condition number κ = L_g/μ (eigenvalue spread),
  - gradient noise Δ (Assumption 3(i)),
  - Hessian noise σ at x⁰ (Assumption 3(ii)),
  - data heterogeneity (spread of per-worker optima / Hessians).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _noise_row(key, r, d: int):
    """Row r of the symmetric-noise base matrix z (one fold per row)."""
    return jax.random.normal(jax.random.fold_in(key, r), (d,)) / d


def _grad_noise(scale: float, key, d: int):
    """Δ-scaled gradient noise of one worker: Δ·N(0, I)/√d."""
    return scale * jax.random.normal(key, (d,)) / jnp.sqrt(d * 1.0)


def _vmap_grads(problem, x_pruned, keys):
    """Every worker's ``worker_grad`` at its own iterate, vmapped."""
    return jax.vmap(problem.worker_grad)(
        jnp.arange(problem.num_workers), x_pruned, keys)


def _sym_noise(key, d: int):
    """Symmetric Hessian noise (z + zᵀ)/2 with z rows drawn per-row-key.

    E‖(z+zᵀ)/2‖_F² = 1 (matching the old single-draw construction), but
    every row of z is its own PRNG stream — which is what lets
    ``_sym_noise_rows`` reproduce an arbitrary row panel bit-identically
    without ever materializing the d×d matrix.
    """
    z = jax.vmap(lambda r: _noise_row(key, r, d))(jnp.arange(d))
    return 0.5 * (z + z.T)


def _sym_noise_rows(key, d: int, row_start, num_rows: int):
    """Rows [row_start, row_start+num_rows) of ``_sym_noise(key, d)``.

    Peak memory O(num_rows·d): the panel needs z's rows (generated
    directly) and z's COLUMNS at the panel (entry [c, r] lives in row c's
    stream), which are produced ``num_rows`` source-rows at a time —
    each chunk generates a (num_rows, d) slab and keeps its (num_rows,
    num_rows) slice, so no intermediate exceeds the output panel.
    ``row_start`` may be traced; ``num_rows`` must be static.
    """
    rows = jax.vmap(lambda r: _noise_row(key, r, d))(
        row_start + jnp.arange(num_rows))                 # z[panel, :]

    def col_slice(c):
        return jax.lax.dynamic_slice(_noise_row(key, c, d),
                                     (row_start,), (num_rows,))

    if d % num_rows == 0:
        chunks = jnp.arange(d).reshape(d // num_rows, num_rows)
        cols = jax.lax.map(lambda cc: jax.vmap(col_slice)(cc),
                           chunks).reshape(d, num_rows)   # z[:, panel]
    else:
        cols = jax.lax.map(col_slice, jnp.arange(d))
    return 0.5 * (rows + cols.T)


@dataclass(frozen=True)
class Quadratic:
    """f_i(x) = ½ (x − b_i)ᵀ A_i (x − b_i);  f = mean_i f_i."""
    A: jnp.ndarray          # (N, d, d) per-worker PSD Hessians
    b: jnp.ndarray          # (N, d) per-worker optima
    grad_noise: float       # Δ
    hess_noise: float       # σ
    x_star: jnp.ndarray     # argmin of the average loss
    mu: float               # λ_min of mean Hessian
    L_g: float              # λ_max of mean Hessian

    @property
    def dim(self) -> int:
        return self.b.shape[1]

    @property
    def num_workers(self) -> int:
        return self.b.shape[0]

    def loss(self, x):
        r = x[None, :] - self.b                       # (N, d)
        return 0.5 * jnp.mean(jnp.einsum("nd,nde,ne->n", r, self.A, r))

    def worker_grad(self, i, x, key):
        """Stochastic ∇F_i(x, ξ): exact grad + bounded-variance noise."""
        g = self.A[i] @ (x - self.b[i])
        noise = self.grad_noise * jax.random.normal(key, g.shape) \
            / jnp.sqrt(g.shape[0] * 1.0)
        return g + noise

    def grad_path(self, use_kernel: bool, interpret: bool | None) -> str:
        """How ``pruned_grads`` computes: always the vmapped oracle."""
        return "vmap"

    def pruned_grads(self, x_pruned, keys, *, use_kernel: bool = False,
                     interpret: bool | None = None):
        """(N, d) gradients of every worker at its pruned iterate (the
        round loop's worker step); ``keys``: (N,) noise keys."""
        return _vmap_grads(self, x_pruned, keys)

    def worker_hessian(self, i, x, key):
        """Stochastic ∇²F_i(x⁰, ξ): exact + symmetric noise (Frobenius σ).

        The noise rows are per-row-key streams (``_sym_noise``) so that
        ``worker_hessian_rows`` can reproduce any row panel bit-identically
        on a dimension shard.
        """
        return self.A[i] + self.hess_noise * _sym_noise(key, self.dim)

    def worker_hessian_rows(self, i, x, key, row_start, num_rows: int):
        """Rows [row_start, row_start+num_rows) of ``worker_hessian``.

        Like ``worker_grad_rows``, computable from a row panel of A — the
        dimension-sharded engine hands each device ``self`` with ``A``
        already sliced to its ``(N_local, num_rows, d)`` panel, and the
        symmetric noise panel is generated at O(num_rows·d) peak from the
        same per-row streams as the full oracle.  The init phase
        accumulates these panels into the mean Hessian without any device
        ever holding a d×d buffer.  ``num_rows`` must be static.
        """
        d = self.A.shape[-1]                          # GLOBAL dim (last axis)
        return self.A[i] + self.hess_noise * _sym_noise_rows(
            key, d, row_start, num_rows)

    def worker_grad_rows(self, i, x, key, row_start, num_rows: int):
        """Rows [row_start, row_start+num_rows) of ``worker_grad(i, x, key)``.

        Computable from a row panel of A — the dimension-sharded engine
        hands each device ``self`` with ``A`` already sliced to its
        ``(N_local, num_rows, d)`` panel (see ``dim_sharded_specs``), so the
        d×d per-worker Hessians never sit whole on one device.  The noise
        stream is drawn at full length and sliced, keeping the values (and
        the Δ/√d scaling) bit-identical to the unsharded oracle.
        ``num_rows`` must be static; ``row_start`` may be traced.
        """
        g = self.A[i] @ (x - self.b[i])               # (num_rows,) panel rows
        d = self.A.shape[-1]                          # GLOBAL dim (last axis)
        noise = self.grad_noise * jax.random.normal(key, (d,)) \
            / jnp.sqrt(d * 1.0)
        return g + jax.lax.dynamic_slice_in_dim(noise, row_start, num_rows)

    def dim_sharded_specs(self, worker_axis: str, dim_axis: str):
        """PartitionSpecs for a ("data","model")-style 2-D mesh: workers
        over ``worker_axis``, the per-worker Hessian rows over ``dim_axis``
        (the O(N d²) state; b is O(N d) and stays dimension-replicated so
        the grad oracle sees the full shift vector)."""
        from jax.sharding import PartitionSpec as P
        return Quadratic(A=P(worker_axis, dim_axis, None),
                         b=P(worker_axis, None), grad_noise=self.grad_noise,
                         hess_noise=self.hess_noise, x_star=P(),
                         mu=self.mu, L_g=self.L_g)

    def mean_hessian(self):
        return self.A.mean(axis=0)


def _worker_het_scales(heterogeneity: float, worker_weights,
                       num_workers: int):
    """(N,) per-worker heterogeneity scales.

    ``worker_weights`` (mean-1 data shares, e.g. Dirichlet — see
    ``repro.hetero.scenarios.dirichlet_weights``) skew the perturbation
    1/√w per worker: data-poor workers drift further from the consensus
    objective, the standard non-IID shard reading.  ``None`` keeps the
    historical uniform scale bit-exactly."""
    if worker_weights is None:
        return jnp.full((num_workers,), heterogeneity)
    w = jnp.asarray(worker_weights)
    if w.shape != (num_workers,):
        raise ValueError(f"worker_weights shape {w.shape} != "
                         f"({num_workers},)")
    return heterogeneity / jnp.sqrt(jnp.maximum(w, 1e-3))


def make_quadratic(key, *, num_workers: int = 16, dim: int = 64,
                   kappa: float = 100.0, mu: float = 1.0,
                   heterogeneity: float = 0.0, grad_noise: float = 0.0,
                   hess_noise: float = 0.0, coupling: float = 1.0,
                   num_regions: int = 1, worker_weights=None) -> Quadratic:
    """Shared eigenbasis, eigenvalues logspace(μ … μκ); per-worker Hessian
    and optimum perturbed at rate ``heterogeneity``.

    ``coupling`` controls cross-region Hessian structure: 0.0 gives a
    block-diagonal Hessian aligned to ``num_regions`` contiguous regions —
    the regime where pruning whole regions leaves kept-region gradients
    unbiased (the paper's Assumption-4 δ-term vanishes and the clean ½-rate
    is observable); 1.0 gives a fully-coupled dense eigenbasis.

    ``worker_weights`` (optional (N,) mean-1 data shares) skew the
    per-worker perturbations 1/√w — Dirichlet non-IID shards; see
    ``_worker_het_scales``."""
    kq, kb, kp, ke, kq2 = jax.random.split(key, 5)
    d, N = dim, num_workers
    het = _worker_het_scales(heterogeneity, worker_weights, N)

    def block_orthobasis(k):
        """Block-diagonal orthogonal matrix aligned to the region partition."""
        bounds = np.linspace(0, d, num_regions + 1).astype(int)
        mats = []
        for q in range(num_regions):
            sz = bounds[q + 1] - bounds[q]
            m, _ = jnp.linalg.qr(
                jax.random.normal(jax.random.fold_in(k, q), (sz, sz)))
            mats.append(m)
        return jax.scipy.linalg.block_diag(*mats)

    eigs = mu * jnp.logspace(0.0, jnp.log10(kappa), d)
    if coupling >= 1.0:
        qmat, _ = jnp.linalg.qr(jax.random.normal(kq, (d, d)))
    elif coupling <= 0.0:
        qmat = block_orthobasis(kq)
    else:
        qb = block_orthobasis(kq)
        qg, _ = jnp.linalg.qr(jax.random.normal(kq2, (d, d)))
        blend = (1.0 - coupling) * qb + coupling * qg
        qmat, _ = jnp.linalg.qr(blend)   # re-orthogonalize the blend

    # per-worker multiplicative eigenvalue jitter (kept PSD by the floor,
    # which is a no-op for the uniform heterogeneity <= 1 regime and only
    # binds for extreme non-IID worker weights)
    jit = jnp.maximum(1.0 + het[:, None] * jax.random.uniform(
        kp, (N, d), minval=-0.5, maxval=0.5), 0.05)
    A = jnp.einsum("ij,nj,kj->nik", qmat, jit * eigs, qmat)

    b0 = jax.random.normal(kb, (d,))
    b = b0[None, :] + het[:, None] * jax.random.normal(ke, (N, d))

    Abar = A.mean(axis=0)
    x_star = jnp.linalg.solve(Abar, jnp.einsum("nij,nj->i", A, b) / N)
    w = jnp.linalg.eigvalsh(Abar)
    return Quadratic(A=A, b=b, grad_noise=grad_noise, hess_noise=hess_noise,
                     x_star=x_star, mu=float(w[0]), L_g=float(w[-1]))


@dataclass(frozen=True)
class Logistic:
    """ℓ2-regularized logistic regression; per-worker datasets (non-IID)."""
    X: jnp.ndarray          # (N, n, d)
    y: jnp.ndarray          # (N, n) in {−1, +1}
    lam: float
    grad_noise: float
    hess_noise: float
    x_star: jnp.ndarray
    mu: float
    L_g: float

    @property
    def dim(self) -> int:
        return self.X.shape[2]

    @property
    def num_workers(self) -> int:
        return self.X.shape[0]

    def loss(self, x):
        z = jnp.einsum("nij,j->ni", self.X, x) * self.y
        return jnp.mean(jax.nn.softplus(-z)) + 0.5 * self.lam * x @ x

    def worker_grad(self, i, x, key):
        Xi, yi = self.X[i], self.y[i]
        z = (Xi @ x) * yi
        s = jax.nn.sigmoid(-z)                         # (n,)
        g = -(Xi.T @ (s * yi)) / yi.shape[0] + self.lam * x
        return g + _grad_noise(self.grad_noise, key, g.shape[0])

    def grad_path(self, use_kernel: bool, interpret: bool | None) -> str:
        """``"fused"`` when ``pruned_grads`` runs the one-pass Pallas kernel
        (``kernels.logistic_grad``), else ``"vmap"``.

        The kernel runs when ``use_kernel`` is set and it compiles here: on
        a TPU (``interpret=None``), or in interpret mode where the caller
        asks for it (``interpret=True``; CPU users otherwise keep the jnp
        path).  It also needs f32 data, a width whose row tile fits in
        VMEM, and X laid out rows-minor, the layout it reads in place."""
        from ..kernels.logistic_grad import row_block, rows_minor_layout
        _, n, d = self.X.shape
        if not use_kernel or self.X.dtype != jnp.float32:
            return "vmap"
        if interpret is None and jax.default_backend() != "tpu":
            return "vmap"
        if row_block(n, d) is None or not rows_minor_layout(self.X.shape):
            return "vmap"
        return "fused"

    def pruned_grads(self, x_pruned, keys, *, use_kernel: bool = False,
                     interpret: bool | None = None):
        """(N, d) gradients of every worker at its pruned iterate (the
        round loop's worker step); ``keys``: (N,) noise keys.

        The fused path reads each worker's X once per call where the vmap
        of ``worker_grad`` reads it twice; its noise is the same draw from
        the same keys (see ``grad_path`` for when it runs)."""
        if self.grad_path(use_kernel, interpret) == "vmap":
            return _vmap_grads(self, x_pruned, keys)
        from ..kernels.logistic_grad import logistic_grads
        G = logistic_grads(self.X, self.y, x_pruned, lam=self.lam,
                           interpret=interpret)
        if self.grad_noise:
            G = G + jax.vmap(lambda k: _grad_noise(
                self.grad_noise, k, self.dim))(keys)
        return G

    def worker_hessian(self, i, x, key):
        Xi, yi = self.X[i], self.y[i]
        z = (Xi @ x) * yi
        s = jax.nn.sigmoid(z) * jax.nn.sigmoid(-z)     # σ'(z)
        H = (Xi.T * s) @ Xi / yi.shape[0] + self.lam * jnp.eye(self.dim)
        return H + self.hess_noise * _sym_noise(key, self.dim)

    def worker_hessian_rows(self, i, x, key, row_start, num_rows: int):
        """Rows [row_start, row_start+num_rows) of ``worker_hessian``.

        The Gauss–Newton rows come from a column slice of the worker's
        design matrix — (Xᵢ[:, rows]ᵀ·σ′) @ Xᵢ is O(n·d) flops and
        O(num_rows·d) memory, never d×d — and the symmetric noise panel
        from the shared per-row streams.  ``num_rows`` must be static.
        """
        Xi, yi = self.X[i], self.y[i]
        z = (Xi @ x) * yi
        s = jax.nn.sigmoid(z) * jax.nn.sigmoid(-z)
        Xr = jax.lax.dynamic_slice_in_dim(Xi, row_start, num_rows, axis=1)
        rows = (Xr.T * s) @ Xi / yi.shape[0]
        d = self.dim
        eye_rows = (jnp.arange(d)[None, :]
                    == (row_start + jnp.arange(num_rows))[:, None])
        return rows + self.lam * eye_rows + self.hess_noise * \
            _sym_noise_rows(key, d, row_start, num_rows)

    def worker_grad_rows(self, i, x, key, row_start, num_rows: int):
        """Rows [row_start, row_start+num_rows) of ``worker_grad``.

        Logistic holds no O(d²) per-worker state (X is N×n×d), so the
        dimension-sharded engine keeps X worker-sharded only and each model
        shard recomputes the full gradient and slices — exact by
        construction, trading redundant O(n d) flops for zero extra
        communication.  ``num_rows`` must be static."""
        g = self.worker_grad(i, x, key)
        return jax.lax.dynamic_slice_in_dim(g, row_start, num_rows)

    def dim_sharded_specs(self, worker_axis: str, dim_axis: str):
        """Workers over ``worker_axis`` only — see ``worker_grad_rows``."""
        from jax.sharding import PartitionSpec as P
        return Logistic(X=P(worker_axis, None, None),
                        y=P(worker_axis, None), lam=self.lam,
                        grad_noise=self.grad_noise,
                        hess_noise=self.hess_noise, x_star=P(),
                        mu=self.mu, L_g=self.L_g)

    def mean_hessian(self):
        return jax.hessian(self.loss)(self.x_star)


def _register_problem_pytrees():
    """Problems flow through jit/vmap boundaries (the scan-compiled RANL
    engine takes them as arguments), so register them as pytrees: arrays
    are data leaves, scalar constants are static metadata."""
    jax.tree_util.register_dataclass(
        Quadratic, ("A", "b", "x_star"),
        ("grad_noise", "hess_noise", "mu", "L_g"))
    jax.tree_util.register_dataclass(
        Logistic, ("X", "y", "x_star"),
        ("lam", "grad_noise", "hess_noise", "mu", "L_g"))


def make_logistic(key, *, num_workers: int = 16, per_worker: int = 128,
                  dim: int = 32, lam: float = 1e-2,
                  heterogeneity: float = 0.0, grad_noise: float = 0.0,
                  hess_noise: float = 0.0, worker_weights=None) -> Logistic:
    """``worker_weights``: optional (N,) mean-1 data shares skewing the
    per-worker distribution shift 1/√w (see ``_worker_het_scales``)."""
    kw, kx, ky, kshift = jax.random.split(key, 4)
    N, n, d = num_workers, per_worker, dim
    het = _worker_het_scales(heterogeneity, worker_weights, N)
    w_true = jax.random.normal(kw, (d,)) / jnp.sqrt(d)
    shifts = het[:, None, None] * jax.random.normal(kshift, (N, 1, d))
    X = jax.random.normal(kx, (N, n, d)) + shifts
    logits = jnp.einsum("nij,j->ni", X, w_true)
    y = jnp.where(jax.random.uniform(ky, (N, n)) < jax.nn.sigmoid(logits),
                  1.0, -1.0)

    prob = Logistic(X=X, y=y, lam=lam, grad_noise=0.0, hess_noise=0.0,
                    x_star=jnp.zeros(d), mu=lam, L_g=1.0)
    # solve for x* with exact Newton on the deterministic full loss
    x = jnp.zeros(d)
    grad_f = jax.grad(prob.loss)
    hess_f = jax.hessian(prob.loss)
    for _ in range(30):
        x = x - jnp.linalg.solve(hess_f(x), grad_f(x))
    # extreme eigenvalues on the host, in float64: the TPU's eigh does not
    # compile at d in the thousands (at d = 2048 its compile alone needs
    # more than 10 GiB of host memory)
    w = np.linalg.eigvalsh(np.asarray(hess_f(x), np.float64))
    return Logistic(X=X, y=y, lam=lam, grad_noise=grad_noise,
                    hess_noise=hess_noise, x_star=x,
                    mu=float(w[0]), L_g=float(w[-1]))


_register_problem_pytrees()
