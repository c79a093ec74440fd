"""Plain RANL on distributed logistic regression: the convex reference.

Algorithm 1 of the paper, written from its description:

* init: every worker's gradient at x0 = 0 seeds the memory C; the mean
  worker Hessian at x0 is projected onto {H >= mu I} (a no-op when H is
  already above mu I, as it is with the l2 term lam >= mu: the check
  below makes sure) and factored; x1 = x0 - lr H^-1 mean_i g_i.
* round t: worker i trains the regions its mask keeps, on x pruned to
  them; the server averages fresh gradients over the workers that cover
  a coordinate and falls back to the mean of the memory where none does;
  the memory takes the fresh gradients; x <- x - lr H^-1 g.
* masks: the run's key splits into an init key and a loop key; round t
  folds t into the loop key, then 1; per-worker keep probabilities are
  uniform about ``keep_prob``; one Bernoulli draw per worker and region.

Regions are contiguous blocks of coordinates, as equal as possible.
The check is teacher-forced: each round starts from the checked job's
own previous iterate, as a served model's reference reads each served
token.  Imports nothing of the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def region_ids(d: int, q: int):
    bounds = np.linspace(0, d, q + 1).astype(np.int64)
    ids = np.zeros(d, np.int32)
    for r in range(q):
        ids[bounds[r]:bounds[r + 1]] = r
    return jnp.asarray(ids)


def masks(key_loop, t, num_workers: int, num_regions: int,
          keep_prob: float):
    kt = jax.random.fold_in(key_loop, t)
    kp, km = jax.random.split(jax.random.fold_in(kt, 1))
    half = min(keep_prob * 0.5, 1.0 - keep_prob)
    probs = jax.random.uniform(kp, (num_workers,), minval=keep_prob - half,
                               maxval=keep_prob + half)
    return jax.random.uniform(jax.random.fold_in(km, t),
                              (num_workers, num_regions)) < probs[:, None]


def _grads(X, y, xw, lam):
    """Per-worker gradients of the mean logistic loss + l2; xw (N, d)."""
    z = jnp.einsum("nij,nj->ni", X, xw) * y
    s = jax.nn.sigmoid(-z) * y
    return -jnp.einsum("nij,ni->nj", X, s) / X.shape[1] + lam * xw


def loss(X, y, x, lam):
    z = jnp.einsum("nij,j->ni", X, x) * y
    return jnp.mean(jax.nn.softplus(-z)) + 0.5 * lam * x @ x


@partial(jax.jit, static_argnames=("num_regions", "keep_prob", "lam",
                                   "mu", "lr", "dtype", "free"))
def run(X, y, key, xs_in, *, num_regions: int, keep_prob: float,
        lam: float, mu: float, lr: float, dtype=jnp.float32,
        free: bool = False):
    """Check a job's iterates one round at a time.

    ``xs_in`` (T+2, d): the iterates to check, x0 ... x_{T+1}.  Round t
    starts from ``xs_in[t]`` (the memory holds the gradients taken at the
    checked iterates), so each returned iterate is the reference's answer
    to the same question the checked one answered, and rounding does not
    compound over rounds.

    -> (xs (T+2, d) the reference's iterates, losses (T+2,) at
    ``xs_in``, comm (T,) uplink coordinates per round, ok: [H]_mu = H).

    ``free``: each round starts from the reference's own previous iterate
    (a whole job, as the program runs it; ``xs_in`` then only gives T and
    the losses are taken at the reference's iterates).

    ``dtype`` float32 runs at "highest" matmul precision (the reference);
    bfloat16 is its control: data, iterates, gradients and memory in
    bfloat16, the curvature factor and solve in float32.
    """
    prec = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        N, n, d = X.shape
        T = xs_in.shape[0] - 2
        X = X.astype(dtype)
        y = y.astype(dtype)
        xs_in = xs_in.astype(dtype)
        ids = region_ids(d, num_regions)
        _, k_loop = jax.random.split(key)
        x0 = jnp.zeros(d, dtype)
        C = _grads(X, y, jnp.zeros((N, d), dtype), lam)
        # logistic Hessian at x0 = 0: sigma'(0) = 1/4 for every row
        H = jnp.einsum("nij,nik->jk", X, X).astype(jnp.float32) \
            / (4.0 * N * n) + lam * jnp.eye(d)
        # H - mu I is positive definite iff its Cholesky factor is finite:
        # then [H]_mu = H and no projection is needed
        low = jax.scipy.linalg.cho_factor(H - mu * jnp.eye(d))[0]
        ok = jnp.all(jnp.isfinite(low))
        cho = jax.scipy.linalg.cho_factor(H)

        def solve(g):
            return jax.scipy.linalg.cho_solve(
                cho, g.astype(jnp.float32)).astype(dtype)

        x1 = x0 - lr * solve(C.mean(axis=0))

        def body(carry, t):
            C, x_prev = carry
            x = x_prev if free else xs_in[t]
            M = masks(k_loop, t, N, num_regions, keep_prob)
            Mx = M[:, ids]
            xw = jnp.where(Mx, x[None, :], 0).astype(dtype)
            G = jnp.where(Mx, _grads(X, y, xw, lam), 0).astype(dtype)
            count = Mx.sum(axis=0)
            fresh = G.sum(axis=0) / jnp.maximum(count, 1).astype(dtype)
            g = jnp.where(count > 0, fresh, C.mean(axis=0))
            C = jnp.where(Mx, G, C)
            x_next = x - lr * solve(g)
            return (C, x_next), (x_next, Mx.sum())

        _, (xs_t, comm) = jax.lax.scan(body, (C, x1), jnp.arange(1, T + 1))
        xs = jnp.concatenate([jnp.stack([x0, x1]), xs_t], axis=0)
        losses = jax.vmap(lambda x: loss(X, y, x, lam))(
            xs if free else xs_in)
        return (xs.astype(jnp.float32), losses.astype(jnp.float32), comm,
                ok)
