"""Inputs and weights made from ``--seed``, on the device.

The same seed gives the same inputs.  The generators here belong to the
benchmark, so no change to the program can make the traffic cheaper, and
the plain reference regenerates the same weights and data from the seed
rather than taking anything the program made.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """PRNG key of any whole seed up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} must lie in [0, 2**64)")
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(seed >> 32))


def stream(seed: int, name: str):
    """Independent key per named stream ("weights", "data", "rng", ...)."""
    k = seed_key(seed)
    for ch in name.encode():
        k = jax.random.fold_in(k, ch)
    return k


# --------------------------------------------------------------------------
# decoder-only transformer weights (program layout: stacked layers)
# --------------------------------------------------------------------------

_DIMS = ("vocab_size", "hidden_size", "intermediate_size",
         "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
         "head_dim")


def transformer_shapes(model: dict):
    V, d, ff, L, H, KV, hd = (model[k] for k in _DIMS)
    return {
        "embed": (V, d),
        "final_norm": (d,),
        "layers": {
            "ln1": (L, d), "ln2": (L, d),
            "attn": {"wq": (L, d, H * hd), "wk": (L, d, KV * hd),
                     "wv": (L, d, KV * hd), "wo": (L, H * hd, d)},
            "mlp": {"gate": (L, d, ff), "up": (L, d, ff),
                    "down": (L, ff, d)},
        },
    }


def _leaf_init(path, shape, key):
    name = path[-1].key
    if name in ("ln1", "ln2", "final_norm"):
        return jnp.ones(shape, jnp.float32)
    std = 0.02 if name == "embed" else shape[-2] ** -0.5
    return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                       jnp.float32) * std


@partial(jax.jit, static_argnums=(0,))
def _weights(dims, key):
    shapes = transformer_shapes(dict(zip(_DIMS, dims)))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [_leaf_init(p, s, k) for (p, s), k in zip(leaves, keys)])


def transformer_weights(model: dict, key):
    """f32 weights in one jitted call: truncated normals at 1/sqrt(fan_in)
    (0.02 for the tied embedding), norm scales at one."""
    return _weights(tuple(int(model[k]) for k in _DIMS), key)


# --------------------------------------------------------------------------
# token traffic: affine bigram chain with noise
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("batch", "seq", "vocab", "noise", "a",
                                   "b"))
def bigram_batch(key, index, *, batch: int, seq: int, vocab: int,
                 noise: float, a: int, b: int):
    """Batch ``index`` of the stream ``key``: tokens (batch, seq) and
    next-token labels.  x_{t+1} = (a x_t + b) mod V with probability
    1 - noise, else uniform."""
    k = jax.random.fold_in(key, index)
    k0, kn, kp = jax.random.split(k, 3)
    x0 = jax.random.randint(k0, (batch,), 0, vocab, jnp.int32)

    def step(x, ks):
        ku, kf = ks
        nxt = (a * x + b) % vocab
        uni = jax.random.randint(ku, (batch,), 0, vocab, jnp.int32)
        flip = jax.random.uniform(kf, (batch,)) < noise
        x = jnp.where(flip, uni, nxt)
        return x, x

    keys = (jax.random.split(kn, seq + 1), jax.random.split(kp, seq + 1))
    _, xs = jax.lax.scan(step, x0, keys)
    toks = jnp.moveaxis(xs, 0, 1)
    return {"tokens": toks[:, :seq], "labels": toks[:, 1:]}


def train_batch(traffic: dict, vocab: int, key, index: int):
    return bigram_batch(key, index, batch=traffic["batch"],
                        seq=traffic["seq"], vocab=vocab,
                        noise=traffic["noise"], a=traffic["affine"][0],
                        b=traffic["affine"][1])


# --------------------------------------------------------------------------
# distributed logistic regression data (LIBSVM epsilon shape)
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("workers", "rows", "dim", "shift",
                                   "logit_scale"))
def logistic_data(key, *, workers: int, rows: int, dim: int, shift: float,
                  logit_scale: float):
    """X (workers, rows, dim) with unit-length rows, worker-shifted
    (non-IID), and labels y in {-1, +1} from a planted logistic model."""
    kw, kx, ks, ky = jax.random.split(key, 4)
    w_true = jax.random.normal(kw, (dim,)) * logit_scale
    shifts = shift * jax.random.normal(ks, (workers, 1, dim))
    X = jax.random.normal(kx, (workers, rows, dim)) + shifts
    X = X * jax.lax.rsqrt(jnp.sum(X * X, axis=-1, keepdims=True))
    logits = jnp.einsum("nij,j->ni", X, w_true)
    y = jnp.where(jax.random.uniform(ky, (workers, rows))
                  < jax.nn.sigmoid(logits), 1.0, -1.0)
    return X, y


@partial(jax.jit, static_argnames=("iters",))
def logistic_optimum(X, y, lam, *, iters: int):
    """x* by Newton's method with the closed-form Gauss-Newton Hessian
    X^T diag(s) X / n + lam I, accumulated one worker at a time."""
    N, n, d = X.shape

    def newton(_, x):
        def acc(carry, xy):
            Xi, yi = xy
            z = (Xi @ x) * yi
            s = jax.nn.sigmoid(z) * jax.nn.sigmoid(-z)
            g = -(Xi.T @ (jax.nn.sigmoid(-z) * yi))
            return (carry[0] + (Xi.T * s) @ Xi, carry[1] + g), None

        (H, g), _ = jax.lax.scan(acc, (jnp.zeros((d, d)), jnp.zeros(d)),
                                 (X, y))
        H = H / (N * n) + lam * jnp.eye(d)
        g = g / (N * n) + lam * x
        c = jax.scipy.linalg.cho_factor(H)
        return x - jax.scipy.linalg.cho_solve(c, g)

    return jax.lax.fori_loop(0, iters, newton, jnp.zeros(d))
