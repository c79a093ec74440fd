"""Plain RANL for deep networks: the reference optimizer of the train cells.

Algorithm 1 of the paper with regions = layers (every glue tensor always
trained), an empirical-Fisher diagonal curvature from the workers' first
gradients, gradient memory in the configured dtype, and the stated
deep-net safeguards: a relative curvature floor and a per-leaf trust
ratio.  The masks follow the configured policy (per-worker keep
probabilities uniform about ``keep_prob``, one Bernoulli draw per worker
and region each round, uncovered regions handed to worker q mod N).  One
worker at a time, in a Python loop.  Imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import transformer


def policy_masks(opt: dict, rng, step: int, num_workers: int,
                 num_regions: int):
    """(N, Q) bool masks of round ``step`` drawn from the run's key."""
    key = jax.random.fold_in(jax.random.fold_in(rng, step), 1)
    kp, km = jax.random.split(key)
    base = opt["keep_prob"]
    half = min(base * 0.5, 1.0 - base)
    probs = jax.random.uniform(kp, (num_workers,), minval=base - half,
                               maxval=base + half)
    m = jax.random.uniform(jax.random.fold_in(km, step),
                           (num_workers, num_regions)) < probs[:, None]
    if opt["tau_star"]:
        q = jnp.arange(num_regions)
        lone = m.sum(axis=0) == 0
        m = m | (lone[None, :] & (jnp.arange(num_workers)[:, None]
                                  == (q % num_workers)[None, :]))
    return m


def _worker_rows(batch, i, n):
    rows = batch["tokens"].shape[0] // n
    return (batch["tokens"][i * rows:(i + 1) * rows],
            batch["labels"][i * rows:(i + 1) * rows])


def make_grad(model: dict, dtype):
    prec = "highest" if dtype == jnp.float32 else "default"

    @jax.jit
    def grad(params, tokens, labels):
        with jax.default_matmul_precision(prec):
            return jax.value_and_grad(transformer.loss)(
                params, tokens, labels, model)
    return grad


def run(params, batches, rng, model: dict, opt: dict, *, steps: int,
        dtype=jnp.float32):
    """Init phase on ``batches[0]`` and ``steps`` rounds on the next ones.

    Returns dict: ``losses`` (steps,) worker-mean loss per round,
    ``memory_norms`` per-leaf norm of the gradient memory after round 1,
    ``update_norms`` per-leaf norm of params_after_steps - params,
    ``grad_norms`` per-leaf norm of the workers' init gradients (to rank
    leaves), all as flat dicts keyed by leaf path.
    """
    N = opt["num_workers"]
    L = model["num_hidden_layers"]
    mdt = jnp.dtype(opt["memory_dtype"])
    grad = make_grad(model, dtype)
    p0 = jax.tree.map(lambda a: a.astype(dtype), params)

    def worker_grads(p, batch):
        out = [grad(p, *_worker_rows(batch, i, N)) for i in range(N)]
        return [o[0] for o in out], [o[1] for o in out]

    _, G0 = worker_grads(p0, batches[0])
    C = [jax.tree.map(lambda g: g.astype(mdt), g) for g in G0]
    h = jax.tree.map(lambda *gs: sum(jnp.square(g.astype(dtype))
                                     for g in gs) / N, *G0)
    names = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_leaves_with_path(params)]
    # regions: layer l of every stacked leaf is region l; glue leaves
    # ("embed", "final_norm") follow and are always trained
    Q = L + sum(1 for n in names if "layers" not in n)

    is_layer = [("layers" in n) for n in names]
    treedef = jax.tree_util.tree_structure(params)

    @jax.jit
    def round_update(p, G, C, h, m):
        """Server aggregate with memory fallback, then the Newton step."""
        Gl = [jax.tree_util.tree_leaves(x) for x in G]
        Cl = [jax.tree_util.tree_leaves(x) for x in C]
        new_p, new_c = [], [[] for _ in range(N)]
        for j, (pl, hl) in enumerate(zip(jax.tree_util.tree_leaves(p),
                                         jax.tree_util.tree_leaves(h))):
            gs = [Gl[i][j] for i in range(N)]
            cs = [Cl[i][j].astype(dtype) for i in range(N)]
            if is_layer[j]:
                mk = [m[i, :L].reshape((L,) + (1,) * (gs[0].ndim - 1))
                      for i in range(N)]
            else:
                mk = [jnp.ones((), bool)] * N
            count = sum(x.astype(dtype) for x in mk)
            fresh = sum(jnp.where(mk[i], gs[i], 0) for i in range(N))
            stale = sum(cs) / N
            g = jnp.where(count > 0, fresh / jnp.maximum(count, 1), stale)
            for i in range(N):
                new_c[i].append(jnp.where(mk[i], gs[i], cs[i]).astype(mdt))
            floor = opt["mu"] + opt["mu_rel"] * jnp.mean(hl)
            delta = opt["lr"] * g / jnp.maximum(hl, floor)
            dn = jnp.sqrt(jnp.sum(jnp.square(delta.astype(jnp.float32))))
            pn = jnp.sqrt(jnp.sum(jnp.square(pl.astype(jnp.float32))))
            scale = jnp.minimum(1.0, opt["trust_ratio"] * (pn + 1.0)
                                / jnp.maximum(dn, 1e-20))
            new_p.append((pl - scale.astype(dtype) * delta).astype(dtype))
        unflat = lambda xs: jax.tree_util.tree_unflatten(treedef, xs)
        return unflat(new_p), [unflat(c) for c in new_c]

    p = p0
    out = {"losses": [], "grad_norms": _leaf_norms(G0, names, stacked=True)}
    del G0
    for s in range(steps):
        losses, G = worker_grads(p, batches[s + 1])
        m = policy_masks(opt, rng, s, N, Q)
        p, C = round_update(p, G, C, h, m)
        del G
        out["losses"].append(float(sum(x.astype(jnp.float32)
                                       for x in losses) / N))
        if s == 0:
            out["memory_norms"] = _leaf_norms(C, names, stacked=True)
    out["update_norms"] = _leaf_norms(
        jax.tree.map(lambda a, b: a.astype(jnp.float32)
                     - b.astype(jnp.float32), p, p0), names)
    return out


def _leaf_norms(tree, names, stacked=False):
    """Flat {leaf path: norm}; ``stacked`` trees are per-worker lists whose
    norm is taken over all workers together."""
    if stacked:
        per = [jax.tree_util.tree_leaves(t) for t in tree]
        sq = [sum(jnp.sum(jnp.square(w[j].astype(jnp.float32)))
                  for w in per) for j in range(len(names))]
    else:
        sq = [jnp.sum(jnp.square(a.astype(jnp.float32)))
              for a in jax.tree_util.tree_leaves(tree)]
    return {n: float(jnp.sqrt(v)) for n, v in zip(names, sq)}
