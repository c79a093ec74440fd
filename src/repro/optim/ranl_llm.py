"""RANL for deep networks — the paper's optimizer at framework scale.

Mapping (DESIGN.md §3–4):
  * workers  = data-parallel ranks; per-worker gradients come from
    ``vmap(grad)`` over a leading worker axis that pjit shards over the
    ``("pod","data")`` mesh axes — one gradient per shard, zero emulation.
  * regions  = layer index for stacked per-layer tensors (depth sub-models,
    à la independent-subnet training) + one region per glue tensor
    (embeddings / head / final norm), which are protected by default.
  * Hessian  = one-shot diagonal curvature at x⁰ (empirical Fisher or
    Hutchinson), projected with the diagonal specialization of the paper's
    [·]_μ (elementwise max(h, μ)) and reused every round (Newton-Zero).
  * memory   = the paper's C_i^{t,q}: per-worker latest gradient per region,
    sharded worker-axis over data and parameter axes like the params.

The server aggregation per region (fresh mean over covering workers,
memory-mean fallback for uncovered regions, memory refresh) is exactly
``repro.core.aggregation.server_aggregate`` generalized to pytrees.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ..core.masks import PolicyConfig, sample_masks


@dataclass(frozen=True)
class RanlLLMConfig:
    num_workers: int
    keep_prob: float = 0.7
    heterogeneous: bool = True
    tau_star: int = 1
    mu: float = 1e-8            # absolute curvature floor of [·]_μ
    mu_rel: float = 0.05        # relative floor: mu_rel * mean(h) per leaf
    lr: float = 1.0             # Newton step scale (paper: 1.0)
    trust_ratio: float = 0.1    # per-leaf cap ‖Δ‖ ≤ trust_ratio·(‖p‖+1)
    protect_glue: bool = True   # glue regions always trained
    memory_dtype: str = "bfloat16"
    # --- beyond-paper knobs (DESIGN.md §6) ---
    # EMA curvature refresh: 0.0 = paper-faithful one-shot Newton-Zero;
    # beta > 0 folds the current round's worker-mean squared gradients
    # into the diagonal curvature (h <- (1-beta) h + beta E_i[g_i^2]),
    # fixing the staleness of the x0 Hessian at zero extra communication
    # (the squared grads are already on the server).
    precond_beta: float = 0.0
    # int8 gradient memory: per-(worker, region-row) absmax-scaled int8
    # for C — 2x below bf16; RANL's dominant state cost.
    memory_int8: bool = False
    # lossy uplink compression of the per-worker gradients before the
    # aggregate (None | "int8" | "bf16") — the deep-net face of
    # ``core.compression``; the region top-k sparsifier has no LLM form
    # (regions here are whole layers, pruned by the mask already).
    compression: str | None = None

    def __post_init__(self):
        if self.compression not in (None, "int8", "bf16"):
            raise ValueError(
                f"unknown compression {self.compression!r} on the LLM "
                f"path (expected None, 'int8' or 'bf16' — 'topk:k' only "
                f"exists on the convex engines, where regions are "
                f"coordinate blocks rather than layers)")

    @property
    def policy(self) -> PolicyConfig:
        return PolicyConfig(name="bernoulli", keep_prob=self.keep_prob,
                            heterogeneous=self.heterogeneous,
                            tau_star=self.tau_star)


# --------------------------------------------------------------------------
# region layout over a params pytree
# --------------------------------------------------------------------------

#: stacked per-layer subtrees, in forward order: an MoE config's leading
#: dense layers, then the main stack
LAYER_STACKS = ("dense_layers", "layers")


def _layer_stack(path):
    for p in path:
        if getattr(p, "key", None) in LAYER_STACKS:
            return p.key
    return None


def region_layout(params):
    """Assign region ids: stacked layer leaves get one region per layer
    (shared layer id across the leaves of a stack), glue leaves one region
    each.  Stacks take consecutive layer regions in forward order
    (``LAYER_STACKS``): with a leading dense stack of depth D, its layers
    are regions 0..D-1 and the main stack's follow from D.

    Returns (num_regions, num_layer_regions, leaf_infos) where leaf_infos is
    a list aligned with tree_leaves: ("layer", (first_region, depth)) or
    ("glue", region_id).

    Every stacked leaf of one stack must agree on ``leaf.shape[0]`` —
    layer q of one leaf and layer q of another share a region id, so a
    mismatched depth would silently assign masks to the wrong layers.
    """
    leaves = jax.tree_util.tree_leaves_with_path(params)
    depths = {}
    for path, leaf in leaves:
        stack = _layer_stack(path)
        if stack is not None:
            depths.setdefault(stack, {})[jax.tree_util.keystr(path)] = \
                leaf.shape[0]
    first, num_layers = {}, 0
    for stack in LAYER_STACKS:
        if stack not in depths:
            continue
        sizes = sorted(set(depths[stack].values()))
        if len(sizes) > 1:
            detail = ", ".join(f"{k}={v}"
                               for k, v in sorted(depths[stack].items()))
            raise ValueError(
                f"region_layout: stacked leaves of {stack!r} disagree on "
                f"the leading (num_layers) dim {sizes} — region ids would "
                f"mis-align across leaves ({detail}). Stack every "
                "per-layer tensor to the same depth, or move the odd leaf "
                "out of the stack.")
        first[stack] = num_layers
        num_layers += sizes[0]
    infos = []
    next_glue = num_layers
    for path, leaf in leaves:
        stack = _layer_stack(path)
        if stack is not None:
            infos.append(("layer", (first[stack], leaf.shape[0])))
        else:
            infos.append(("glue", next_glue))
            next_glue += 1
    return next_glue, num_layers, infos


def region_param_counts(params):
    """(Q,) float32 parameter count per region.

    Layer regions: summed per-layer slice sizes of the stacked leaves that
    hold that layer; glue regions: the whole leaf.  This is the per-region
    "work" unit the heterogeneity cost models price when the closed-loop
    controller drives the deep-net path (``launch.train --scenario``).
    """
    num_regions, _, infos = region_layout(params)
    counts = [0] * num_regions
    for (kind, v), leaf in zip(infos, jax.tree_util.tree_leaves(params)):
        if kind == "layer":
            start, depth = v
            per_layer = leaf.size // depth
            for q in range(start, start + depth):
                counts[q] += per_layer
        else:
            counts[v] += leaf.size
    return jnp.asarray(counts, jnp.float32)


def leaf_masks(masks, infos, protect_glue: bool):
    """masks: (N, Q) bool -> per-leaf broadcastable masks list.

    Layer leaves get their stack's masks[:, first:first + L], reshaped
    (N, L, 1, ...) where used; glue leaves get masks[:, q] (or all-True
    when protected) reshaped (N, 1, ...).
    """
    out = []
    for kind, v in infos:
        if kind == "layer":
            start, depth = v
            out.append(masks[:, start:start + depth])
        else:
            m = (jnp.ones_like(masks[:, v]) if protect_glue
                 else masks[:, v])
            out.append(m[:, None])
    return out


def _bshape(mask, leaf_ndim_plus1):
    """Reshape (N, L) / (N, 1) mask to broadcast against (N, *leaf.shape)."""
    extra = leaf_ndim_plus1 - mask.ndim
    return mask.reshape(mask.shape + (1,) * extra)


def masked_aggregate(G, mask, C):
    """Pytree-leaf server aggregation (Algorithm 1 lines 15–22).

    G, C: (N, *leaf); mask: bool broadcastable (N, ...). Returns (g, C_new).

    Single-reduction form: the covered-region fresh mean and the
    uncovered-region memory-mean fallback are folded into one per-worker
    contribution *before* the worker-axis sum — sharded over the data axis
    this costs ONE param-sized all-reduce instead of two (the coverage
    counts are a mask-sized reduction, negligible). See EXPERIMENTS.md
    §Perf pair 5.
    """
    N = G.shape[0]
    m = _bshape(mask, G.ndim)
    mf = m.astype(G.dtype)
    count = mf.sum(axis=0)                      # mask-sized reduce (tiny)
    covered = count > 0
    # covered regions: m_i G_i / count; uncovered: C_i / N
    contrib = jnp.where(covered, mf * G / jnp.maximum(count, 1.0),
                        C.astype(G.dtype) / N)
    g = contrib.sum(axis=0)                     # ONE param-sized reduce
    C_new = jnp.where(m, G, C.astype(G.dtype)).astype(C.dtype)
    return g, C_new


# --------------------------------------------------------------------------
# state init / step
# --------------------------------------------------------------------------

def split_batch(batch, num_workers: int):
    return jax.tree.map(
        lambda a: a.reshape(num_workers, a.shape[0] // num_workers,
                            *a.shape[1:]), batch)


# --------------------------------------------------------------------------
# mesh plumbing: worker/batch axes sharded over the data axes of a mesh
# --------------------------------------------------------------------------

def _data_axes(mesh):
    from ..launch.shard import BATCH
    return tuple(a for a in BATCH if a in mesh.axis_names)


def _data_shards(mesh) -> int:
    n = 1
    for a in _data_axes(mesh):
        n *= mesh.shape[a]
    return n


def _shard_worker_axis(tree, mesh, num_workers: int):
    """Constrain the leading (worker) axis of every leaf over the mesh's
    data axes — the pjit sharding that makes vmap-over-workers execute
    one-worker-shard-per-device."""
    axes = _data_axes(mesh)
    n = _data_shards(mesh)
    if not axes or n == 1:
        return tree
    if num_workers % n:
        raise ValueError(
            f"num_workers={num_workers} must divide evenly across the "
            f"{n}-way {axes} mesh axes")
    def one(leaf):
        spec = jax.sharding.PartitionSpec(axes,
                                          *([None] * (leaf.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            leaf, jax.sharding.NamedSharding(mesh, spec))
    return jax.tree.map(one, tree)


def _apply_pspecs(tree, specs, mesh):
    from ..launch.shard import to_shardings
    return jax.tree.map(jax.lax.with_sharding_constraint, tree,
                        to_shardings(specs, mesh))


def per_worker_grads(loss_fn, params, batch, num_workers: int, *,
                     mesh=None):
    """vmap(value_and_grad) over the worker axis. batch leaves (B, ...).

    A ``loss_fn`` whose ``has_aux`` attribute is true returns (loss,
    counters); its per-worker values are then (losses, counters), as
    ``value_and_grad(..., has_aux=True)`` gives them.

    With ``mesh``, the split (num_workers, B/num_workers, ...) batch is
    sharding-constrained worker-axis-over-data so pjit partitions the
    per-worker gradient evaluations across devices (real data parallelism,
    not emulation); the vmap names the data axes (``spmd_axis_name``), so
    sharding constraints and ``shard_map`` regions inside the loss keep
    the worker axis on them.
    """
    wb = split_batch(batch, num_workers)
    spmd = None
    if mesh is not None:
        wb = _shard_worker_axis(wb, mesh, num_workers)
        spmd = _data_axes(mesh) or None
    has_aux = getattr(loss_fn, "has_aux", False)
    return jax.vmap(
        lambda b: jax.value_and_grad(loss_fn, has_aux=has_aux)(params, b),
        spmd_axis_name=spmd)(wb)


def quantize_memory(G):
    """Per-(leading-axes) absmax int8 quantization of a memory leaf.

    Scales are kept per (worker, region-row): for stacked layer leaves
    (N, L, ...) that is one scale per (worker, layer)."""
    red_axes = tuple(range(2, G.ndim)) if G.ndim > 2 else (1,)
    absmax = jnp.max(jnp.abs(G.astype(jnp.float32)), axis=red_axes,
                     keepdims=True)
    scale = jnp.maximum(absmax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(G.astype(jnp.float32) / scale), -127, 127)
    return {"q": q.astype(jnp.int8), "scale": scale}


def dequantize_memory(Cq):
    return Cq["q"].astype(jnp.float32) * Cq["scale"]


def _encode_memory(G, cfg):
    if cfg.memory_int8:
        return quantize_memory(G)
    return G.astype(jnp.dtype(cfg.memory_dtype))


def _decode_memory(C, cfg, like_dtype):
    if cfg.memory_int8:
        return dequantize_memory(C).astype(like_dtype)
    return C.astype(like_dtype)


def init_state(params, loss_fn, batch, cfg: RanlLLMConfig, key,
               precond_batches=None, mesh=None):
    """Round-0: one-shot curvature + memory seeded with init gradients."""
    mdt = jnp.dtype(cfg.memory_dtype)
    _, G0 = per_worker_grads(loss_fn, params, batch, cfg.num_workers,
                             mesh=mesh)
    C = jax.tree.map(lambda g: _encode_memory(g, cfg), G0)
    # empirical-Fisher diagonal from the per-worker init gradients
    # (mean over workers of squared grads — one extra pass over nothing:
    # reuses G0, the paper's "initialization phase" communication)
    h = jax.tree.map(lambda g: jnp.mean(
        jnp.square(g.astype(jnp.float32)), axis=0), G0)
    del mdt
    if precond_batches is not None:
        for b in precond_batches:
            _, Gb = per_worker_grads(loss_fn, params, b, cfg.num_workers,
                                     mesh=mesh)
            h = jax.tree.map(
                lambda a, g: a + jnp.mean(
                    jnp.square(g.astype(jnp.float32)), axis=0), h, Gb)
        h = jax.tree.map(lambda a: a / (1 + len(precond_batches)), h)
    return {"step": jnp.zeros((), jnp.int32), "precond": h, "memory": C}


def train_step(params, state, batch, rng, *, loss_fn, cfg: RanlLLMConfig,
               mesh=None, pspecs=None, masks=None):
    """One RANL round. Returns (new_params, new_state, metrics).

    With ``mesh``, the step runs pjit-sharded end to end: the global batch
    and the split worker axis shard over the mesh's data axes and the
    per-worker gradients are constrained with the worker-prefixed
    PartitionSpecs from ``launch.shard`` — the worker-axis sum inside
    ``masked_aggregate`` then lowers to the round's single param-sized
    all-reduce.  ``pspecs`` optionally carries precomputed trees
    ({"state": ranl_state_pspecs(...), "batch": batch_pspecs(...)});
    omitted entries are derived from ``params``/``batch``.

    ``masks`` (optional bool (num_workers, num_regions)) overrides the
    internal ``cfg.policy`` draw — the hook the closed-loop heterogeneity
    controllers use (``launch.train --controller`` keeps controller state
    host-side across steps and passes each round's allocation in).
    """
    num_regions, num_layer_regions, infos = region_layout(params)
    if mesh is not None:
        from ..launch.shard import batch_pspecs, ranl_state_pspecs
        pspecs = dict(pspecs or {})
        if "batch" not in pspecs:
            pspecs["batch"] = batch_pspecs(
                batch, batch_shards=_data_shards(mesh))
        if "state" not in pspecs:
            pspecs["state"] = ranl_state_pspecs(
                params, model_shards=mesh.shape.get("model", 1))
        batch = _apply_pspecs(batch, pspecs["batch"], mesh)
    value, G = per_worker_grads(loss_fn, params, batch, cfg.num_workers,
                                mesh=mesh)
    losses, counters = value if getattr(loss_fn, "has_aux", False) \
        else (value, {})
    if mesh is not None:
        G = _apply_pspecs(G, pspecs["state"]["memory"], mesh)

    if masks is None:
        mask_key = jax.random.fold_in(rng, state["step"])
        masks = sample_masks(cfg.policy, mask_key, state["step"],
                             cfg.num_workers, num_regions)
    lmasks = leaf_masks(masks, infos, cfg.protect_glue)

    g_leaves, c_leaves = [], []
    leaves, treedef = jax.tree_util.tree_flatten(G)
    is_mem_leaf = lambda x: not isinstance(x, dict) or "q" in x
    c_old = jax.tree_util.tree_leaves(state["memory"], is_leaf=is_mem_leaf)
    for Gl, ml, Cl in zip(leaves, lmasks, c_old):
        if cfg.compression == "int8":
            # lossy uplink: per-(worker, region-row) absmax int8
            # round-trip — what the server decodes from the wire (the
            # exact local gradient still refreshes nothing; memory C is
            # seeded from the decoded value the server actually saw)
            Gl = dequantize_memory(quantize_memory(Gl)).astype(Gl.dtype)
        elif cfg.compression == "bf16":
            Gl = Gl.astype(jnp.bfloat16).astype(Gl.dtype)
        Cl_arr = _decode_memory(Cl, cfg, Gl.dtype)
        g, c = masked_aggregate(Gl, ml, Cl_arr)
        g_leaves.append(g)
        c_leaves.append(_encode_memory(c, cfg))
    g = jax.tree.unflatten(treedef, g_leaves)
    C_new = jax.tree.unflatten(treedef, c_leaves)

    # beyond-paper: EMA curvature refresh (0.0 = paper-faithful one-shot)
    precond = state["precond"]
    if cfg.precond_beta > 0.0:
        gsq = jax.tree.map(
            lambda Gl: jnp.mean(jnp.square(Gl.astype(jnp.float32)), axis=0),
            G)
        precond = jax.tree.map(
            lambda h, q: (1.0 - cfg.precond_beta) * h + cfg.precond_beta * q,
            precond, gsq)

    # Newton step with the projected one-shot diagonal curvature.
    # Deep-net safeguards on top of the paper's update (DESIGN.md §6):
    # a per-leaf *relative* μ floor (the paper's μ is the strong-convexity
    # constant, unknowable for deep nets) and a LAMB-style trust ratio so a
    # near-singular curvature estimate cannot produce unbounded steps.
    def newton(p, gl, hl):
        h_mu = jnp.maximum(hl, cfg.mu + cfg.mu_rel * jnp.mean(hl))
        delta = cfg.lr * gl.astype(jnp.float32) / h_mu
        # NB: all-axis reductions, never reshape(-1): flattening a
        # model-sharded dim is unpartitionable and makes GSPMD replicate
        # the full fp32 tensor on every device.
        dn = jnp.sqrt(jnp.sum(jnp.square(delta)))
        pn = jnp.sqrt(jnp.sum(jnp.square(p.astype(jnp.float32))))
        scale = jnp.minimum(1.0, cfg.trust_ratio * (pn + 1.0)
                            / jnp.maximum(dn, 1e-20))
        return (p.astype(jnp.float32) - scale * delta).astype(p.dtype)

    new_params = jax.tree.map(newton, params, g, precond)
    new_state = {"step": state["step"] + 1, "precond": precond,
                 "memory": C_new}
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                         for x in g_leaves))
    coverage = masks.any(axis=0).mean()
    metrics = {"loss": losses.mean(), "grad_norm": gnorm,
               "coverage": coverage,
               "uplink_frac": masks.mean()}
    if counters:
        # routing over all workers: rows the held experts computed, routes
        # to experts held elsewhere, rows dropped (0: dropless), and the
        # busiest held expert's rows over the mean (layers x experts)
        rows = counters["expert_rows"].sum(axis=0)
        metrics.update(
            held_rows=counters["held_rows"].sum(),
            absent_rows=counters["absent_rows"].sum(),
            dropped_rows=counters["dropped_rows"].sum(),
            load_max_ratio=rows.max() / jnp.maximum(rows.mean(), 1e-9))
    return new_params, new_state, metrics
