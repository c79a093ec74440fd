"""Mixture-of-Experts FFN: an expert-share layer with dropless dispatch.

The router scores every expert (``cfg.num_experts``) at its published
width; the layer holds the weights of the first ``cfg.held_experts`` of
them and computes only their part of the output.  What experts held
elsewhere would add is left out (it belongs to other chips of an
expert-parallel deployment).  Scoring is softmax or sigmoid; a sigmoid
router selects by score plus a frozen per-expert bias and weighs by the
score alone, and the top-k weights are renormalised and scaled
(DeepSeek-V3's ``noaux_tc``).  Shared experts see every token.

Dispatch is dropless: the (token, expert) pairs routed to held experts are
sorted by expert into a buffer sized for the worst case (every token
routed to min(k, held) of them) and run as one grouped matmul per
projection (``grouped_matmul`` over ``jax.lax.ragged_dot``), whose groups
cover only the routed rows.  Under a mesh with a ``model`` axis the held
experts split evenly over it: each device computes its experts' share
inside ``shard_map`` and the shares are summed over ``model``; the shared
experts run outside, tensor-parallel, and so count once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import apply_swiglu, dense_init, init_swiglu
from .sharding import current_mesh

BIAS_SCALE = 1e-3     # std of the frozen selection bias


def init_moe(cfg, key, dtype=jnp.float32):
    d, ff, E, held = (cfg.d_model, cfg.expert_d_ff, cfg.num_experts,
                      cfg.held_experts)
    kr, kg, ku, kd, ks, kb = jax.random.split(key, 6)
    p = {
        "router": dense_init(kr, (d, E), dtype, scale=0.02),
        "gate": dense_init(kg, (held, d, ff), dtype),
        "up": dense_init(ku, (held, d, ff), dtype),
        "down": dense_init(kd, (held, ff, d), dtype),
    }
    if cfg.router_score == "sigmoid":
        p["router_bias"] = (BIAS_SCALE * jax.random.normal(kb, (E,))
                            ).astype(dtype)
    if cfg.num_shared_experts:
        p["shared"] = init_swiglu(ks, d, cfg.num_shared_experts * ff, dtype)
    return p


def route(params, xf, cfg):
    """xf (T, d) -> (selected expert ids (T, k), weights (T, k) f32, aux).
    The selection bias takes no gradient."""
    E, k = cfg.num_experts, cfg.experts_per_token
    logits = (xf @ params["router"]).astype(jnp.float32)       # (T, E)
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        bias = jax.lax.stop_gradient(params["router_bias"]).astype(
            jnp.float32)
        _, sel = jax.lax.top_k(scores + bias, k)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        _, sel = jax.lax.top_k(logits, k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * cfg.routed_scale
    aux = jnp.zeros((), jnp.float32)
    if cfg.router_aux_weight and cfg.router_score == "softmax":
        # load-balance aux loss (Switch-style): E * sum_e f_e * p_e
        frac = jax.nn.one_hot(sel, E, dtype=jnp.float32).mean(axis=(0, 1))
        aux = E * jnp.sum(frac * scores.mean(axis=0)) * cfg.router_aux_weight
    return sel, w, aux


# --------------------------------------------------------------------------
# grouped matmul: jax.lax.ragged_dot, differentiable under vmap
# --------------------------------------------------------------------------
# ragged_dot's own batching rule takes only operands that are all batched
# at dim 0, the expert weights are not batched under the worker vmap, and
# the TPU compiler takes no ragged dot with batch dims: each op below runs
# once per batch element (one, per device, when the worker axis is sharded
# over the data axes), and the VJP is written out so that reverse mode never
# differentiates through that rule.

_TGMM_DIMS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _batching_all(fn):
    op = jax.custom_batching.custom_vmap(fn)

    @op.def_vmap
    def _rule(axis_size, in_batched, *args):
        outs = [fn(*[a[i] if b else a for a, b in zip(args, in_batched)])
                for i in range(axis_size)]
        return jnp.stack(outs), True
    return op


# The TPU's ragged dot leaves the rows past the groups unwritten, and the
# gather's transpose would carry them into real tokens' gradients: they are
# set to zero here, and so is an empty group's product, the other output
# that no group covers.

def _live_rows(out, group_sizes):
    live = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
    return jnp.where(live[:, None], out, jnp.zeros((), out.dtype))


def _live_groups(out, group_sizes):
    return jnp.where((group_sizes > 0)[:, None, None], out,
                     jnp.zeros((), out.dtype))


_gmm = _batching_all(
    lambda x, w, gs: _live_rows(jax.lax.ragged_dot(x, w, gs), gs))
_tgmm = _batching_all(lambda x, g, gs: _live_groups(
    jax.lax.ragged_dot_general(x, g, gs, _TGMM_DIMS), gs))


@jax.custom_vjp
def grouped_matmul(x, w, group_sizes):
    """Rows x (m, k) in groups of ``group_sizes`` (G,) times w (G, k, n):
    row block g times w[g]; rows past the groups give zeros."""
    return _gmm(x, w, group_sizes)


def _grouped_fwd(x, w, group_sizes):
    return _gmm(x, w, group_sizes), (x, w, group_sizes)


def _grouped_bwd(res, g):
    x, w, group_sizes = res
    return (_gmm(g, jnp.swapaxes(w, 1, 2), group_sizes),
            _tgmm(x, g, group_sizes), None)


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def held_offset(n_local: int, shard):
    """First expert id held by model shard ``shard`` of ``n_local``."""
    return shard * n_local


def sum_shares(out):
    """The model shards' partial outputs summed over ``model``."""
    return jax.lax.psum(out, "model")


def expert_share(xf, sel, w, gate, up, down, first):
    """The part of the output that experts [first, first + n_local) give:
    rows sorted by expert, one grouped matmul per projection.  No row is
    dropped.  Returns (out (T, d), rows per local expert (n_local,),
    rows computed)."""
    T, k = sel.shape
    n_local = gate.shape[0]
    local = sel.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < n_local), local, n_local)
    cap = T * min(k, n_local)           # most routes a share can receive
    order = jnp.argsort(local, stable=True)[:cap]
    counts = jnp.bincount(local, length=n_local + 1)[:n_local]
    tok = order // k
    wr = jnp.where(local[order] < n_local, w.reshape(-1)[order], 0.0)
    with jax.named_scope("moe.experts"):
        xs = xf[tok]
        h = (jax.nn.silu(grouped_matmul(xs, gate, counts))
             * grouped_matmul(xs, up, counts))
        ys = grouped_matmul(h, down, counts)
        out = jnp.zeros_like(xf).at[tok].add(ys * wr[:, None].astype(
            ys.dtype))
    return out, counts, jnp.minimum(jnp.sum(counts), cap)


def apply_moe(params, x, cfg):
    """x: (B, S, d) -> (out (B, S, d), aux loss, counters).

    Counters: ``expert_rows`` (held,) rows each held expert computed,
    ``absent_rows`` routes to experts not held here, ``dropped_rows``
    routes to held experts that were not computed (0: dropless)."""
    B, S, d = x.shape
    held = cfg.held_experts
    xf = x.reshape(B * S, d)
    with jax.named_scope("moe.route"):
        sel, w, aux = route(params, xf, cfg)
    mesh = current_mesh()
    shards = mesh.shape.get("model", 1) if mesh is not None else 1
    experts = (params["gate"], params["up"], params["down"])
    if shards > 1:
        if held % shards:
            raise ValueError(f"{held} held experts do not split over "
                             f"{shards} model shards")

        def share(xf, sel, w, gate, up, down):
            first = held_offset(gate.shape[0], jax.lax.axis_index("model"))
            out, counts, done = expert_share(xf, sel, w, gate, up, down,
                                             first)
            placed = jax.lax.dynamic_update_slice(
                jnp.zeros((held,), counts.dtype), counts, (first,))
            return (sum_shares(out), jax.lax.psum(placed, "model"),
                    jax.lax.psum(done, "model"))

        ew = P("model", None, None)
        out, counts, done = jax.shard_map(
            share, mesh=mesh, in_specs=(P(), P(), P(), ew, ew, ew),
            out_specs=(P(), P(), P()), check_vma=False)(
                xf, sel, w, *experts)
    else:
        out, counts, done = expert_share(xf, sel, w, *experts,
                                         held_offset(held, 0))
    if "shared" in params:
        with jax.named_scope("moe.shared"):
            out = out + apply_swiglu(params["shared"], xf)
    routed_held = jnp.sum(sel < held)
    stats = {"expert_rows": counts,
             "absent_rows": sel.size - routed_held,
             "dropped_rows": routed_held - done}
    return out.reshape(B, S, d), aux, stats
