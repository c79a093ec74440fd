"""PartitionSpec rules for every pytree the framework moves through pjit.

Conventions (DESIGN.md §5):
  * batch/worker axes shard over ("pod", "data");
  * tensor-parallel over "model": attention heads (q out-dim), FFN width,
    vocab, MoE experts, SSM inner width, RWKV head projections;
  * small glue (norms, token-shift mixes, routers and their selection
    bias, latent attention's down-projection) replicated;
  * decode caches: batch over data when divisible, else the window/sequence
    dim (long_500k batch=1 → sequence-parallel cache).
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

BATCH = ("pod", "data")


def _names(path) -> list[str]:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "idx"):
            out.append(str(p.idx))
        else:
            out.append(str(p))
    return out


def _param_spec(names: list[str], shape, model_shards: int,
                fsdp_shards=None, tied_embeddings: bool = False) -> P:
    """Spec for one parameter leaf (no worker axis).

    The "model" axis is only placed on a dim divisible by ``model_shards``
    (pjit argument shardings must divide evenly); otherwise that dim falls
    back to replicated.  With ``fsdp_shards`` > 1 (large models), the
    model-sharded dim is additionally sharded over the batch axes
    (FSDP/ZeRO-3: per-layer weight all-gather inside the layer loop),
    cascading ("model","pod","data") -> ("model","data") -> "model" by
    divisibility.
    """
    name = names[-1]
    in_layers = "layers" in names or "dense_layers" in names
    ndim = len(shape)
    off = 1 if in_layers else 0          # skip the stacked-layer axis

    def _model_axis(dim):
        if dim % model_shards:
            return None
        if dim > 1 << 12:
            # fsdp_shards: ordered [(extra_axes, extra_count), ...]
            for extra_axes, extra_n in (fsdp_shards or ()):
                if dim % (model_shards * extra_n) == 0:
                    return ("model",) + tuple(extra_axes)
        return "model"

    def _fsdp_axis(dim):
        """Batch-axes-only sharding (dims with no model axis, e.g. MoE
        expert FFN width — the expert dim takes "model")."""
        if dim > 1 << 12:
            for extra_axes, extra_n in (fsdp_shards or ()):
                if dim % extra_n == 0:
                    return tuple(extra_axes) if len(extra_axes) > 1 \
                        else extra_axes[0]
        return None

    def lay(*spec):
        """Prefix the stacked-layer axis when inside params['layers'],
        dropping "model" from dims that don't divide evenly."""
        full = (None,) * off + spec
        fixed = tuple(
            (_model_axis(shape[i]) if ax == "model" else
             (_fsdp_axis(shape[i]) if ax == "fsdp" else ax))
            for i, ax in enumerate(full))
        return P(*fixed)

    if name in ("embed", "lm_head", "vision_proj"):
        # glue params stay out of the FSDP cascade: token gathers over a
        # batch-axes-sharded table trigger involuntary replication in the
        # SPMD partitioner (observed on qwen3-32b)
        fsdp_shards = None
        if name == "embed" and tied_embeddings and ndim == 2:
            # tied embed doubles as the LM head: shard the VOCAB dim so
            # logits come out vocab-sharded (d-sharded would make the
            # h @ embed.T contraction all-reduce full-vocab logits)
            return lay("model", None)
        return (lay(None, None, "model") if ndim == 3
                else lay(None, "model"))
    if name == "final_norm":
        return lay(None)

    # attention / generic projections (output dim on "model"); latent
    # attention's wkv_b splits by heads, its wkv_a and kv_norm replicate
    if name in ("wq", "wk", "wv", "wkv_b", "in_proj", "w_r", "w_k", "w_v",
                "w_g"):
        return lay(None, "model")
    if name in ("wo", "w_o", "out_proj", "down"):
        if ndim - off == 3:                             # MoE (E, ff, d)
            return lay("model", "fsdp", None)
        return lay("model", None)
    if name in ("gate", "up"):
        if ndim - off == 3:                             # MoE (E, d, ff)
            return lay("model", None, "fsdp")
        return lay(None, "model")
    if name == "router":
        return lay(None, None)
    # ssm
    if name == "conv":
        return lay(None, "model")
    if name == "dt_lo":
        return lay("model", None)
    if name == "dt_hi":
        return lay(None, "model")
    if name in ("w_B", "w_C", "A_log"):
        return lay("model", None)
    if name in ("dt_bias", "D", "decay_base"):
        return lay("model")
    # rwkv
    if name == "decay_lo":
        return lay(None, None)
    if name == "decay_hi":
        return lay(None, "model")
    if name == "bonus_u":
        return lay("model", None)
    if name in ("mu", "ln_x", "q_norm", "k_norm", "ln1", "ln2"):
        return lay(*([None] * (ndim - off)))
    # default: replicate
    return P(*([None] * ndim))


def params_pspecs(params, model_shards: int = 1, fsdp_shards=None,
                  tied_embeddings: bool = False):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _param_spec(_names(path), leaf.shape,
                                       model_shards, fsdp_shards,
                                       tied_embeddings), params)


def worker_prefix(spec: P) -> P:
    """Prepend the worker axis (grads / RANL memory leaves).

    Batch axes move to the worker dim, so they are stripped from the inner
    parameter spec (an axis may appear only once per spec)."""
    def strip(part):
        if isinstance(part, tuple):
            kept = tuple(a for a in part if a not in BATCH)
            return kept if len(kept) > 1 else (kept[0] if kept else None)
        return None if part in BATCH else part
    return P(BATCH, *(strip(p) for p in spec))


def ranl_state_pspecs(params, model_shards: int = 1, fsdp_shards=None,
                      tied_embeddings: bool = False):
    pspec = params_pspecs(params, model_shards, fsdp_shards,
                          tied_embeddings)
    return {
        "step": P(),
        "precond": pspec,
        "memory": jax.tree.map(worker_prefix, pspec),
    }


def ranl2d_pspecs(problem, *, worker_axis: str = "data",
                  dim_axis: str = "model"):
    """PartitionSpecs for the dimension-sharded convex RANL engine.

    One dict per moving pytree of the sharded2d engine on a
    ``(worker_axis, dim_axis)`` mesh:

      * ``problem`` — the problem's own leaf rules (worker axes over
        ``worker_axis``; O(d²) per-worker state additionally row-sharded
        over ``dim_axis`` — see each problem's ``dim_sharded_specs``);
      * ``memory`` — gradient memory C (N, d): workers × dimension (the
        diag path's host-seeded init; the dense path seeds C in-program
        from ``worker_grad_rows`` and needs no spec for it);
      * ``hdiag`` — diagonal curvature (d,) over ``dim_axis``.

    The dense curvature state carries no spec at all anymore: the
    Cholesky row panels are produced INSIDE the shard_map'd program
    (sharded mean-Hessian accumulation → Newton–Schulz projection →
    blocked factorization), so they never cross a pjit boundary.
    """
    return {
        "problem": problem.dim_sharded_specs(worker_axis, dim_axis),
        "memory": P(worker_axis, dim_axis),
        "hdiag": P(dim_axis),
    }


def batch_pspecs(batch_specs, batch_shards: int = 1):
    def one(path, leaf):
        names = _names(path)
        if names[-1] == "pos":
            return P()
        bax = BATCH if leaf.shape[0] % max(batch_shards, 1) == 0 else None
        return P(bax, *([None] * (leaf.ndim - 1)))
    return jax.tree_util.tree_map_with_path(one, batch_specs)


def cache_pspecs(cache_specs, *, batch_shards: int, model_shards: int = 1):
    """Decode-cache specs. Leaves have a leading num_layers axis.

    pjit argument shardings must divide evenly, so the model axis goes on
    the kv-head dim when divisible, else on head_dim; the batch dim shards
    over data when divisible, else the window/sequence dim (long_500k)."""
    def one(path, leaf):
        names = _names(path)
        name = names[-1]
        if name == "slot_pos":
            return P(None, None)
        b = leaf.shape[1]
        batch_ok = b % batch_shards == 0
        if name in ("k", "v"):
            L, B, W, KV, hd = leaf.shape
            # decode reads the whole window every step: sharding W over
            # "model" partitions the attention reduction itself (GSPMD
            # emits the softmax-stat all-reduce), vs. kv-head/hd sharding
            # which leaves the per-device score compute amplified
            w_model = "model" if W % model_shards == 0 else None
            kv_axis = hd_axis = None
            if w_model is None:
                kv_axis = "model" if KV % model_shards == 0 else None
                hd_axis = ("model" if kv_axis is None
                           and hd % model_shards == 0 else None)
            if batch_ok:
                return P(None, BATCH, w_model, kv_axis, hd_axis)
            # batch=1 (long_500k): window over the batch axes only —
            # measured: adding "model" on the window here regressed bytes
            # 3x (softmax-stat all-reduce over 256 shards dominates the
            # small per-shard window)
            w_axis = BATCH if W % batch_shards == 0 else None
            kv_axis = "model" if KV % model_shards == 0 else None
            hd_axis = ("model" if kv_axis is None
                       and hd % model_shards == 0 else None)
            return P(None, None, w_axis, kv_axis, hd_axis)
        bax = BATCH if batch_ok else None
        fit = lambda n: "model" if n % model_shards == 0 else None
        if name == "h":                                # ssm state (L,B,di,n)
            return P(None, bax, fit(leaf.shape[2]), None)
        if name == "conv":                             # (L,B,W-1,di)
            return P(None, bax, None, fit(leaf.shape[3]))
        if name == "wkv":                              # (L,B,H,hdk,hdv)
            h_ax = fit(leaf.shape[2])
            hd_ax = fit(leaf.shape[3]) if h_ax is None else None
            return P(None, bax, h_ax, hd_ax, None)
        if name in ("tmix_last_x", "cmix_last_x"):     # (L,B,d)
            return P(None, bax, fit(leaf.shape[2]))
        return P(*([None] * leaf.ndim))
    return jax.tree_util.tree_map_with_path(one, cache_specs)


def trim_tree(specs, mesh):
    """Drop axis names not present in ``mesh`` from every spec."""
    def trim(spec):
        out = []
        for part in spec:
            if part is None:
                out.append(None)
            elif isinstance(part, (tuple, list)):
                kept = tuple(a for a in part if a in mesh.axis_names)
                out.append(kept if kept else None)
            else:
                out.append(part if part in mesh.axis_names else None)
        return P(*out)
    return jax.tree.map(trim, specs,
                        is_leaf=lambda x: isinstance(x, P))


def to_shardings(specs, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        trim_tree(specs, mesh),
                        is_leaf=lambda x: isinstance(x, P))
