"""Plain Moonlight (DeepSeek-V3 block) and RANL rounds: the reference of
the Moonlight train cell.

Written from the published description (Moonlight-16B-A3B's config.json;
DeepSeek-V3, arXiv:2412.19437 §2.1; latent attention from DeepSeek-V2,
arXiv:2405.04434), with the keys of the configuration file as run:

* latent attention: q = x W_q gives H heads of (nope + rope) dims;
  [c, k_rope] = x W_kva; [k_nope, v] = RMSNorm(c) W_kvb per head; rotary
  on q_rope and on the one k_rope that every head shares; causal softmax
  at (nope + rope)^-1/2; W_o;
* a leading dense SwiGLU layer, then MoE layers: sigmoid scores over all
  ``router_experts``, top-k of score + selection bias, the chosen scores
  renormalised and scaled by ``routed_scaling_factor``; every held expert
  runs on every token (a dense loop, weighted, zero where not chosen),
  plus the shared experts (one SwiGLU of n_shared x the expert width);
* untied embedding and head, RMSNorm at ``rms_norm_eps``.

Departures from the published model, as in the program: only the held
experts' part of the routed output (the chips' share of an
expert-parallel deployment); a frozen selection bias with no balancing
update and no sequence-wise auxiliary loss; rotate-half rotary where
DeepSeek interleaves the pairs (the same up to a fixed permutation of the
rope columns).  Queries are taken in chunks, each recomputed in the
backward pass, so that the full (seq x seq) scores never live at once;
each stack's layers run as one ``lax.scan``, which keeps the compile
short, each layer recomputed in the backward pass.

The rounds are ``ranl_llm``'s, with regions over the two stacks in
forward order (the dense layer, then the MoE layers, then glue).  Under
``mesh`` (a 1-D mesh over the cell's chips) the weights are spread over
it by the rules below and XLA partitions the work.  Imports nothing of the
program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import moe as moe_layout
from .ranl_llm import _leaf_norms, policy_masks

STACKS = ("dense_layers", "layers")


def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rotary(x, theta):
    """Rotate-half rotary embedding of x (B, S, heads, dim)."""
    S, dim = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :dim // 2], xf[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def attention(p, x, m, q_chunk):
    B, S, _ = x.shape
    H, r = m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, hv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    q = (x @ p["wq"]).reshape(B, S, H, nope + rope)
    kv_a = x @ p["wkv_a"]
    kv = (rms_norm(kv_a[..., :r], p["kv_norm"], m["rms_norm_eps"])
          @ p["wkv_b"]).reshape(B, S, H, nope + hv)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:],
                                               m["rope_theta"])], -1)
    k_rope = rotary(kv_a[..., None, r:], m["rope_theta"])
    k = jnp.concatenate([kv[..., :nope], jnp.repeat(k_rope, H, axis=2)], -1)
    v = kv[..., nope:]
    scale = 1.0 / jnp.sqrt(jnp.asarray(nope + rope, jnp.float32))
    neg = jnp.asarray(-1e30 if x.dtype == jnp.float32 else -3e38, x.dtype)

    @partial(jax.checkpoint, static_argnums=(1,))
    def rows(qc, q0):
        """Queries q0.. against the keys up to the chunk's last one."""
        n = q0 + qc.shape[1]
        s = jnp.einsum("bqhd,bkhd->bhqk", qc, k[:, :n]) \
            * scale.astype(x.dtype)
        causal = (jnp.arange(n)[None, :]
                  <= q0 + jnp.arange(qc.shape[1])[:, None])
        s = jnp.where(causal[None, None], s, neg)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                          v[:, :n])

    chunk = min(q_chunk, S)
    out = jnp.concatenate([rows(q[:, i:i + chunk], i)
                           for i in range(0, S, chunk)], axis=1)
    return out.reshape(B, S, H * hv) @ p["wo"]


def swiglu(p, x):
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def moe(p, x, m):
    """Held experts' part of the routed output plus the shared experts;
    x (T, d)."""
    s = jax.nn.sigmoid((x @ p["router"]).astype(jnp.float32))
    _, sel = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32),
                           m["num_experts_per_tok"])
    w = s * jax.nn.one_hot(sel, s.shape[-1]).sum(axis=-2)
    w = w / w.sum(axis=-1, keepdims=True) * m["routed_scaling_factor"]
    held = p["gate"].shape[0]
    h = (jax.nn.silu(jnp.einsum("td,edf->tef", x, p["gate"]))
         * jnp.einsum("td,edf->tef", x, p["up"]))
    h = h * w[:, :held, None].astype(x.dtype)
    return jnp.einsum("tef,efd->td", h, p["down"]) + swiglu(p["shared"], x)


def block(p, x, m, q_chunk):
    eps = m["rms_norm_eps"]
    x = x + attention(p["attn"], rms_norm(x, p["ln1"], eps), m, q_chunk)
    h = rms_norm(x, p["ln2"], eps)
    if "moe" in p:
        B, S, d = h.shape
        return x + moe(p["moe"], h.reshape(B * S, d), m).reshape(B, S, d)
    return x + swiglu(p["mlp"], h)


def loss(params, tokens, labels, m, q_chunk=1024):
    """Mean next-token cross-entropy over all positions."""
    x = params["embed"][tokens]
    for stack in STACKS:
        x, _ = jax.lax.scan(jax.checkpoint(
            lambda h, p: (block(p, h, m, q_chunk), None)), x, params[stack])
    logits = rms_norm(x, params["final_norm"], m["rms_norm_eps"]) \
        @ params["lm_head"]
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold.astype(jnp.float32))


# --------------------------------------------------------------------------
# layout over the cell's chips
# --------------------------------------------------------------------------

def _spec(path, ndim):
    """Heads, FFN widths, experts and vocabulary over the one axis "x";
    norms, routers, the selection bias and W_kva replicated."""
    names = [getattr(k, "key", None) for k in path]
    name = names[-1]
    if name in ("wq", "wkv_b", "lm_head", "embed") or (
            name in ("gate", "up") and "moe" not in names[-2:]):
        return P(*([None] * (ndim - 1)), "x")
    if name in ("wo",) or (name == "down" and "moe" not in names[-2:]):
        return P(*([None] * (ndim - 2)), "x", None)
    if name in ("gate", "up", "down"):                  # (L, E, ., .)
        return P(None, "x", None, None)
    return P(*([None] * ndim))


def shardings(m: dict, mesh):
    """The weights' layout on ``mesh``; a dim that does not divide over
    the axis stays whole."""
    n = mesh.shape["x"]

    def one(path, shape):
        spec = _spec(path, len(shape))
        return NamedSharding(mesh, P(*(
            a if a is None or shape[i] % n == 0 else None
            for i, a in enumerate(spec))))
    return jax.tree_util.tree_map_with_path(
        one, moe_layout.shapes(m), is_leaf=lambda x: isinstance(x, tuple))


# --------------------------------------------------------------------------
# RANL rounds
# --------------------------------------------------------------------------

def _worker_rows(batch, i, n):
    rows = batch["tokens"].shape[0] // n
    return (batch["tokens"][i * rows:(i + 1) * rows],
            batch["labels"][i * rows:(i + 1) * rows])


def run(params, batches, rng, m: dict, opt: dict, *, steps: int,
        dtype=jnp.float32, q_chunk=1024):
    """Init phase on ``batches[0]`` and ``steps`` rounds on the next ones.
    Returns what ``ranl_llm.run`` returns: ``losses``, ``memory_norms``
    after round 1, ``update_norms`` after the last and ``grad_norms`` of
    the init gradients, keyed by leaf path."""
    N = opt["num_workers"]
    mdt = jnp.dtype(opt["memory_dtype"])
    prec = "highest" if dtype == jnp.float32 else "default"

    @jax.jit
    def grad(p, tokens, labels):
        with jax.default_matmul_precision(prec):
            return jax.value_and_grad(loss)(p, tokens, labels, m, q_chunk)

    p0 = jax.tree.map(lambda a: a.astype(dtype), params)

    def worker_grads(p, batch):
        out = [grad(p, *_worker_rows(batch, i, N)) for i in range(N)]
        return [o[0] for o in out], [o[1] for o in out]

    _, G0 = worker_grads(p0, batches[0])
    C = [jax.tree.map(lambda g: g.astype(mdt), g) for g in G0]
    h = jax.tree.map(lambda *gs: sum(jnp.square(g.astype(dtype))
                                     for g in gs) / N, *G0)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    names = [jax.tree_util.keystr(k) for k, _ in leaves]
    # regions: each stack's layers in forward order, then glue leaves
    first, Q = {}, 0
    for stack in STACKS:
        first[stack] = Q
        Q += params[stack]["ln1"].shape[0]
    stack_of = [next((k.key for k in path
                      if getattr(k, "key", None) in STACKS), None)
                for path, _ in leaves]
    Q += stack_of.count(None)
    treedef = jax.tree_util.tree_structure(params)

    @jax.jit
    def round_update(p, G, C, h, mk_all):
        Gl = [jax.tree_util.tree_leaves(x) for x in G]
        Cl = [jax.tree_util.tree_leaves(x) for x in C]
        new_p, new_c = [], [[] for _ in range(N)]
        for j, (pl, hl) in enumerate(zip(jax.tree_util.tree_leaves(p),
                                         jax.tree_util.tree_leaves(h))):
            gs = [Gl[i][j] for i in range(N)]
            cs = [Cl[i][j].astype(dtype) for i in range(N)]
            if stack_of[j] is not None:
                q0, L = first[stack_of[j]], pl.shape[0]
                mk = [mk_all[i, q0:q0 + L].reshape(
                    (L,) + (1,) * (gs[0].ndim - 1)) for i in range(N)]
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
        p, C = round_update(p, G, C, h, policy_masks(opt, rng, s, N, Q))
        del G
        out["losses"].append(float(sum(x.astype(jnp.float32)
                                       for x in losses) / N))
        if s == 0:
            out["memory_norms"] = _leaf_norms(C, names, stacked=True)
    out["update_norms"] = _leaf_norms(
        jax.tree.map(lambda a, b: a.astype(jnp.float32)
                     - b.astype(jnp.float32), p, p0), names)
    return out
