"""Model assembly: init, forward (train / prefill / decode), loss.

Layer stacks are ``lax.scan`` over stacked per-layer params (compact HLO,
depth-independent compile time).  ``scan_layers=False`` unrolls the stack in
Python — used by the roofline cost graphs for exact per-layer FLOP counting.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .attention import (apply_attention, apply_mla, init_attention,
                        init_cache, init_mla)
from .common import embed_init, dense_init, rms_norm, softmax_cross_entropy
from .moe import apply_moe, init_moe
from .rwkv import (apply_channel_mix, apply_time_mix, init_channel_mix,
                   init_rwkv_state, init_time_mix)
from .sharding import shard_hint
from .ssm import apply_ssm, init_ssm, init_ssm_state


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def init_layer(cfg, key, dtype=jnp.float32, dense=False):
    """One block; ``dense`` gives an MoE config's leading dense FFN."""
    d = cfg.d_model
    ones = lambda: jnp.ones((d,), dtype)
    keys = jax.random.split(key, 4)
    if cfg.attn_free:
        return {
            "ln1": ones(), "tmix": init_time_mix(cfg, keys[0], dtype),
            "ln2": ones(), "cmix": init_channel_mix(cfg, keys[1], dtype),
        }
    attn = init_mla if cfg.uses_mla else init_attention
    p = {"ln1": ones(), "attn": attn(cfg, keys[0], dtype), "ln2": ones()}
    if cfg.family == "hybrid":
        p["ssm"] = init_ssm(cfg, keys[1], dtype)
    if cfg.num_experts and not dense:
        p["moe"] = init_moe(cfg, keys[2], dtype)
    else:
        from .common import init_swiglu
        p["mlp"] = init_swiglu(keys[2], d, cfg.d_ff, dtype)
    return p


def init_model(cfg, key, dtype=jnp.float32):
    keys = jax.random.split(key, 4 + cfg.num_layers)
    d, V = cfg.d_model, cfg.vocab_size
    params = {}
    if cfg.modality == "audio":
        params["embed"] = embed_init(keys[0], (cfg.num_codebooks, V, d), dtype)
        params["lm_head"] = dense_init(keys[1], (cfg.num_codebooks, d, V),
                                       dtype)
    else:
        params["embed"] = embed_init(keys[0], (V, d), dtype)
        if not cfg.tie_embeddings:
            params["lm_head"] = dense_init(keys[1], (d, V), dtype)
    if cfg.modality == "vision":
        params["vision_proj"] = dense_init(
            keys[2], (cfg.vision_embed_dim, d), dtype)
    nd = cfg.first_dense_layers
    if nd:
        params["dense_layers"] = jax.vmap(
            lambda k: init_layer(cfg, k, dtype, dense=True))(
                jnp.stack(keys[4:4 + nd]))
    layer_keys = jnp.stack(keys[4 + nd:4 + cfg.num_layers])
    params["layers"] = jax.vmap(
        lambda k: init_layer(cfg, k, dtype))(layer_keys)
    params["final_norm"] = jnp.ones((d,), dtype)
    return params


# --------------------------------------------------------------------------
# blocks
# --------------------------------------------------------------------------

def apply_block(lp, x, cfg, *, mode, layer_cache, positions, pos, window,
                q_chunk, kv_chunk):
    """Returns (x, cache_out_or_None, aux): aux is the aux-loss scalar, or
    for an MoE block a dict of it ("aux") and the layer's counters."""
    aux = jnp.zeros((), jnp.float32)
    cache_out = None

    if cfg.attn_free:  # RWKV
        ts = None if mode == "train" else (
            None if layer_cache is None else
            {"last_x": layer_cache["tmix_last_x"], "wkv": layer_cache["wkv"]})
        if mode == "prefill":
            ts = None
        h, tstate = apply_time_mix(lp["tmix"], rms_norm(x, lp["ln1"]), cfg,
                                   state=ts)
        x = x + h
        cs = None if mode in ("train", "prefill") else (
            None if layer_cache is None else
            {"last_x": layer_cache["cmix_last_x"]})
        h, cstate = apply_channel_mix(lp["cmix"], rms_norm(x, lp["ln2"]), cfg,
                                      state=cs)
        x = x + h
        if mode != "train":
            cache_out = {"tmix_last_x": tstate["last_x"],
                         "wkv": tstate["wkv"],
                         "cmix_last_x": cstate["last_x"]}
        return x, cache_out, aux

    # --- attention (+ optional parallel SSM branch) ---
    h_in = rms_norm(x, lp["ln1"], cfg.norm_eps)
    if cfg.uses_mla:
        if mode == "decode":
            raise NotImplementedError("latent attention has no decode cache")
        attn_out = apply_mla(lp["attn"], h_in, cfg, positions,
                             q_chunk=q_chunk, kv_chunk=kv_chunk)
        attn_cache_out = None
    else:
        attn_cache = None if layer_cache is None else layer_cache.get("attn")
        attn_out, attn_cache_out = apply_attention(
            lp["attn"], h_in, cfg, positions,
            cache=attn_cache if mode == "decode" else None,
            pos=pos, window=window, q_chunk=q_chunk, kv_chunk=kv_chunk,
            return_cache=(mode == "prefill"))
    if cfg.family == "hybrid":
        ssm_state = None if layer_cache is None else layer_cache.get("ssm")
        ssm_out, ssm_state_out = apply_ssm(
            lp["ssm"], h_in, cfg,
            state=ssm_state if mode == "decode" else None)
        x = x + 0.5 * (attn_out + ssm_out)
    else:
        x = x + attn_out

    h2 = rms_norm(x, lp["ln2"], cfg.norm_eps)
    if "moe" in lp:
        ffn_out, moe_aux, stats = apply_moe(lp["moe"], h2, cfg)
        aux = {"aux": moe_aux, **stats}
    else:
        from .common import apply_swiglu
        ffn_out = apply_swiglu(lp["mlp"], h2)
    x = x + ffn_out

    if mode != "train":
        cache_out = {"attn": attn_cache_out}
        if cfg.family == "hybrid":
            cache_out["ssm"] = ssm_state_out
    return x, cache_out, aux


# --------------------------------------------------------------------------
# embeddings / head
# --------------------------------------------------------------------------

def embed_inputs(params, batch, cfg):
    tokens = batch["tokens"]
    if cfg.modality == "audio":
        # tokens: (B, S, C); sum codebook embeddings
        parts = [params["embed"][c][tokens[..., c]]
                 for c in range(cfg.num_codebooks)]
        h = sum(parts)
    else:
        h = params["embed"][tokens]
    if cfg.modality == "vision" and "patch_embeds" in batch:
        patches = batch["patch_embeds"] @ params["vision_proj"]
        h = jax.lax.dynamic_update_slice(h, patches.astype(h.dtype), (0, 0, 0))
    return h


def lm_logits(params, h, cfg):
    if cfg.modality == "audio":
        return jnp.einsum("bsd,cdv->bscv", h, params["lm_head"])
    if cfg.tie_embeddings:
        return h @ params["embed"].T
    return h @ params["lm_head"]


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fold_aux(auxs, stats):
    """Sum a stack's aux losses; an MoE stack's counters go to ``stats``."""
    if isinstance(auxs, dict):
        stats.update({k: v for k, v in auxs.items() if k != "aux"})
        return auxs["aux"].sum()
    return auxs.sum()


def forward(params, batch, cfg, *, mode="train", cache=None,
            scan_layers=True, remat=True, window=None,
            q_chunk=1024, kv_chunk=1024, compute_logits=True,
            with_stats=False):
    """Returns (logits, new_cache, aux), and the MoE layers' counters
    (per layer, stacked) after them with ``with_stats``.

    batch: {"tokens": (B,S) or (B,S,C)[, "patch_embeds", "pos"]}.
    mode: train | prefill | decode.  decode consumes+updates ``cache``.
    window: sliding window (None -> cfg default: hybrid archs train with
    their configured SWA window; others full attention).
    Stacks run in order: ``params["dense_layers"]`` (an MoE config's
    leading dense layers) where present, then ``params["layers"]``.
    """
    if window is None:
        window = cfg.sliding_window if cfg.family == "hybrid" else 0
    x = embed_inputs(params, batch, cfg)
    B, S = x.shape[:2]
    if mode == "decode":
        pos = batch["pos"]
        positions = jnp.broadcast_to(
            jnp.asarray(pos, jnp.int32)[None, None], (B, S))
    else:
        pos = None
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    block = partial(apply_block, cfg=cfg, mode=mode, positions=positions,
                    pos=pos, window=window, q_chunk=q_chunk,
                    kv_chunk=kv_chunk)

    stacks = [n for n in ("dense_layers", "layers") if n in params]
    auxes, stats = [], {}
    new_cache = None
    if scan_layers:
        for name in stacks:
            layers = params[name]
            if mode == "train":
                def body(h, lp):
                    fn = (jax.checkpoint(lambda h_, lp_: block(
                        lp_, h_, layer_cache=None)[::2]) if remat
                        else (lambda h_, lp_: block(
                            lp_, h_, layer_cache=None)[::2]))
                    h, aux = fn(h, lp)
                    # sequence parallelism between blocks: the scan-carry
                    # activation stash shards its seq dim over "model"
                    # (Megatron SP) — a no-op without an ambient mesh.  The
                    # batch dim is UNCONSTRAINED: under the RANL
                    # vmap-over-workers it is the per-worker batch (worker
                    # axis carries "data" instead).
                    from .sharding import UNCONSTRAINED
                    h = shard_hint(h, (UNCONSTRAINED, "model", None))
                    return h, aux
                x, auxs = jax.lax.scan(body, x, layers)
            elif mode == "prefill":
                def body(h, lp):
                    h, c, aux = block(lp, h, layer_cache=None)
                    return h, (c, aux)
                x, (caches, auxs) = jax.lax.scan(body, x, layers)
                new_cache = dict(new_cache or {}, **{name: caches})
            else:  # decode
                def body(h, lp_cache):
                    lp, lc = lp_cache
                    h, c, aux = block(lp, h, layer_cache=lc)
                    return h, (c, aux)
                x, (caches, auxs) = jax.lax.scan(
                    body, x, (layers, cache[name]))
                new_cache = dict(new_cache or {}, **{name: caches})
            auxes.append(_fold_aux(auxs, stats))
    else:
        cache_outs = {}
        for name in stacks:
            layers = params[name]
            for i in range(jax.tree.leaves(layers)[0].shape[0]):
                lp = jax.tree.map(lambda a: a[i], layers)
                lc = (None if cache is None
                      else jax.tree.map(lambda a: a[i], cache[name]))
                x, c, a = block(lp, x, layer_cache=lc)
                if isinstance(a, dict):
                    for k, v in a.items():
                        if k != "aux":
                            stats.setdefault(k, []).append(v)
                    a = a["aux"]
                auxes.append(a)
                if c is not None:
                    cache_outs.setdefault(name, []).append(c)
        stats = {k: jnp.stack(v) for k, v in stats.items()}
        if cache_outs:
            new_cache = {name: jax.tree.map(lambda *xs: jnp.stack(xs), *cs)
                         for name, cs in cache_outs.items()}

    aux = (sum(auxes[1:], auxes[0]) if auxes
           else jnp.zeros((), jnp.float32))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    out = x if not compute_logits else lm_logits(params, x, cfg)
    if with_stats:
        return out, new_cache, aux, stats
    return out, new_cache, aux


def lm_loss(params, batch, cfg, *, loss_chunk=1024, with_stats=False,
            **fwd_kwargs):
    """Next-token loss with *chunked* cross-entropy: logits are produced
    (and re-produced in the backward pass via checkpoint) one sequence chunk
    at a time, so the (B, S, vocab) tensor never materializes — at 151936
    vocab that is the difference between a multi-GiB spike and ~chunk/S of
    it.  Chunks are a Python loop (straight-line HLO) so cost analysis
    counts every FLOP.

    ``with_stats`` returns (loss, counters): the MoE layers' routed rows
    (``moe_counters``), for ``value_and_grad(..., has_aux=True)``."""
    h, _, aux, stats = forward(params, batch, cfg, mode="train",
                               compute_logits=False, with_stats=True,
                               **fwd_kwargs)
    labels = batch["labels"]
    B, S = h.shape[:2]
    chunk = min(loss_chunk, S)
    n_chunks = (S + chunk - 1) // chunk

    @jax.checkpoint
    def chunk_loss(hc, lc):
        logits = lm_logits(params, hc, cfg).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        return jnp.sum(logz - gold)

    total = jnp.zeros((), jnp.float32)
    denom = 0
    for i in range(n_chunks):
        sl = slice(i * chunk, min((i + 1) * chunk, S))
        total = total + chunk_loss(h[:, sl], labels[:, sl])
        denom += labels[:, sl].size
    loss = total / denom + aux
    return (loss, moe_counters(stats)) if with_stats else loss


def moe_counters(stats):
    """Per-step routing counters from the MoE stack's per-layer ones:
    ``held_rows`` (rows the held experts computed), ``absent_rows``
    (routes to experts not held here), ``dropped_rows`` and
    ``expert_rows`` (layers, held experts).  Empty without MoE layers."""
    if not stats:
        return {}
    rows = stats["expert_rows"]
    return {"held_rows": jnp.sum(rows), "absent_rows":
            jnp.sum(stats["absent_rows"]),
            "dropped_rows": jnp.sum(stats["dropped_rows"]),
            "expert_rows": rows}


# --------------------------------------------------------------------------
# caches
# --------------------------------------------------------------------------

def init_decode_cache(cfg, batch: int, cache_len: int, dtype=jnp.bfloat16):
    """Stacked (num_layers-leading) decode cache for a fresh sequence."""
    def one_layer(_):
        if cfg.attn_free:
            st = init_rwkv_state(cfg, batch, dtype)
            return st
        c = {"attn": init_cache(cfg, batch, cache_len, dtype)}
        if cfg.family == "hybrid":
            c["ssm"] = init_ssm_state(cfg, batch, dtype)
        return c
    layers = jax.vmap(one_layer)(jnp.arange(cfg.num_layers))
    return {"layers": layers}
