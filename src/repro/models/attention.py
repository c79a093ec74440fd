"""GQA and latent (MLA) attention: blocked (flash-style) softmax, sliding
window, KV cache (GQA only).

The blocked implementation unrolls the q/kv block loops in Python so the
per-layer HLO is straight-line: XLA's ``cost_analysis`` then counts every
attention FLOP exactly once per layer, which the roofline pipeline relies on
(``lax.scan``/``while`` bodies are otherwise counted once regardless of trip
count).  Causal block skipping is done at trace time, so the compiled graph
contains only the lower-triangular blocks — HLO FLOPs match the true
causal-attention FLOPs.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .common import apply_rope, dense_init, rms_norm
from .sharding import current_mesh

NEG_INF = -1e30


def init_attention(cfg, key, dtype=jnp.float32):
    hd = cfg.resolved_head_dim
    d, H, KV = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "wq": dense_init(k1, (d, H * hd), dtype),
        "wk": dense_init(k2, (d, KV * hd), dtype),
        "wv": dense_init(k3, (d, KV * hd), dtype),
        "wo": dense_init(k4, (H * hd, d), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _block_mask(q_pos, k_pos, window: int):
    """q_pos: (B, qc), k_pos: (B, kc) -> bool (B, 1, qc, kc). Causal+window."""
    q = q_pos[:, None, :, None]
    k = k_pos[:, None, None, :]
    valid = (k <= q) & (k >= 0)
    if window:
        valid &= k > q - window
    return valid


def blocked_attention(q, k, v, q_pos, k_pos, *, window: int = 0,
                      q_chunk: int = 1024, kv_chunk: int = 1024,
                      static_positions: bool = False):
    """Online-softmax GQA attention (grouped — kv heads are NEVER
    materialized at H width: the einsums carry an explicit (KV, G) group
    split, saving the groups-x kv read amplification that a repeat-KV
    formulation pays; measured on decode in EXPERIMENTS.md §Perf pair 4).

    q, k: (B, Sq|Skv, H|KV, hd) with H = KV*G; v: (B, Skv, KV, hv), whose
    head dim may differ from the query/key one (latent attention).
    q_pos: (B, Sq) int32; k_pos: (B, Skv) int32 (−1 marks empty cache slots).
    static_positions: True when positions are literally ``arange`` (train /
    prefill) — enables trace-time skipping of fully-masked blocks.
    """
    B, Sq, H, hd = q.shape
    Skv, KV, hv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // KV
    scale = 1.0 / math.sqrt(hd)
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    n_q = (Sq + q_chunk - 1) // q_chunk
    n_kv = (Skv + kv_chunk - 1) // kv_chunk

    out_blocks = []
    for i in range(n_q):
        q0, q1 = i * q_chunk, min((i + 1) * q_chunk, Sq)
        qc = q1 - q0
        qb = (q[:, q0:q1].astype(jnp.float32) * scale)     # (B, qc, H, hd)
        qb = qb.reshape(B, qc, KV, G, hd)
        qpb = q_pos[:, q0:q1]
        m = jnp.full((B, KV, G, qc), NEG_INF, jnp.float32)
        l = jnp.zeros((B, KV, G, qc), jnp.float32)
        acc = jnp.zeros((B, KV, G, qc, hv), jnp.float32)
        for j in range(n_kv):
            k0, k1_ = j * kv_chunk, min((j + 1) * kv_chunk, Skv)
            if static_positions:
                # trace-time skip: causal upper blocks / out-of-window blocks
                if k0 > q1 - 1:
                    continue
                if window and (k1_ - 1) < (q0 - window + 1):
                    continue
            kb = k[:, k0:k1_].astype(jnp.float32)          # (B, kc, KV, hd)
            vb = v[:, k0:k1_].astype(jnp.float32)
            kpb = k_pos[:, k0:k1_]
            s = jnp.einsum("bqcgh,bkch->bcgqk", qb, kb)
            mask = _block_mask(qpb, kpb, window)           # (B,1,qc,kc)
            s = jnp.where(mask[:, :, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            corr = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] \
                + jnp.einsum("bcgqk,bkch->bcgqh", p, vb)
            m = m_new
        out = acc / jnp.maximum(l, 1e-30)[..., None]       # (B,KV,G,qc,hv)
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, qc, H, hv)
        out_blocks.append(out)
    return jnp.concatenate(out_blocks, axis=1).astype(q.dtype)


def apply_attention(params, x, cfg, positions, *, cache=None, pos=None,
                    window: int = 0, q_chunk: int = 1024, kv_chunk: int = 1024,
                    return_cache: bool = False):
    """Attention with optional KV cache.

    x: (B, S, d).  positions: (B, S) absolute positions of x tokens.
    cache: None or dict(k=(B, W, KV, hd), v=..., slot_pos=(W,)) — when given,
    runs a decode/append step: the new k/v are written at slot ``pos % W``
    and attention runs over the whole cache.
    return_cache: in prefill mode, also return the freshly-built cache.
    """
    B, S, d = x.shape
    hd = cfg.resolved_head_dim
    H, KV = cfg.num_heads, cfg.num_kv_heads

    q = (x @ params["wq"]).reshape(B, S, H, hd)
    k = (x @ params["wk"]).reshape(B, S, KV, hd)
    v = (x @ params["wv"]).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"])
        k = rms_norm(k, params["k_norm"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if cache is not None:
        W = cache["k"].shape[1]
        slot = jnp.asarray(pos, jnp.int32) % W
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        slot_pos = jax.lax.dynamic_update_slice_in_dim(
            cache["slot_pos"], jnp.asarray(pos, jnp.int32)[None], slot, axis=0)
        new_cache = {"k": ck, "v": cv, "slot_pos": slot_pos}
        k_pos = jnp.broadcast_to(slot_pos[None], (B, W))
        out = blocked_attention(
            q, ck, cv, positions, k_pos, window=window,
            q_chunk=q_chunk, kv_chunk=kv_chunk, static_positions=False)
    else:
        out = blocked_attention(
            q, k, v, positions, positions, window=window,
            q_chunk=q_chunk, kv_chunk=kv_chunk, static_positions=True)
        if return_cache:
            new_cache = {"k": k, "v": v,
                         "slot_pos": positions[0].astype(jnp.int32)}

    out = out.reshape(B, S, H * hd) @ params["wo"]
    return out, new_cache


def init_mla(cfg, key, dtype=jnp.float32):
    """Latent attention with a direct q projection (no q latent)."""
    d, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, hv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wq": dense_init(k1, (d, H * (nope + rope)), dtype),
        "wkv_a": dense_init(k2, (d, r + rope), dtype),
        "kv_norm": jnp.ones((r,), dtype),
        "wkv_b": dense_init(k3, (r, H * (nope + hv)), dtype),
        "wo": dense_init(k4, (H * hv, d), dtype),
    }


def apply_mla(params, x, cfg, positions, *, q_chunk: int = 1024,
              kv_chunk: int = 1024):
    """Latent attention (DeepSeek-V2/V3), train and prefill.

    q = x W_q gives H heads of (nope + rope) dims; [c, k_rope] = x W_kva;
    [k_nope, v] = RMSNorm(c) W_kvb per head; rotary on q_rope and on the
    one k_rope, which every head shares; softmax at (nope + rope)^-1/2.

    Under a mesh whose ``model`` axis divides the heads, the attention core
    runs inside ``shard_map`` on each device's heads: every head's scores
    stay on the device that projected it, forward and backward.
    """
    B, S, _ = x.shape
    H, r = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, hv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    with jax.named_scope("mla"):
        q = (x @ params["wq"]).reshape(B, S, H, nope + rope)
        kv_a = x @ params["wkv_a"]
        c = rms_norm(kv_a[..., :r], params["kv_norm"], cfg.norm_eps)
        kv = (c @ params["wkv_b"]).reshape(B, S, H, nope + hv)
        q_rope = apply_rope(q[..., nope:], positions, cfg.rope_theta)
        k_rope = apply_rope(kv_a[..., None, r:], positions, cfg.rope_theta)
        q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_rope, (B, S, H, rope))],
            axis=-1)
        attend = partial(blocked_attention, q_chunk=q_chunk,
                         kv_chunk=kv_chunk, static_positions=True)
        mesh = current_mesh()
        shards = mesh.shape.get("model", 1) if mesh is not None else 1
        if shards > 1 and H % shards == 0:
            hs = P(None, None, "model", None)
            attend = jax.shard_map(attend, mesh=mesh,
                                   in_specs=(hs, hs, hs, P(), P()),
                                   out_specs=hs, check_vma=False)
        out = attend(q, k, kv[..., nope:], positions, positions)
        return out.reshape(B, S, H * hv) @ params["wo"]


def init_cache(cfg, batch: int, cache_len: int, dtype=jnp.bfloat16):
    """Empty per-layer KV cache (slot_pos −1 = invalid)."""
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, cache_len, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, cache_len, cfg.num_kv_heads, hd), dtype),
        "slot_pos": jnp.full((cache_len,), -1, jnp.int32),
    }
