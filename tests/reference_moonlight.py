"""Plain reference of the Moonlight (DeepSeek-V3) block and loss, for tests.

Straight ``jax.numpy`` at float32 under ``jax.default_matmul_precision(
"highest")``: full causal softmax, a dense loop over the held experts
(each expert runs on every token, weighted by its routing weight, zero
where not selected), no kernels, sharding, sorting or remat.  Reads the
program's parameter tree and the sizes of its ModelConfig.

Departures from the published model, as in the program:
* only the held experts' part of the routed output is computed (the
  chip's share of an expert-parallel deployment);
* the selection bias is frozen (no balancing update) and there is no
  sequence-wise auxiliary loss;
* rotary on the rope dims is rotate-half, where DeepSeek interleaves the
  pairs: the same up to a fixed permutation of the rope columns.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def rotary(x, theta):
    """Rotate-half rotary embedding of x (B, S, heads, dim)."""
    S, dim = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(p, x, c):
    B, S, _ = x.shape
    H, r = c.num_heads, c.kv_lora_rank
    nope, rope, hv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    q = (x @ p["wq"]).reshape(B, S, H, nope + rope)
    kv_a = x @ p["wkv_a"]
    kv = (rms_norm(kv_a[..., :r], p["kv_norm"], c.norm_eps)
          @ p["wkv_b"]).reshape(B, S, H, nope + hv)
    k_rope = rotary(kv_a[..., None, r:], c.rope_theta)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], c.rope_theta)],
                        -1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.repeat(k_rope, H, axis=2)], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(nope + rope)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), kv[..., nope:])
    return o.reshape(B, S, H * hv) @ p["wo"]


def swiglu(p, x):
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def routing(p, x, c):
    """(T, E) weight of every expert for every token: sigmoid scores,
    top-k by score + bias, weights normalised and scaled; 0 elsewhere."""
    s = jax.nn.sigmoid(x @ p["router"])
    _, sel = jax.lax.top_k(s + p["router_bias"], c.experts_per_token)
    chosen = jax.nn.one_hot(sel, s.shape[-1]).sum(axis=-2)
    w = s * chosen
    return w / w.sum(axis=-1, keepdims=True) * c.routed_scale


def moe(p, x, c, experts=None, shared=True):
    """Routed part of ``experts`` (default: all held) plus the shared
    experts; x (T, d)."""
    w = routing(p, x, c)
    out = jnp.zeros_like(x)
    for e in (range(p["gate"].shape[0]) if experts is None else experts):
        ep = {k: p[k][e] for k in ("gate", "up", "down")}
        out = out + w[:, e:e + 1] * swiglu(ep, x)
    if shared:
        out = out + swiglu(p["shared"], x)
    return out


def block(p, x, c):
    x = x + mla(p["attn"], rms_norm(x, p["ln1"], c.norm_eps), c)
    h = rms_norm(x, p["ln2"], c.norm_eps)
    if "moe" in p:
        B, S, d = h.shape
        return x + moe(p["moe"], h.reshape(B * S, d), c).reshape(B, S, d)
    return x + swiglu(p["mlp"], h)


def loss(params, tokens, labels, c):
    """Mean next-token cross-entropy over all positions."""
    x = params["embed"][tokens]
    for stack in ("dense_layers", "layers"):
        layers = params[stack]
        for i in range(layers["ln1"].shape[0]):
            x = block(jax.tree.map(lambda a: a[i], layers), x, c)
    logits = rms_norm(x, params["final_norm"], c.norm_eps) \
        @ params["lm_head"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold)
