"""Plain decoder-only transformer loss: the reference of the train cells.

Written from the published description of a Phi-style dense decoder
(pre-norm RMSNorm, grouped-query attention with rotary positions, SwiGLU
feed-forward, tied input/output embedding), with the keys of the
configuration file as run.  Straight ``jax.numpy``: full causal softmax,
no blocking, no remat, no kernels.  Imports nothing of the program.

``dtype`` float32 is the reference (matmuls at "highest" precision);
bfloat16 is its control, the precision below the configuration's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rotary(x, theta, fraction):
    """Rotate-half rotary embedding on the first ``fraction`` of each
    head's dimensions; x (B, S, heads, hd)."""
    B, S, _, hd = x.shape
    rot = int(hd * fraction)
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xr = x[..., :rot].astype(jnp.float32)
    x1, x2 = xr[..., :rot // 2], xr[..., rot // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.astype(x.dtype), x[..., rot:]], axis=-1)


def attention(p, x, m):
    B, S, _ = x.shape
    H, KV, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, KV, hd)
    v = (x @ p["wv"]).reshape(B, S, KV, hd)
    q = rotary(q, m["rope_theta"], m["partial_rotary_factor"])
    k = rotary(k, m["rope_theta"], m["partial_rotary_factor"])
    # query head h reads key/value head h // (H / KV)
    k = jnp.repeat(k, H // KV, axis=2)
    v = jnp.repeat(v, H // KV, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(hd, q.dtype))
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, jnp.asarray(-1e30, s.dtype)
                  if s.dtype == jnp.float32 else jnp.asarray(-3e38, s.dtype))
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", a, v).reshape(B, S, H * hd)
    return o @ p["wo"]


def loss(params, tokens, labels, m):
    """Mean next-token cross-entropy over all positions."""
    eps = m["rms_norm_eps"]
    x = params["embed"][tokens]
    lay = params["layers"]
    for li in range(m["num_hidden_layers"]):
        p = jax.tree.map(lambda a: a[li], lay)
        x = x + attention(p["attn"], rms_norm(x, p["ln1"], eps), m)
        h = rms_norm(x, p["ln2"], eps)
        f = p["mlp"]
        x = x + (jax.nn.silu(h @ f["gate"]) * (h @ f["up"])) @ f["down"]
    h = rms_norm(x, params["final_norm"], eps)
    logits = h @ params["embed"].T
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(logz - gold.astype(jnp.float32))
