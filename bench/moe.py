"""The Moonlight configuration's layout, weights and work counts, kept with
the benchmark: weights from ``--seed`` in the program's tree layout, and
the FLOPs and bytes behind ``step_mfu.moe`` and ``expert_roofline.moe``.
Nothing here reads the program."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

BIAS_STD = 1e-3     # the frozen selection bias: small, drawn from the seed


def mla_shapes(m: dict, L: int):
    d, H, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, hv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    return {"wq": (L, d, H * (nope + rope)), "wkv_a": (L, d, r + rope),
            "kv_norm": (L, r), "wkv_b": (L, r, H * (nope + hv)),
            "wo": (L, H * hv, d)}


def shapes(m: dict):
    """Parameter shapes: a leading dense stack, then the MoE stack."""
    d, V = m["hidden_size"], m["vocab_size"]
    nd = m["first_k_dense_replace"]
    L = m["num_hidden_layers"] - nd
    ff, fe = m["intermediate_size"], m["moe_intermediate_size"]
    held, E = m["n_routed_experts"], m["router_experts"]
    fs = m["n_shared_experts"] * fe
    swiglu = lambda n, w: {"gate": (n, d, w), "up": (n, d, w),
                           "down": (n, w, d)}
    return {
        "embed": (V, d), "lm_head": (d, V), "final_norm": (d,),
        "dense_layers": {"ln1": (nd, d), "ln2": (nd, d),
                         "attn": mla_shapes(m, nd), "mlp": swiglu(nd, ff)},
        "layers": {
            "ln1": (L, d), "ln2": (L, d), "attn": mla_shapes(m, L),
            "moe": {"router": (L, d, E), "router_bias": (L, E),
                    "gate": (L, held, d, fe), "up": (L, held, d, fe),
                    "down": (L, held, fe, d), "shared": swiglu(L, fs)}},
    }


def _leaf_init(path, shape, key):
    name = path[-1].key
    if name in ("ln1", "ln2", "final_norm", "kv_norm"):
        return jnp.ones(shape, jnp.float32)
    if name == "router_bias":
        return BIAS_STD * jax.random.normal(key, shape, jnp.float32)
    std = 0.02 if name == "embed" else shape[-2] ** -0.5
    return jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                       jnp.float32) * std


_DIMS = ("hidden_size", "vocab_size", "first_k_dense_replace",
         "num_hidden_layers", "intermediate_size", "moe_intermediate_size",
         "n_routed_experts", "router_experts", "n_shared_experts",
         "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim")


def _weights(dims, key):
    tree = shapes(dict(dims))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    return jax.tree_util.tree_unflatten(
        treedef, [_leaf_init(p, s, k) for (p, s), k in zip(leaves, keys)])


_JITTED = {}


def weights(m: dict, key, shardings=None):
    """f32 weights in one jitted call: truncated normals at 1/sqrt(fan_in)
    (0.02 for the embedding), norm scales at one, the selection bias at
    ``BIAS_STD``; laid out with ``shardings`` where given."""
    dims = tuple((k, int(m[k])) for k in _DIMS)
    cache_key = (dims, jax.tree_util.tree_structure(shardings),
                 tuple(jax.tree.leaves(shardings)))
    if cache_key not in _JITTED:
        _JITTED[cache_key] = jax.jit(partial(_weights, dims),
                                     out_shardings=shardings)
    return _JITTED[cache_key](key)


# --------------------------------------------------------------------------
# work counts
# --------------------------------------------------------------------------

def mla_params(m: dict) -> int:
    return sum(a * b for _, a, b in (
        s for k, s in mla_shapes(m, 1).items() if k != "kv_norm"))


def dense_matmul_params(m: dict) -> int:
    """Parameters that enter a matmul for every token: attention, the
    dense FFN, the routers and shared experts, and the output head.  The
    routed experts see only their rows; the embedding gather is not a
    matmul."""
    d = m["hidden_size"]
    nd = m["first_k_dense_replace"]
    L = m["num_hidden_layers"] - nd
    shared = 3 * d * m["n_shared_experts"] * m["moe_intermediate_size"]
    return (m["num_hidden_layers"] * mla_params(m)
            + nd * 3 * d * m["intermediate_size"]
            + L * (d * m["router_experts"] + shared)
            + m["vocab_size"] * d)


def expert_row_params(m: dict) -> int:
    """Matmul parameters one routed row passes through (gate, up, down)."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def attention_flops(m: dict, batch: int, seq: int) -> float:
    """Forward FLOPs of the causal QK^T (dim nope + rope) and PV (dim v)
    matmuls over the lower triangle, all layers."""
    pairs = batch * seq * (seq + 1) / 2
    dims = m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]
    return (2.0 * pairs * m["num_attention_heads"] * dims
            * m["num_hidden_layers"])


def train_step_flops(m: dict, batch: int, seq: int, held_rows: float):
    """Model FLOPs of one step, forward and backward: 6 x (tokens x the
    dense matmul params + the rows the held experts computed x their
    params), plus 3 x the attention matmuls.  Recomputation does not
    count."""
    return (6.0 * (batch * seq * dense_matmul_params(m)
                   + held_rows * expert_row_params(m))
            + 3.0 * attention_flops(m, batch, seq))


def expert_least_s(m: dict, held_rows: float, chips: int, model_shards: int,
                   peak: dict) -> float:
    """Least device time per step of one device's grouped matmuls, forward
    and backward: the larger of its rows' FLOPs over the bf16 peak and of
    its least bytes (its experts' f32 weights once, each row in and out)
    over the HBM bandwidth.  Rows split evenly over the devices."""
    rows = held_rows / chips
    flops = 6.0 * rows * expert_row_params(m)
    weights = m["n_routed_experts"] // model_shards * expert_row_params(m)
    nbytes = 4.0 * (weights + 2 * rows * m["hidden_size"])
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
