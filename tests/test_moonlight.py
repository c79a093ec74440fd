"""Moonlight (latent attention, expert-share MoE, leading dense layer)
against the plain float32 reference ``reference_moonlight`` on seeded
weights at a small size.

Tolerances: on the CPU the program computes in float32 like the
reference, so the gaps are float32 round-off of reordered sums (blocked
softmax, sorted expert rows, the tensor-parallel shares): measured below
1e-5 relative.  Each bound sits well above that and well below what one
bfloat16 rounding of the matmul inputs gives (~1e-2 relative), which
``test_bf16_matmuls_fail_the_tolerance`` checks.
"""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

import reference_moonlight as ref
from repro.configs import get_config, smoke_variant
from repro.data import make_batch
from repro.launch.train import build_loss
from repro.models import init_model
from repro.models.attention import apply_mla, init_mla
from repro.models.moe import apply_moe, init_moe
from repro.optim import RanlLLMConfig, init_state, region_layout, train_step

KEY = jax.random.PRNGKey(7)
# relative gaps: float32 round-off of reordered sums is ~1e-6 here
BLOCK_RTOL = 1e-4
# the RANL round divides by a curvature floored at mu_rel x its mean, so
# gradient round-off can grow by up to ~1/mu_rel = 20x in the update
ROUND_RTOL = 1e-3


def _cfg(**kw):
    """Smoke Moonlight with 8 routed experts top-3, 4 of them held."""
    base = dataclasses.replace(
        smoke_variant(get_config("moonlight-16b-a3b")), num_experts=8,
        experts_per_token=3, experts_held=4)
    return dataclasses.replace(base, **kw)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _moe_params(cfg, key=KEY):
    p = init_moe(cfg, key)
    # a selection bias large enough to change some choices
    p["router_bias"] = 0.05 * jax.random.normal(jax.random.fold_in(key, 3),
                                                p["router_bias"].shape)
    return p


def test_mla_block_matches_reference():
    cfg = _cfg()
    p = init_mla(cfg, KEY)
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 24, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(24), (2, 24))
    got = apply_mla(p, x, cfg, pos, q_chunk=8, kv_chunk=8)
    with jax.default_matmul_precision("highest"):
        want = ref.mla(p, x, cfg)
    assert _rel(got, want) < BLOCK_RTOL


def test_expert_share_layer_matches_reference():
    cfg = _cfg()
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 16, cfg.d_model))
    out, aux, stats = apply_moe(p, x, cfg)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(p, x.reshape(32, -1), cfg)
        w = ref.routing(p, x.reshape(32, -1), cfg)
    assert _rel(out.reshape(32, -1), want) < BLOCK_RTOL
    routes = np.asarray(w > 0)
    assert int(stats["dropped_rows"]) == 0
    np.testing.assert_array_equal(stats["expert_rows"],
                                  routes[:, :4].sum(axis=0))
    assert int(stats["absent_rows"]) == routes[:, 4:].sum()
    assert float(aux) == 0.0


def test_forced_imbalance_drops_no_row():
    """Every token routed to the same three experts, two of them held:
    each held one gets every row and none is dropped."""
    cfg = _cfg()
    p = _moe_params(cfg)
    p["router_bias"] = jnp.zeros((8,)).at[jnp.array([0, 3, 6])].set(10.0)
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (2, 16, cfg.d_model))
    out, _, stats = apply_moe(p, x, cfg)
    np.testing.assert_array_equal(stats["expert_rows"], [32, 0, 0, 32])
    assert int(stats["dropped_rows"]) == 0
    assert int(stats["absent_rows"]) == 32
    with jax.default_matmul_precision("highest"):
        want = ref.moe(p, x.reshape(32, -1), cfg)
    assert _rel(out.reshape(32, -1), want) < BLOCK_RTOL


def test_unwritten_ragged_dot_rows_reach_no_gradient(monkeypatch):
    """The TPU's ragged dot leaves the rows past the groups unwritten (the
    CPU writes zeros).  With NaN planted there, and in an empty group's
    product, the layer's output and its gradients still match the
    reference: under forced imbalance two held experts get no row."""
    cfg = _cfg()
    p = _moe_params(cfg)
    p["router_bias"] = jnp.zeros((8,)).at[jnp.array([0, 3, 6])].set(10.0)
    x = jax.random.normal(jax.random.fold_in(KEY, 4), (2, 16, cfg.d_model))
    probe = jax.random.normal(jax.random.fold_in(KEY, 5), (32, cfg.d_model))
    rd, rdg = jax.lax.ragged_dot, jax.lax.ragged_dot_general

    def unset_rows(lhs, rhs, group_sizes, **kw):
        out = rd(lhs, rhs, group_sizes, **kw)
        live = jnp.arange(out.shape[0]) < jnp.sum(group_sizes)
        return jnp.where(live[:, None], out, jnp.nan)

    def unset_groups(lhs, rhs, group_sizes, dims, **kw):
        out = rdg(lhs, rhs, group_sizes, dims, **kw)
        return jnp.where((group_sizes > 0)[:, None, None], out, jnp.nan)

    monkeypatch.setattr(jax.lax, "ragged_dot", unset_rows)
    monkeypatch.setattr(jax.lax, "ragged_dot_general", unset_groups)
    experts = ("gate", "up", "down")

    def got(x, ws):
        out = apply_moe(dict(p, **dict(zip(experts, ws))), x, cfg)[0]
        return jnp.sum(out.reshape(32, -1) * probe)

    def want(x, ws):
        return jnp.sum(ref.moe(dict(p, **dict(zip(experts, ws))),
                               x.reshape(32, -1), cfg) * probe)

    ws = [p[n] for n in experts]
    g_val, g_grad = jax.value_and_grad(got, argnums=(0, 1))(x, ws)
    with jax.default_matmul_precision("highest"):
        w_val, w_grad = jax.value_and_grad(want, argnums=(0, 1))(x, ws)
    assert abs(float(g_val - w_val)) < BLOCK_RTOL * abs(float(w_val))
    for a, b in zip(jax.tree.leaves(g_grad), jax.tree.leaves(w_grad)):
        assert np.isfinite(np.asarray(a)).all()
        assert _rel(a, b) < BLOCK_RTOL


def test_bf16_matmuls_fail_the_tolerance():
    """The control: the reference with bfloat16 matmul inputs misses the
    block tolerance, so the comparisons above can tell the precisions
    apart."""
    cfg = _cfg()
    p = _moe_params(cfg)
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (32, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        want = ref.moe(p, x, cfg)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), p)
    got = ref.moe(low, x.astype(jnp.bfloat16), cfg)
    assert _rel(got.astype(jnp.float32), want) > 10 * BLOCK_RTOL


def test_region_layout_runs_across_the_two_stacks():
    cfg = _cfg(num_layers=3)
    params = init_model(cfg, KEY)
    n, n_layer, infos = region_layout(params)
    assert params["dense_layers"]["ln1"].shape[0] == 1
    assert params["layers"]["ln1"].shape[0] == 2
    assert n_layer == 3
    leaves = jax.tree_util.tree_leaves_with_path(params)
    firsts = {}
    for (path, _), info in zip(leaves, infos):
        name = jax.tree_util.keystr(path)
        firsts[name.split("']")[0]] = info
    assert firsts["['dense_layers"] == ("layer", (0, 1))
    assert firsts["['layers"] == ("layer", (1, 2))
    glue = [v for k, v in infos if k == "glue"]
    assert glue == [3, 4, 5] and n == 6     # embed, final_norm, lm_head


def test_phi4_region_layout_is_unchanged():
    cfg = smoke_variant(get_config("phi4-mini-3.8b"))
    params = jax.eval_shape(lambda: init_model(cfg, KEY))
    n, n_layer, infos = region_layout(params)
    assert (n, n_layer) == (4, 2)
    assert {v for k, v in infos if k == "layer"} == {(0, 2)}
    assert [v for k, v in infos if k == "glue"] == [2, 3]


def _reference_round(params, batches, masks, cfg, rcfg):
    """Init phase on batches[0] and one RANL round on batches[1], from
    the reference's per-worker gradients; returns (loss, memory, new
    params).  Regions: the dense layer, then the MoE layers, then glue
    (always trained)."""
    N = rcfg.num_workers

    def worker_grads(batch):
        out = []
        for i in range(N):
            rows = slice(i * 2, (i + 1) * 2)
            out.append(jax.value_and_grad(ref.loss)(
                params, batch["tokens"][rows], batch["labels"][rows], cfg))
        return [o[0] for o in out], [o[1] for o in out]

    with jax.default_matmul_precision("highest"):
        _, G0 = worker_grads(batches[0])
        losses, G = worker_grads(batches[1])
    h = jax.tree.map(lambda *g: sum(x * x for x in g) / N, *G0)
    first = {"dense_layers": 0, "layers": 1}

    def update(path, p, hl, *gc):
        gs, cs = gc[:N], gc[N:]
        stack = getattr(path[0], "key", None)
        if stack in first:
            depth = p.shape[0]
            mk = [masks[i, first[stack]:first[stack] + depth].reshape(
                (depth,) + (1,) * (p.ndim - 1)) for i in range(N)]
        else:
            mk = [jnp.ones((), bool)] * N
        count = sum(m.astype(jnp.float32) for m in mk)
        fresh = sum(jnp.where(m, g, 0.0) for m, g in zip(mk, gs))
        g = jnp.where(count > 0, fresh / jnp.maximum(count, 1.0),
                      sum(cs) / N)
        mem = [jnp.where(m, gi, ci) for m, gi, ci in zip(mk, gs, cs)]
        delta = rcfg.lr * g / jnp.maximum(
            hl, rcfg.mu + rcfg.mu_rel * jnp.mean(hl))
        scale = jnp.minimum(1.0, rcfg.trust_ratio
                            * (jnp.linalg.norm(p) + 1.0)
                            / jnp.maximum(jnp.linalg.norm(delta), 1e-20))
        return p - scale * delta, jnp.stack(mem)

    out = jax.tree_util.tree_map_with_path(update, params, h, *G, *G0)
    is_pair = lambda x: isinstance(x, tuple)
    new_p = jax.tree.map(lambda t: t[0], out, is_leaf=is_pair)
    memory = jax.tree.map(lambda t: t[1], out, is_leaf=is_pair)
    return sum(losses) / N, memory, new_p


def test_train_step_round_matches_reference():
    cfg = _cfg(num_layers=3)
    params = init_model(cfg, KEY)
    # give the frozen selection bias some spread, as the benchmark does
    params["layers"]["moe"]["router_bias"] = 0.05 * jax.random.normal(
        KEY, params["layers"]["moe"]["router_bias"].shape)
    loss_fn = build_loss(cfg, q_chunk=8, kv_chunk=8)
    batches = [make_batch(cfg, jax.random.fold_in(KEY, 10 + i), 4, 16,
                          pattern="bigram") for i in range(2)]
    rcfg = RanlLLMConfig(num_workers=2, lr=1e-3, mu=1e-4,
                         memory_dtype="float32")
    n_regions = region_layout(params)[0]
    masks = jnp.zeros((2, n_regions), bool).at[0, 0].set(True) \
        .at[1, 1].set(True).at[:, 3:].set(True)   # region 2 uncovered
    state = init_state(params, loss_fn, batches[0], rcfg, KEY)
    new_p, new_s, m = jax.jit(lambda p, s, b: train_step(
        p, s, b, KEY, loss_fn=loss_fn, cfg=rcfg, masks=masks))(
            params, state, batches[1])
    want_loss, want_mem, want_p = _reference_round(params, batches, masks,
                                                   cfg, rcfg)
    assert abs(float(m["loss"]) - float(want_loss)) < 1e-5 * float(want_loss)
    assert int(m["dropped_rows"]) == 0
    assert int(m["held_rows"]) + int(m["absent_rows"]) == 2 * 4 * 16 * 3
    for (path, got), want in zip(
            jax.tree_util.tree_leaves_with_path(new_s["memory"]),
            jax.tree.leaves(want_mem)):
        if np.linalg.norm(np.asarray(want)) > 0:
            assert _rel(got, want) < ROUND_RTOL, jax.tree_util.keystr(path)
    for (path, got), want, p0 in zip(
            jax.tree_util.tree_leaves_with_path(new_p),
            jax.tree.leaves(want_p), jax.tree.leaves(params)):
        moved = np.asarray(want) - np.asarray(p0)
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.any(np.asarray(got) - np.asarray(p0)), name
        elif np.linalg.norm(moved) > 0:
            assert _rel(np.asarray(got) - np.asarray(p0), moved) \
                < ROUND_RTOL, name


_SHARE = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=2'
import dataclasses, json, sys
import jax, jax.numpy as jnp, numpy as np
sys.path.insert(0, TESTS)
import reference_moonlight as ref
from repro.configs import get_config, smoke_variant
from repro.launch.mesh import make_mesh
from repro.models.moe import apply_moe, init_moe
from repro.models.sharding import use_mesh
cfg = dataclasses.replace(smoke_variant(get_config('moonlight-16b-a3b')),
                          num_experts=8, experts_per_token=3,
                          experts_held=8)
key = jax.random.PRNGKey(5)
p = init_moe(cfg, key)
p['router_bias'] = 0.05 * jax.random.normal(key, (8,))
x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, cfg.d_model))
mesh = make_mesh((1, 2), ('data', 'model'))
with use_mesh(mesh):
    out, _, stats = jax.jit(lambda p, x: apply_moe(p, x, cfg))(p, x)
xf = x.reshape(32, -1)
with jax.default_matmul_precision('highest'):
    whole = ref.moe(p, xf, cfg)
    shares = [ref.moe(p, xf, cfg, experts=range(4 * s, 4 * s + 4),
                      shared=False) for s in range(2)]
    shared = ref.moe(p, xf, cfg, experts=[], shared=True)
rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
print(json.dumps({
    'sharded_vs_whole': rel(out.reshape(32, -1), whole),
    'shares_sum_vs_whole': rel(shares[0] + shares[1] + shared, whole),
    'shared_twice_vs_whole': rel(shares[0] + shares[1] + 2 * shared, whole),
    'dropped': int(stats['dropped_rows']),
    'rows': int(stats['expert_rows'].sum())}))
"""


def test_model_axis_shares_add_up_to_the_whole_layer():
    """On a 2-device ("data", "model") mesh the expert layer's two shares
    (4 experts each) plus the shared experts, counted once, give what the
    uncut reference gives for all 8 experts."""
    env = dict(os.environ)
    here = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = os.path.join(here, "..", "src")
    code = _SHARE.replace("TESTS", repr(here))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["sharded_vs_whole"] < BLOCK_RTOL, res
    assert res["shares_sum_vs_whole"] < BLOCK_RTOL, res
    assert res["shared_twice_vs_whole"] > 10 * BLOCK_RTOL, res
    assert res["dropped"] == 0 and res["rows"] == 32 * 3, res


_HEADS = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=4'
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config, smoke_variant
from repro.launch.mesh import make_mesh
from repro.models.attention import apply_mla, init_mla
from repro.models.sharding import use_mesh
mesh = make_mesh((2, 2), ('data', 'model'))
pos = jnp.broadcast_to(jnp.arange(24), (1, 24))
res = {}
for heads in (4, 3):
    cfg = dataclasses.replace(smoke_variant(get_config('moonlight-16b-a3b')),
                              num_heads=heads, num_kv_heads=heads)
    key = jax.random.PRNGKey(heads)
    p = init_mla(cfg, key)
    # two workers of one sequence each, vmapped over "data" as RANL's are
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 1, 24, cfg.d_model))
    probe = jax.random.normal(jax.random.fold_in(key, 2), x.shape)

    def value(p, x, spmd):
        out = jax.vmap(lambda x: apply_mla(p, x, cfg, pos, q_chunk=8,
                                           kv_chunk=8),
                       spmd_axis_name=spmd)(x)
        return jnp.sum(out * probe)

    grad = jax.value_and_grad(value, argnums=(0, 1))
    got = {False: jax.jit(grad, static_argnums=2)(p, x, None)}
    with use_mesh(mesh):
        got[True] = jax.jit(grad, static_argnums=2)(p, x, 'data')
        jaxpr = str(jax.make_jaxpr(grad, static_argnums=2)(p, x, 'data'))
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    (v0, (gp0, gx0)), (v1, (gp1, gx1)) = got[False], got[True]
    res[heads] = {'value': abs(float(v1 - v0)) / abs(float(v0)),
                  'x': rel(gx1, gx0),
                  'weights': {k: rel(gp1[k], gp0[k]) for k in gp0},
                  'shard_map': 'shard_map' in jaxpr}
print(json.dumps(res))
"""


def test_head_local_attention_matches_the_unsharded_layer():
    """Under a data 2 x model 2 mesh, with two workers vmapped over
    ``data``, latent attention computes each device's heads inside
    ``shard_map``: its output and the gradients of a scalar of it (w.r.t.
    x and every weight) match the call without a mesh.  Three heads do
    not split over two model shards and take the unsharded path, with the
    same numbers."""
    env = dict(os.environ)
    here = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = os.path.join(here, "..", "src")
    out = subprocess.run([sys.executable, "-c", _HEADS], env=env,
                         capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    for heads, split in (("4", True), ("3", False)):
        r = res[heads]
        assert r["shard_map"] is split, res
        assert r["value"] < BLOCK_RTOL and r["x"] < BLOCK_RTOL, res
        assert set(r["weights"]) == {"wq", "wkv_a", "kv_norm", "wkv_b",
                                     "wo"}, res
        assert max(r["weights"].values()) < BLOCK_RTOL, res
