"""Sharding-spec rules + a subprocess mini dry-run (isolated XLA_FLAGS)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ALL_ARCHS, get_config, smoke_variant
from repro.launch.mesh import make_mesh
from repro.launch.shard import (batch_pspecs, cache_pspecs, params_pspecs,
                                ranl_state_pspecs, trim_tree, worker_prefix)

KEY = jax.random.PRNGKey(0)


def _abstract_params(cfg):
    from repro.models import init_model
    return jax.eval_shape(lambda: init_model(cfg, KEY))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_param_specs_divisible(arch):
    """Every 'model'-sharded dim divides by the shard count (pjit rule),
    at production model_shards=16 on the FULL config."""
    cfg = get_config(arch)
    params = _abstract_params(cfg)
    specs = params_pspecs(params, model_shards=16,
                          fsdp_shards=[(("data",), 16)])
    flat_p = jax.tree_util.tree_leaves_with_path(params)
    flat_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for (path, leaf), spec in zip(flat_p, flat_s):
        for i, part in enumerate(spec):
            if part is None:
                continue
            parts = part if isinstance(part, tuple) else (part,)
            shards = 1
            for a in parts:
                shards *= {"model": 16, "data": 16, "pod": 2}[a]
            assert leaf.shape[i] % shards == 0, (path, leaf.shape, spec)


def test_worker_prefix_strips_batch_axes():
    s = worker_prefix(P(("model", "data"), None))
    assert s == P(("pod", "data"), "model", None)
    s2 = worker_prefix(P("data", "model"))
    assert s2 == P(("pod", "data"), None, "model")


def test_trim_tree_drops_missing_axes():
    mesh = make_mesh((1, 1), ("data", "model"))
    t = trim_tree({"a": P(("pod", "data"), "model")}, mesh)
    assert t["a"] == P(("data",), "model")


def test_ranl_state_specs_structure():
    cfg = smoke_variant(get_config("phi4-mini-3.8b"))
    params = _abstract_params(cfg)
    specs = ranl_state_pspecs(params, model_shards=16)
    assert specs["step"] == P()
    mem_leaves = jax.tree_util.tree_leaves(
        specs["memory"], is_leaf=lambda x: isinstance(x, P))
    for s in mem_leaves:
        assert s[0] == ("pod", "data")       # worker axis first


@pytest.mark.slow
def test_subprocess_mini_dryrun():
    """Full dry-run path on 8 fake devices in a subprocess (keeps this
    process's jax device count untouched)."""
    code = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import jax, dataclasses, json
from repro.configs import get_config, smoke_variant, INPUT_SHAPES
from repro.launch.mesh import make_mesh
from repro.launch.dryrun import lower_and_compile
mesh = make_mesh((2, 4), ('data', 'model'))
cfg = dataclasses.replace(smoke_variant(get_config('hymba-1.5b')),
                          num_layers=4)
shape = dataclasses.replace(INPUT_SHAPES['train_4k'],
                            seq_len=128, global_batch=8)
r = lower_and_compile(cfg, shape, mesh)
print(json.dumps({'ok': r['ok'],
                  'coll': r['collectives']['total_bytes'] > 0,
                  'mem': r['memory']['total_bytes'] > 0}))
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res == {"ok": True, "coll": True, "mem": True}


def test_resolve_logical_default_rules():
    """T5X-style logical names map through DEFAULT_LOGICAL_RULES: batch/
    worker split jointly over ("pod","data"), width-like axes go to
    "model", sequence/head axes replicate, unknown names (including
    literal mesh axes) pass through untouched."""
    from repro.models.sharding import (DEFAULT_LOGICAL_RULES,
                                       resolve_logical)
    assert resolve_logical(("batch", "embed")) == (("pod", "data"),
                                                  "model")
    assert resolve_logical(("worker", None, "mlp")) == (("pod", "data"),
                                                        None, "model")
    assert resolve_logical(("pods", "seq", "kv")) == ("pod", None, None)
    # literal mesh axis names and unknown logical names fall through
    assert resolve_logical(("data", "mystery")) == ("data", "mystery")
    # a tuple part flattens each member through the rules; members that
    # resolve to None drop, and an all-dropped part becomes None
    assert resolve_logical((("batch",), "vocab")) == (("pod", "data"),
                                                     "model")
    assert resolve_logical((("seq", "kv"),)) == (None,)
    assert resolve_logical((("heads", "kv"),)) == (("model",),)
    # explicit rules argument bypasses the active set
    assert resolve_logical(("batch",), rules=(("batch", "data"),)) \
        == ("data",)
    assert ("batch", ("pod", "data")) in DEFAULT_LOGICAL_RULES


def test_use_logical_axis_rules_override():
    from repro.models.sharding import (DEFAULT_LOGICAL_RULES,
                                       logical_axis_rules,
                                       resolve_logical,
                                       use_logical_axis_rules)
    assert logical_axis_rules() == DEFAULT_LOGICAL_RULES
    # list targets normalize to tuples; first match wins
    with use_logical_axis_rules([("batch", ["data"]),
                                 ("batch", "model"),
                                 ("embed", None)]) as rules:
        assert rules == (("batch", ("data",)), ("batch", "model"),
                         ("embed", None))
        assert resolve_logical(("batch", "embed")) == (("data",), None)
    assert logical_axis_rules() == DEFAULT_LOGICAL_RULES


def test_named_sharding_trims_missing_mesh_axes():
    """The same logical spec shards correctly on pod-bearing and podless
    meshes: axes the active mesh lacks are dropped (the single-pod /
    single-model degenerate layouts)."""
    from repro.models.sharding import named_sharding
    mesh_dm = make_mesh((1, 1), ("data", "model"))
    s = named_sharding(mesh_dm, "batch", "embed")
    assert s.spec == P(("data",), "model")
    mesh_d = make_mesh((1,), ("data",))
    s = named_sharding(mesh_d, "batch", "embed")
    assert s.spec == P(("data",), None)
    assert named_sharding(mesh_d, "pods").spec == P(None)


def test_shard_hint_logical_spec():
    from repro.models.sharding import shard_hint, use_mesh
    x = jnp.ones((4, 8))
    # mesh-agnostic: a no-op when no mesh is installed
    assert shard_hint(x, ("batch", "embed")) is x
    mesh = make_mesh((1, 1), ("data", "model"))
    with use_mesh(mesh):
        y = jax.jit(lambda a: shard_hint(a, ("batch", "embed")))(x)
    # on the degenerate 1x1 mesh the constraint canonicalizes to fully
    # replicated — the output still lands on our mesh with x unchanged
    assert y.sharding.mesh.axis_names == ("data", "model")
    assert (y == x).all()


def test_hlo_collective_parser():
    from repro.launch.hlo_analysis import (collect_collectives,
                                           shape_bytes,
                                           summarize_collectives)
    assert shape_bytes("f32[4,8]") == 128
    assert shape_bytes("(bf16[2,2], s32[3])") == 20
    hlo = """
HloModule test

%body.1 (p: (s32[], f32[128])) -> (s32[], f32[128]) {
  %p = (s32[], f32[128]) parameter(0)
  %x = f32[128]{0} get-tuple-element(%p), index=1
  %ar = f32[128]{0} all-reduce(%x), replica_groups={}, to_apply=%add
  %i = s32[] get-tuple-element(%p), index=0
  ROOT %t = (s32[], f32[128]) tuple(%i, %ar)
}

%cond.1 (p: (s32[], f32[128])) -> pred[] {
  %p = (s32[], f32[128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

ENTRY %main (a: f32[128]) -> f32[128] {
  %a = f32[128]{0} parameter(0)
  %ag = f32[256]{0} all-gather(%a), dimensions={0}
  %init = (s32[], f32[128]) tuple(%zero, %a)
  %w = (s32[], f32[128]) while(%init), condition=%cond.1, body=%body.1
  ROOT %out = f32[128]{0} get-tuple-element(%w), index=1
}
"""
    recs = collect_collectives(hlo, default_trip=3)
    kinds = {r.kind: r for r in recs}
    assert kinds["all-reduce"].multiplier == 7      # parsed trip count
    assert kinds["all-reduce"].total_bytes == 128 * 4 * 7
    assert kinds["all-gather"].multiplier == 1
    s = summarize_collectives(recs)
    assert s["total_bytes"] == 128 * 4 * 7 + 128 * 4
