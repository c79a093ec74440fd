"""Compile the main-path Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached.  Interpret mode (what every other kernel test
runs) cannot see the chip's tiling rules; these compiles can.  The
topology is described inside a fixture, never at import, so that every
pytest-xdist worker collects the same tests and only the worker that
runs this file loads the TPU library.
"""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.logistic_grad import logistic_grads, rows_minor_layout
from repro.kernels.region_aggregate import ranl_update, region_aggregate

N = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache off around these
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(one_chip, d):
    def s(*shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return {"vec": s(d), "tile": s(N, d), "mask": s(N, d, dtype=jnp.bool_)}


@pytest.mark.parametrize("kernel", ["ranl_update", "region_aggregate"])
@pytest.mark.parametrize("d", [4096, 3000, 1 << 20])
def test_kernel_compiles_for_v5e(one_chip, kernel, d):
    sh = _shapes(one_chip, d)
    if kernel == "ranl_update":
        fn = partial(ranl_update, mu=1e-3, lr=1.0, interpret=False)
        args = (sh["vec"], sh["vec"], sh["tile"], sh["mask"], sh["tile"])
    else:
        fn = partial(region_aggregate, interpret=False)
        args = (sh["tile"], sh["mask"], sh["tile"])
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("shape,rows_minor", [
    ((16, 25000, 2000), True),      # the convex cell: rows ride the lanes
    ((16, 5000, 200), True),
    ((16, 25000, 2048), False),     # a lane-multiple width keeps d minor
])
def test_logistic_grads_layout_query_on_v5e(topo, shape, rows_minor):
    """The chip's default layout of X decides whether the one-pass kernel
    can read it in place; where it keeps d minor, ``Logistic`` keeps its
    vmap path."""
    assert rows_minor_layout(shape, device=topo.devices[0]) is rows_minor


@pytest.mark.parametrize("shape", [
    (16, 25000, 2000),              # the convex cell
    (16, 5000, 200),                # ragged last row tile
])
def test_logistic_grads_compiles_for_v5e(one_chip, shape):
    """The one-pass logistic kernel compiles for X laid out rows-minor and
    reads X in place: no copy of X."""
    N, n, d = shape

    def s(*dims):
        return jax.ShapeDtypeStruct(dims, jnp.float32, sharding=one_chip)

    fn = partial(logistic_grads, lam=1e-4, interpret=False)
    hlo = jax.jit(fn).lower(s(N, n, d), s(N, n), s(N, d)).compile() \
        .as_text()
    assert "tpu_custom_call" in hlo
    x_shape = f"f32[{N},{n},{d}]"
    assert not [line for line in hlo.splitlines()
                if " copy(" in line and x_shape in line]


def _moonlight_step_hlo(topo, shape, chunk):
    """The compiled RANL step of a small Moonlight (latent attention, 4 of
    8 experts held, split over the model axis; 2 workers) on a data 2 x
    model 2 mesh of the described chips, at a (rows, tokens) batch
    ``shape`` and attention blocks of ``chunk``."""
    import dataclasses

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_config, smoke_variant
    from repro.launch.shard import (params_pspecs, ranl_state_pspecs,
                                    to_shardings)
    from repro.launch.train import build_loss
    from repro.models import init_model
    from repro.models.sharding import use_mesh
    from repro.optim import RanlLLMConfig, init_state, train_step

    cfg = dataclasses.replace(smoke_variant(get_config("moonlight-16b-a3b")),
                              num_experts=8, experts_per_token=3,
                              experts_held=4, num_layers=3)
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2), ("data", "model"))
    loss_fn = build_loss(cfg, q_chunk=chunk, kv_chunk=chunk)
    rcfg = RanlLLMConfig(num_workers=2)

    def placed(tree, specs):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            tree, to_shardings(specs, mesh))

    params = jax.eval_shape(partial(init_model, cfg), jax.random.PRNGKey(0))
    rep = NamedSharding(mesh, P())
    batch = {k: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)
             for k in ("tokens", "labels")}
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    state = jax.eval_shape(partial(init_state, loss_fn=loss_fn, cfg=rcfg),
                           params, batch=batch, key=key)
    state = placed(state, ranl_state_pspecs(params, model_shards=2))
    params = placed(params, params_pspecs(params, model_shards=2))
    with use_mesh(mesh):
        return jax.jit(partial(train_step, loss_fn=loss_fn, cfg=rcfg,
                               mesh=mesh)).lower(params, state, batch, key) \
            .compile().as_text()


def test_sharded_moonlight_step_compiles_for_v5e_2x2(topo):
    """The expert layer's grouped matmuls compile to the chip's ragged-dot
    kernels inside the shard_map."""
    hlo = _moonlight_step_hlo(topo, (4, 128), 64)
    assert "tpu_custom_call" in hlo and "ragged-dot" in hlo
    assert "all-reduce" in hlo


_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
          "u8": 1, "pred": 1}


def test_latent_attention_moves_no_score_block_for_v5e_2x2(topo):
    """Latent attention computes each device's heads where they were
    projected: no all-to-all in its scope moves as much as half of one
    per-device score block, forward or backward.  The blocks (512 x 512)
    are larger than a device's q, k and v, so a re-sharded score block
    would stand out."""
    hlo = _moonlight_step_hlo(topo, (4, 1024), 512)
    # one worker's 2 rows x 2 of the 4 heads x a 512 x 512 f32 block
    block = 2 * 2 * 512 * 512 * 4
    moved = []
    for line in hlo.splitlines():
        op = re.search(r"=\s*(.*?)\s+all-to-all(?:-start)?\(", line)
        if op is None or "/mla/" not in line:
            continue
        moved.append(sum(
            _BYTES[dtype] * int(np.prod([int(d) for d in dims.split(",")
                                         if d]))
            for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]",
                                          op.group(1))))
    assert "/mla/" in hlo, "latent attention's scope left the metadata"
    assert max(moved, default=0) < block / 2, sorted(moved)[-5:]
