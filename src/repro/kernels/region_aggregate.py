"""Pallas TPU kernel: RANL server aggregation (Algorithm 1 lines 15–22).

One fused pass over the parameter dimension computes, per coordinate block:
coverage counts, fresh-mean over covering workers, memory-mean fallback for
uncovered regions, and the memory refresh — all while the (N, block) tile is
resident in VMEM.  The reference implementation (three jnp reductions +
selects) makes XLA materialize several (N, D) intermediates in HBM; the
kernel reads G/M/C once and writes g/C_new once: HBM traffic drops from
~(7·N+2)·D·4B to (3·N+1+N)·D·4B.

Grid: 1-D over D blocks.  Block shape (N, BLOCK_D) with BLOCK_D a multiple
of 128 (lane dimension); the worker dimension N (≤ 32) rides the sublane
axis, so reductions over workers are cheap vector-unit column sums.

The length-D operands (params, the curvature diagonal, the aggregated
gradient) are carried as (1, D) rows with (1, BLOCK_D) blocks.  A 1-D
f32 array gets XLA's T(1024) tiling on TPU, which a 1-D block of any
other size cannot match (Mosaic refuses the kernel); a row takes the
same 2-D tiling as the (N, D) operands, so any lane-multiple block
compiles.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_D = 512


def local_region_ids(dim: int, num_regions: int, offset, size: int):
    """Region id per coordinate of the slice [offset, offset+size) of a
    ``dim``-coordinate vector partitioned into ``num_regions`` contiguous
    regions.

    Slice-offset-aware: a dimension-sharded engine expands its (N, Q)
    region masks into *local* coordinate masks with these ids, so the
    kernels in this module (and the jnp aggregation oracle) operate on
    d-slices without ever materializing the full coordinate mask row.
    ``offset`` may be a traced index (e.g. derived from
    ``jax.lax.axis_index``); ``dim``/``num_regions``/``size`` are static.
    """
    from ..core.regions import contiguous_regions
    ids = contiguous_regions(dim, num_regions)
    return jax.lax.dynamic_slice_in_dim(ids, offset, size)


def _resolve_interpret(interpret: bool | None) -> bool:
    """None -> interpret everywhere except real TPUs (compiled there)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _kernel(g_ref, m_ref, c_ref, out_g_ref, out_c_ref):
    g = g_ref[...]                       # (N, bd) float
    m = m_ref[...]                       # (N, bd) mask (same dtype as g)
    c = c_ref[...]
    count = jnp.sum(m, axis=0, keepdims=True)          # (1, bd)
    fresh = jnp.sum(g * m, axis=0, keepdims=True) / jnp.maximum(count, 1.0)
    stale = jnp.mean(c, axis=0, keepdims=True)
    out_g_ref[...] = jnp.where(count > 0, fresh, stale)
    out_c_ref[...] = jnp.where(m > 0, g, c)


def region_aggregate(grads, masks, memory, *, block_d: int = BLOCK_D,
                     interpret: bool | None = None):
    """grads, memory: (N, D) f32; masks: (N, D) bool.

    Returns (global_grad (D,), new_memory (N, D)).  D is padded to the
    block size internally.  ``interpret=None`` picks interpret mode on
    CPU and the compiled kernel on TPU.
    """
    return _region_aggregate(grads, masks, memory, block_d=block_d,
                             interpret=_resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def _region_aggregate(grads, masks, memory, *, block_d: int,
                      interpret: bool):
    N, D = grads.shape
    dt = grads.dtype
    bd = min(block_d, max(128, D))
    pad = (-D) % bd
    if pad:
        grads = jnp.pad(grads, ((0, 0), (0, pad)))
        masks = jnp.pad(masks, ((0, 0), (0, pad)))
        memory = jnp.pad(memory, ((0, 0), (0, pad)))
    Dp = D + pad
    m = masks.astype(dt)

    out_g, out_c = pl.pallas_call(
        _kernel,
        grid=(Dp // bd,),
        in_specs=[
            pl.BlockSpec((N, bd), lambda i: (0, i)),
            pl.BlockSpec((N, bd), lambda i: (0, i)),
            pl.BlockSpec((N, bd), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bd), lambda i: (0, i)),
            pl.BlockSpec((N, bd), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Dp), dt),
            jax.ShapeDtypeStruct((N, Dp), dt),
        ],
        interpret=interpret,
    )(grads, m, memory)
    return out_g[0, :D], out_c[:, :D]


def _fused_kernel(x_ref, h_ref, g_ref, m_ref, c_ref, out_x_ref, out_c_ref,
                  *, mu: float, lr: float):
    g = g_ref[...]
    m = m_ref[...]
    c = c_ref[...]
    count = jnp.sum(m, axis=0, keepdims=True)          # (1, bd)
    fresh = jnp.sum(g * m, axis=0, keepdims=True) / jnp.maximum(count, 1.0)
    stale = jnp.mean(c, axis=0, keepdims=True)
    gbar = jnp.where(count > 0, fresh, stale)
    h_mu = jnp.maximum(h_ref[...], mu)   # diagonal [·]_μ projection
    out_x_ref[...] = x_ref[...] - lr * gbar / h_mu
    out_c_ref[...] = jnp.where(m > 0, g, c)


def ranl_update(params, hdiag, grads, masks, memory, *, mu: float,
                lr: float = 1.0, block_d: int = BLOCK_D,
                interpret: bool | None = None):
    """Fused aggregation + projected-Newton update (one HBM pass).

    params, hdiag: (D,); grads/masks/memory: (N, D).
    Returns (new_params, new_memory).  ``interpret=None`` picks interpret
    mode on CPU and the compiled kernel on TPU."""
    return _ranl_update(params, hdiag, grads, masks, memory, mu=mu, lr=lr,
                        block_d=block_d,
                        interpret=_resolve_interpret(interpret))


@functools.partial(jax.jit,
                   static_argnames=("mu", "lr", "block_d", "interpret"))
def _ranl_update(params, hdiag, grads, masks, memory, *, mu: float,
                 lr: float, block_d: int, interpret: bool):
    N, D = grads.shape
    dt = params.dtype
    bd = min(block_d, max(128, D))
    pad = (-D) % bd
    if pad:
        params = jnp.pad(params, (0, pad))
        hdiag = jnp.pad(hdiag, (0, pad), constant_values=1.0)
        grads = jnp.pad(grads, ((0, 0), (0, pad)))
        masks = jnp.pad(masks, ((0, 0), (0, pad)))
        memory = jnp.pad(memory, ((0, 0), (0, pad)))
    Dp = D + pad
    m = masks.astype(dt)

    out_x, out_c = pl.pallas_call(
        functools.partial(_fused_kernel, mu=mu, lr=lr),
        grid=(Dp // bd,),
        in_specs=[
            pl.BlockSpec((1, bd), lambda i: (0, i)),
            pl.BlockSpec((1, bd), lambda i: (0, i)),
            pl.BlockSpec((N, bd), lambda i: (0, i)),
            pl.BlockSpec((N, bd), lambda i: (0, i)),
            pl.BlockSpec((N, bd), lambda i: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, bd), lambda i: (0, i)),
            pl.BlockSpec((N, bd), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Dp), dt),
            jax.ShapeDtypeStruct((N, Dp), dt),
        ],
        interpret=interpret,
    )(params[None, :], hdiag[None, :], grads, m, memory)
    return out_x[0, :D], out_c[:, :D]
