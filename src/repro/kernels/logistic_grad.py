"""Pallas TPU kernel: every worker's logistic-regression gradient in one
pass over its design matrix.

For worker i with rows Xᵢ (n, d), labels yᵢ and pruned iterate xᵢ:

    Gᵢ = −Xᵢᵀ (σ(−(Xᵢ xᵢ)·yᵢ)·yᵢ) / n + lam·xᵢ

XLA computes this as two fusions, ``Xᵢ xᵢ`` and ``Xᵢᵀ s``, each of which
streams Xᵢ from HBM: the σ between them splits the work.  The kernel reads
a row tile of Xᵢ into VMEM once, forms that tile's logits, σ and its share
of ``Xᵢᵀ s`` while the tile is resident, and accumulates across tiles.
Both products are vector-unit multiply-reduces in f32: an M=1 matvec on
the MXU would waste 127/128 of it.

Grid: (worker, row tile).  The row-tile axis accumulates into VMEM
scratch; the last tile writes the worker's (1, d) gradient row.

X is read in its HBM layout, never re-laid-out.  The TPU's default layout
of an (N, n, d) f32 array keeps the rows minor where n pads less on the
lanes than d (at d = 2,000 and n = 25,000), and the kernel takes X as its
(N, d, n) transpose, which XLA then passes as a bitcast: tiles are
(d, tn), the logits come out lane-major beside the labels, and the
gradient accumulates as (d, 128) lane partials.  tn is a multiple of 128
(it rides the lanes of y), or all of n; a ragged last tile is masked in
the kernel.  X laid out any other way would need a copy of X per call,
so ``rows_minor_layout`` tells the caller to keep its two-pass path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.layout import Layout
from jax.experimental.pallas import tpu as pltpu

from .region_aggregate import _resolve_interpret

# rows per tile: on a TPU v5 lite at (16, 25000, 2000), tiles of 256 to
# 1,024 rows ran within 0.3% of each other (4.27 ms per call, 92% of the
# HBM peak) and 2,048 1.1% slower; 512 compiles 1 s sooner than 1,024
BLOCK_N = 512
VMEM_LIMIT = 64 * 1024 * 1024


def _padded(v: int, m: int) -> int:
    return -(-v // m) * m


def row_block(n: int, d: int) -> int | None:
    """Rows per tile: all of n when that fits in one tile, else the
    largest multiple of 128 up to ``BLOCK_N`` whose tile, double-buffered
    beside two tile-sized temporaries, fits in ``VMEM_LIMIT``.  None when
    not even 128 rows fit (d too wide for one pass)."""
    def tile_bytes(tn):                  # f32, both sides padded to lanes
        return 4 * _padded(d, 128) * _padded(tn, 128)

    tn = n if n <= BLOCK_N else BLOCK_N
    while 4 * tile_bytes(tn) > VMEM_LIMIT:
        if tn <= 128:
            return None
        tn = max(128, tn // 256 * 128)
    return tn


def rows_minor_layout(shape, dtype=jnp.float32, device=None) -> bool:
    """Whether the default layout of ``shape`` (N, n, d) on ``device``
    (default: the default backend's first) keeps the rows minor, the one
    layout the kernel reads in place.  False for any other order or
    tiling, and where the backend gives no layout to query."""
    dev = jax.devices()[0] if device is None else device
    try:
        layout = Layout.from_pjrt_layout(dev.client.get_default_layout(
            np.dtype(dtype), tuple(shape), dev))
    except Exception:  # noqa: BLE001 — no layout to query
        return False
    return (layout.major_to_minor == (0, 2, 1)
            and layout.tiling in ((), ((8, 128),)))


def logistic_grads(X, y, x, *, lam: float, interpret: bool | None = None):
    """X: (N, n, d) f32 laid out rows-minor; y: (N, n); x: (N, d)
    per-worker iterates.

    Returns G (N, d), ``G[i] = −X[i]ᵀ(σ(−(X[i] x[i])·y[i])·y[i])/n +
    lam·x[i]``.  ``interpret=None`` picks interpret mode off-TPU and the
    compiled kernel on TPU."""
    return _logistic_grads(X, y, x, lam=float(lam),
                           interpret=_resolve_interpret(interpret))


def _kernel(x_ref, y_ref, X_ref, g_ref, xcol_ref, acc_ref, *, n: int,
            tn: int, lam: float):
    j = pl.program_id(1)
    last = pl.num_programs(1) - 1

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        d = x_ref.shape[-1]      # x as a column, to scale the tile's rows
        xcol_ref[...] = jnp.transpose(
            jnp.broadcast_to(x_ref[...], (8, d)))[:, :1]

    def tile_step(masked: bool):
        tile = X_ref[...]                                # (d, tn)
        y = y_ref[...]                                   # (1, tn)
        if masked:               # rows past n hold whatever the DMA left
            pos = jax.lax.broadcasted_iota(jnp.int32, (1, tn), 1)
            valid = j * tn + pos < n
            tile = jnp.where(valid, tile, 0.0)
        z = jnp.sum(tile * xcol_ref[...], axis=0, keepdims=True)
        s = jax.nn.sigmoid(-z * y) * y
        if masked:
            s = jnp.where(valid, s, 0.0)
        p = tile * s
        if tn % 128 == 0:                   # (d, 128) lane partials
            acc = acc_ref[...]
            for c in range(0, tn, 128):
                acc = acc + p[:, c:c + 128]
            acc_ref[...] = acc
        else:                               # one tile of all n rows
            acc_ref[...] += jnp.sum(p, axis=1, keepdims=True)

    if n % tn:
        pl.when(j < last)(lambda: tile_step(False))
        pl.when(j == last)(lambda: tile_step(True))
    else:
        tile_step(False)

    @pl.when(j == last)
    def _():
        row = jnp.sum(jnp.transpose(acc_ref[...]), axis=0,
                      keepdims=True)                     # (1, d)
        g_ref[...] = -row / n + lam * x_ref[...]


@functools.partial(jax.jit, static_argnames=("lam", "interpret"))
def _logistic_grads(X, y, x, *, lam: float, interpret: bool):
    N, n, d = X.shape
    tn = row_block(n, d)
    if tn is None:
        raise ValueError(f"d={d} is too wide for one pass in "
                         f"{VMEM_LIMIT >> 20} MiB of VMEM")
    Xt = jnp.swapaxes(X, 1, 2)                           # bitcast on TPU
    row = pl.BlockSpec((None, 1, d), lambda i, j: (i, 0, 0))
    G = pl.pallas_call(
        functools.partial(_kernel, n=n, tn=tn, lam=lam),
        grid=(N, pl.cdiv(n, tn)),
        in_specs=[row, pl.BlockSpec((None, 1, tn), lambda i, j: (i, 0, j)),
                  pl.BlockSpec((None, d, tn), lambda i, j: (i, 0, j))],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((N, 1, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, 1), jnp.float32),
                        pltpu.VMEM((d, 128 if tn % 128 == 0 else 1),
                                   jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="logistic_grad",
    )(x.astype(jnp.float32)[:, None, :], y.astype(jnp.float32)[:, None, :],
      Xt)
    return G[:, 0, :]
