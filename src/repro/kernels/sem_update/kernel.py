"""Batched rank-1 Sherman–Morrison update Pallas kernel (walker-tiled).

The single-electron-move hot path applies, per accepted walker,

    Minv <- Minv - outer(u, row);   Minv[j] <- row

over the whole ``(W, n, n)`` ensemble — an outer-product axpy plus one row
replacement, O(W n^2) memory-bound work repeated n_e times per sweep.  XLA
lowers the naive jnp version to several passes over the ensemble (outer
product, subtract, dynamic row scatter, accept select); the kernel fuses
all of it into one read + one write of each walker tile.

Tile layout: the grid runs over (walker tile, row tile); each grid step
owns a ``(tile_w, tile_r, n)`` block of ``Minv`` (the matrix axes padded to
the f32 (8, 128) VMEM tile by ``ops.sem_rank1_update``).  Tiling the rows
keeps the block a few MiB at any width — the update of a row needs only
that row, ``u``'s entry for it and the shared replacement row.  ``u``
rides along as a ``(tile_w, tile_r, 1)`` column and ``row`` as a
``(tile_w, 1, n)`` row, both broadcast against the block in registers; the
row replacement is a select on a broadcasted electron-index iota (no
dynamic-slice store), and the per-walker accept bit predicates the whole
update as a select against the resident input tile.  The electron index
``j`` is scalar-prefetched: it is the same for every walker, and
prefetching keeps it out of the tiled operand path.

Every block is written once, so both grid dimensions are declared
``parallel`` on the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(j_ref, minv_ref, u_ref, row_ref, acc_ref, out_ref):
    j = j_ref[0]
    minv = minv_ref[...]                               # (tile_w, tile_r, n)
    row = row_ref[...]                                 # (tile_w, 1, n)
    upd = minv - u_ref[...] * row
    first = pl.program_id(1) * minv.shape[1]
    elec = jax.lax.broadcasted_iota(jnp.int32, upd.shape, 1) + first
    upd = jnp.where(elec == j, row, upd)
    keep = acc_ref[...] == 0                           # (tile_w, 1, 1)
    out_ref[...] = jnp.where(keep, minv, upd)


@functools.partial(jax.jit, static_argnames=('tile_w', 'tile_r',
                                             'interpret'))
def sem_update_matmul(minv: jnp.ndarray, u: jnp.ndarray, row: jnp.ndarray,
                      accept: jnp.ndarray, j: jnp.ndarray, *,
                      tile_w: int, tile_r: int, interpret: bool):
    """Raw kernel dispatch on pre-padded operands.

    Args:
      minv: (W, n, n) f32, W a multiple of ``tile_w`` and n of ``tile_r``
        (last dim 128-padded on the TPU; see ops.sem_rank1_update).
      u: (W, n, 1) f32 column; row: (W, 1, n) f32.
      accept: (W, 1, 1) int32 (0 = reject); padding walkers pass 0.
      j: (1,) int32 electron row index (scalar-prefetched).
      interpret: run the Python interpreter backend (off the TPU; see
        ``kernels.platform.kernel_plan``); False compiles for the TPU.

    Returns the updated (W, n, n) f32 inverses.
    """
    W, n, _ = minv.shape
    assert W % tile_w == 0 and n % tile_r == 0
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(W // tile_w, n // tile_r),
        in_specs=[
            pl.BlockSpec((tile_w, tile_r, n), lambda w, r, jr: (w, r, 0)),
            pl.BlockSpec((tile_w, tile_r, 1), lambda w, r, jr: (w, r, 0)),
            pl.BlockSpec((tile_w, 1, n), lambda w, r, jr: (w, 0, 0)),
            pl.BlockSpec((tile_w, 1, 1), lambda w, r, jr: (w, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_w, tile_r, n),
                               lambda w, r, jr: (w, r, 0)),
    )
    kwargs = {}
    if not interpret:
        # every (walker, row) tile is written once: fully parallel
        kwargs['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel'))
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((W, n, n), jnp.float32),
        interpret=interpret,
        **kwargs,
    )(j, minv, u, row, accept)
