"""Tile-sparse MO-product Pallas kernel (TPU adaptation of paper §III).

The paper's algorithm skips *elements* of B via per-electron active-AO index
lists, keeping A dense for SIMD.  The MXU equivalent exploits sparsity at
(tile_k x tile_e) granularity: AOs are stored atom-contiguous, electrons are
sorted spatially, so the active AO rows of an electron tile cluster into a
few 128-row blocks.  A scalar-prefetched per-electron-tile *block index list*
drives the BlockSpec index maps — the kernel only ever touches active
(A-panel, B-panel) pairs and accumulates into a resident C tile:

    C[o_tile, e_tile] = sum_{k in active(e_tile)} A[o_tile, k] @ B[k, e_tile]

All five right-hand sides (value, 3 gradients, Laplacian) ride in the same
B panel (electron-major, 5 columns per electron), so the A panel is loaded
once for all five products — the TPU version of the paper's unroll-and-jam
load/store-ratio optimization.  The column axis is walker-agnostic: an
ensemble-flattened ``W * n_e`` electron batch uses the identical layout, and
is how tiles actually fill for small per-walker electron counts (see
``ops.ensemble_tile_e`` and DESIGN.md §4).

Grid: (e_tiles, o_tiles, max_kb); k innermost so the C tile stays in VMEM
across the accumulation.  Inactive k slots are skipped with pl.when.  The
e/o dimensions write disjoint C tiles and are declared ``parallel`` on real
TPU; only k is ``arbitrary`` (sequential accumulation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(block_ids_ref, num_active_ref, a_ref, b_ref, c_ref):
    e = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        c_ref[...] = jnp.zeros_like(c_ref)

    @pl.when(k < num_active_ref[e])
    def _acc():
        c_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=jnp.float32)


@functools.partial(
    jax.jit,
    static_argnames=('tile_o', 'tile_k', 'tile_e5', 'interpret'))
def sparse_mo_matmul(A: jnp.ndarray, B2d: jnp.ndarray,
                     block_ids: jnp.ndarray, num_active: jnp.ndarray,
                     *, tile_o: int = 128, tile_k: int = 128,
                     tile_e5: int = 320, interpret: bool):
    """Block-sparse product C = A @ B2d using per-column-tile block lists.

    Args:
      A: (n_orb, n_ao) f32, padded to (tile_o, tile_k) multiples.
      B2d: (n_ao, n_cols) f32 (n_cols = 5 * n_e), padded likewise.
      block_ids: (n_e_tiles, max_kb) int32 — active k-tile indices per
        column tile (padding entries arbitrary but in-range).
      num_active: (n_e_tiles,) int32 — valid prefix length of block_ids.
      interpret: run the Python interpreter backend (off the TPU; see
        ``kernels.platform.kernel_plan``); False compiles for the TPU.

    Returns C: (n_orb, n_cols) f32.
    """
    n_orb, n_ao = A.shape
    n_cols = B2d.shape[1]
    assert n_orb % tile_o == 0 and n_ao % tile_k == 0
    assert n_cols % tile_e5 == 0
    e_tiles = n_cols // tile_e5
    o_tiles = n_orb // tile_o
    max_kb = block_ids.shape[1]
    assert block_ids.shape[0] == e_tiles

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(e_tiles, o_tiles, max_kb),
        in_specs=[
            pl.BlockSpec((tile_o, tile_k),
                         lambda e, o, k, ids, na: (o, ids[e, k])),
            pl.BlockSpec((tile_k, tile_e5),
                         lambda e, o, k, ids, na: (ids[e, k], e)),
        ],
        out_specs=pl.BlockSpec((tile_o, tile_e5),
                               lambda e, o, k, ids, na: (o, e)),
    )
    kwargs = {}
    if not interpret:
        # e/o tiles are independent outputs; only the k accumulation is
        # order-dependent.  (Interpret mode ignores compiler params.)
        kwargs['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'))
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_orb, n_cols), jnp.float32),
        interpret=interpret,
        **kwargs,
    )(block_ids, num_active, A, B2d)
