"""Screened-gather MO product Pallas kernel (paper §II-§III on the MXU).

Where ``kernels.sparse_mo`` exploits sparsity at (tile_k x tile_e) *tile*
granularity over the dense B, this kernel consumes the packed-CSR output of
the cell-list screening pipeline directly: per electron a static-budget row
of candidate AO ids (``idx``) and packed values (``Bp``), so the kernel
only ever touches active (electron, AO) pairs — the memory-minimal layout
of the paper's idea ii.).

Per grid step the kernel holds a resident (n_ao, tile_o) panel of A^T
(A stays dense — the paper's key choice; transposed so that one AO's
orbital coefficients are one row), and for each electron of the tile
walks its k-chunk of candidates: the candidate id and its five packed
values are scalars read from SMEM, the A^T row is a dynamic one-row load,
and the five right-hand sides accumulate on the VPU in fp32:

    C^T[e*5 + f, o_tile] += sum_k Bp[e, k, f] * A^T[idx[e, k], o_tile]

The output is electron-major, ``(n_e * 5, n_orb)``, so an 8-electron tile
fills a (40, tile_o) block: 40 sublanes and 128 lanes, which the TPU's
(8, 128) tiling accepts.  A scalar-prefetched per-(electron-tile, k-chunk)
activity mask skips chunks whose candidates are all inactive
(``pl.when``), which is where ragged active counts win back time.  Grid:
(e_tiles, o_tiles, k_chunks) with k innermost so the C tile stays resident
across the accumulation; e/o are ``parallel``, k ``arbitrary`` on the TPU.
``kernels.platform.kernel_plan`` decides interpret mode and tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(chunk_any_ref, at_ref, idx_ref, b_ref, c_ref):
    e_tile = pl.program_id(0)
    kc = pl.program_id(2)
    tile_e, tile_k = idx_ref.shape
    tile_o = c_ref.shape[1]

    @pl.when(kc == 0)
    def _zero():
        c_ref[...] = jnp.zeros_like(c_ref)

    @pl.when(chunk_any_ref[e_tile, kc] > 0)
    def _acc():
        for e in range(tile_e):
            def _cand(k, acc, e=e):
                row = at_ref[pl.ds(idx_ref[e, k], 1), :]      # (1, tile_o)
                return tuple(a + b_ref[e, k * 5 + f] * row
                             for f, a in enumerate(acc))

            acc = jax.lax.fori_loop(
                0, tile_k, _cand,
                tuple(jnp.zeros((1, tile_o), jnp.float32) for _ in range(5)))
            for f in range(5):
                c_ref[pl.ds(e * 5 + f, 1), :] += acc[f]


@functools.partial(
    jax.jit, static_argnames=('tile_o', 'tile_k', 'tile_e', 'interpret'))
def screened_mo_matmul(At: jnp.ndarray, B2d: jnp.ndarray,
                       idx: jnp.ndarray, chunk_any: jnp.ndarray,
                       *, tile_o: int = 128, tile_k: int = 128,
                       tile_e: int = 8, interpret: bool):
    """Packed-CSR screened product C^T = scatter(A[:, idx] @ Bp)^T.

    Args:
      At: (n_ao, n_orb) f32 transposed MO coefficients, n_orb padded to
        tile_o (the n_ao axis is resident).
      B2d: (n_e, K * 5) f32 packed values, electron-major, padded to
        (tile_e, tile_k * 5) multiples; zeros at inactive/padding slots.
      idx: (n_e, K) int32 candidate ids, padded likewise (in-range).
      chunk_any: (e_tiles, k_chunks) int32 — nonzero where the chunk has
        any active candidate (scalar-prefetched skip list).
      interpret: run the Python interpreter backend (off the TPU; see
        ``kernels.platform.kernel_plan``); False compiles for the TPU.

    Returns C^T: (n_e * 5, n_orb) f32, row ``e * 5 + f`` for electron e,
    component f.
    """
    n_ao, n_orb = At.shape
    n_e, k5 = B2d.shape
    assert n_orb % tile_o == 0 and n_e % tile_e == 0
    assert k5 == idx.shape[1] * 5 and idx.shape[1] % tile_k == 0
    e_tiles = n_e // tile_e
    o_tiles = n_orb // tile_o
    k_chunks = idx.shape[1] // tile_k
    assert chunk_any.shape == (e_tiles, k_chunks)

    smem = pltpu.SMEM
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(e_tiles, o_tiles, k_chunks),
        in_specs=[
            pl.BlockSpec((n_ao, tile_o), lambda e, o, k, ca: (0, o)),
            pl.BlockSpec((tile_e, tile_k), lambda e, o, k, ca: (e, k),
                         memory_space=smem),
            pl.BlockSpec((tile_e, tile_k * 5), lambda e, o, k, ca: (e, k),
                         memory_space=smem),
        ],
        out_specs=pl.BlockSpec((tile_e * 5, tile_o),
                               lambda e, o, k, ca: (e, o)),
    )
    kwargs = {}
    if not interpret:
        kwargs['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'))
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_e * 5, n_orb), jnp.float32),
        interpret=interpret,
        **kwargs,
    )(chunk_any, At, idx, B2d)
