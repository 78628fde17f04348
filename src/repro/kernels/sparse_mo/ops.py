"""jit'd wrapper: padding, tile-activity extraction, kernel dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..platform import kernel_plan
from .kernel import sparse_mo_matmul
from .ref import mo_products_ref


def _pad_to(x: jnp.ndarray, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def tile_block_ids(ao_active: jnp.ndarray, *, tile_e: int, tile_k: int,
                   max_kb: int):
    """Active k-tile lists per electron tile.

    ao_active: (n_e, n_ao) bool (exact-zero structure of B).
    Returns (block_ids (e_tiles, max_kb) int32, num_active (e_tiles,) int32).
    Overflow beyond max_kb is truncated — callers choose max_kb >= worst case
    (n_kb tiles) for exactness; see ``sparse_mo_products``.
    """
    ao_active = _pad_to(ao_active, 0, tile_e)
    ao_active = _pad_to(ao_active, 1, tile_k)
    n_e, n_ao = ao_active.shape
    e_tiles, n_kb = n_e // tile_e, n_ao // tile_k
    act = ao_active.reshape(e_tiles, tile_e, n_kb, tile_k)
    tile_act = jnp.any(act, axis=(1, 3))                     # (e_tiles, n_kb)
    order = jnp.argsort(~tile_act, axis=-1, stable=True)     # active first
    count = jnp.sum(tile_act.astype(jnp.int32), axis=-1)
    ids = order[:, :max_kb].astype(jnp.int32)
    ids = jnp.where(jnp.arange(max_kb)[None] < count[:, None], ids, 0)
    return ids, jnp.minimum(count, max_kb)


def ensemble_tile_e(n_e_total: int, tile_e: int, cap: int = 128) -> int:
    """Electron-tile width for an ensemble-flattened column axis.

    A single walker rarely has enough electrons to fill a (tile_k, tile_e*5)
    B panel — per-walker calls pad most of every tile.  Once the column axis
    is the flattened ``W * n_e`` batch there are plenty of columns, so grow
    the per-walker ``tile_e`` by powers of two up to ``cap`` (128 keeps
    tile_e*5 = 640 lanes = 5 full TPU registers).  Fewer, fuller tiles also
    shrink the grid, which is what makes the interpret-mode path faster.
    """
    te = max(1, tile_e)
    while te < cap and te * 2 <= max(n_e_total, 1):
        te *= 2
    return te


def _pow2_cover(n: int, cap: int) -> int:
    """Smallest power of two >= n, capped (tile width fully covering a dim)."""
    t = 1
    while t < n and t < cap:
        t *= 2
    return min(t, cap)


TILE_E_CAP_INTERPRET = 2048   # interpret mode: grid-step overhead rules


def ensemble_tiles(tiles, n_orb: int, n_e_total: int,
                   cap_o: int = 128, cap_e: int = 0):
    """Re-tune per-walker kernel tiles for an ensemble-flattened call.

    Per-walker tiles are sized to one walker's electron count.  With the
    whole population in one call the balance shifts: the grid is ``e_tiles
    * o_tiles * max_kb`` and every step has fixed dispatch overhead
    (interpret mode) or pipeline latency (TPU), so wider tiles that the
    ensemble can actually fill win.  tile_o grows (never shrinks) toward
    covering n_orb — o-padding is bounded by one tile either way; tile_e
    grows toward ``cap_e``.  ``cap_e=0`` (default) is the interpret-mode
    cap: there per-grid-step overhead dominates, so very wide electron
    tiles win (measured: te 8 -> 2048 is ~25x on the micro-peptide
    ensemble).  On a TPU ``kernels.platform.kernel_plan`` replaces these
    tiles with its aligned ones.  tile_k stays at the caller's choice:
    k-padding costs real zero-flops, coarser k-tiles skip less, and neither
    tradeoff changes with ensemble size.
    """
    if cap_e <= 0:
        cap_e = TILE_E_CAP_INTERPRET
    to, tk, te = tiles
    return (max(to, _pow2_cover(n_orb, cap_o)), tk,
            ensemble_tile_e(n_e_total, te, cap_e))


def sparse_mo_products(A: jnp.ndarray, B: jnp.ndarray,
                       ao_active: jnp.ndarray, *,
                       tile_o: int = 128, tile_k: int = 128,
                       tile_e: int = 128, max_kb: int = 0) -> jnp.ndarray:
    """Tile-sparse C_i = A @ B_i for i=1..5.

    A: (n_orb, n_ao); B: (n_ao, n_e, 5); ao_active: (n_e, n_ao) bool.
    max_kb=0 -> exact (worst-case number of k tiles).
    Returns C: (n_orb, n_e, 5).

    The electron axis may be one walker's ``n_e`` or a whole ensemble
    flattened walker-major to ``W * n_e``: the column layout is tile_e-aware
    (5 contiguous columns per electron, ``tile_e * 5`` per tile), so electron
    tiles that a per-walker call would pad get filled by neighbouring
    walkers, and each A panel load amortizes over the full population.  Use
    ``ensemble_tile_e`` to pick ``tile_e`` for flattened batches.

    The tiles are the interpreter's; ``kernels.platform.kernel_plan``
    decides interpret mode and, on a TPU, replaces them with (128, 128, 128).
    """
    plan = kernel_plan('sparse_mo', (tile_o, tile_k, tile_e))
    return _products(A, B, ao_active, tiles=plan.tiles, max_kb=max_kb,
                     interpret=plan.interpret)


@functools.partial(jax.jit, static_argnames=('tiles', 'max_kb',
                                             'interpret'))
def _products(A, B, ao_active, *, tiles, max_kb, interpret):
    tile_o, tile_k, tile_e = tiles
    n_orb, n_ao = A.shape
    n_e = B.shape[1]
    Ap = _pad_to(_pad_to(A, 0, tile_o), 1, tile_k)
    # electron-major 2-D layout: 5 contiguous columns per electron
    B2 = B.reshape(n_ao, n_e * 5)
    B2 = _pad_to(_pad_to(B2, 0, tile_k), 1, tile_e * 5)
    n_kb = Ap.shape[1] // tile_k
    if max_kb <= 0 or max_kb > n_kb:
        max_kb = n_kb
    ids, num = tile_block_ids(ao_active, tile_e=tile_e, tile_k=tile_k,
                              max_kb=max_kb)
    C2 = sparse_mo_matmul(Ap, B2, ids, num, tile_o=tile_o, tile_k=tile_k,
                          tile_e5=tile_e * 5, interpret=interpret)
    return C2[:n_orb, :n_e * 5].reshape(n_orb, n_e, 5)


__all__ = ['sparse_mo_products', 'tile_block_ids', 'mo_products_ref',
           'ensemble_tile_e', 'ensemble_tiles']
