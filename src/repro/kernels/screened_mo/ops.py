"""jit'd wrapper: padding, chunk-activity extraction, kernel dispatch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..platform import kernel_plan
from .kernel import screened_mo_matmul
from .ref import screened_mo_ref


def _pad_to(x: jnp.ndarray, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def screened_mo_products(A: jnp.ndarray, Bp: jnp.ndarray, idx: jnp.ndarray,
                         active: jnp.ndarray, *, tile_o: int = 128,
                         tile_k: int = 128, tile_e: int = 8) -> jnp.ndarray:
    """Screened-gather C_i = A @ B_i from the packed-CSR representation.

    The kernel front door of the cell-list screening pipeline
    (``core.screening``): inputs are the per-electron candidate lists with
    a static budget K, not the dense (n_ao, n_e, 5) B.  Values at inactive
    slots are zeroed here (defensive — ``eval_ao_block_screened`` already
    zeroes them), candidate ids at padding stay in-range, and a per-
    (electron-tile, k-chunk) activity table drives the kernel's skip list,
    so ragged active counts cost only the chunks they actually populate.

    Args:
      A: (n_orb, n_ao) dense MO coefficients.
      Bp: (n_e, K, 5) packed candidate-AO values.
      idx: (n_e, K) int32 candidate AO ids (padding -> 0).
      active: (n_e, K) bool — within-cutoff mask.
      tile_o / tile_k / tile_e: o-rows, candidate-slots, electrons per
        tile in interpret mode; ``kernels.platform.kernel_plan`` decides
        interpret mode and picks 128/128/8 on a TPU.

    Returns C: (n_orb, n_e, 5) f32.

    The electron axis may be one walker's ``n_e`` or an ensemble flattened
    walker-major to ``W * n_e`` — candidates are per-electron either way.
    """
    plan = kernel_plan('screened_mo', (tile_o, tile_k, tile_e),
                       n_ao=A.shape[1])
    return _products(A, Bp, idx, active, tiles=plan.tiles,
                     interpret=plan.interpret)


@functools.partial(jax.jit, static_argnames=('tiles', 'interpret'))
def _products(A, Bp, idx, active, *, tiles, interpret):
    tile_o, tile_k, tile_e = tiles
    n_orb, n_ao = A.shape
    n_e, K, _ = Bp.shape
    Bz = jnp.where(active[..., None], Bp, 0.0)
    Bz = _pad_to(_pad_to(Bz, 1, tile_k), 0, tile_e)
    idx_p = _pad_to(_pad_to(idx, 1, tile_k), 0, tile_e)
    act_p = _pad_to(_pad_to(active, 1, tile_k), 0, tile_e)
    At = _pad_to(A, 0, tile_o).T
    ne_p, kp = idx_p.shape
    e_tiles, k_chunks = ne_p // tile_e, kp // tile_k
    chunk_any = jnp.any(
        act_p.reshape(e_tiles, tile_e, k_chunks, tile_k),
        axis=(1, 3)).astype(jnp.int32)
    B2 = Bz.reshape(ne_p, kp * 5)
    Ct = screened_mo_matmul(At, B2, idx_p, chunk_any, tile_o=tile_o,
                            tile_k=tile_k, tile_e=tile_e,
                            interpret=interpret)
    Ct = Ct[:n_e * 5, :n_orb].reshape(n_e, 5, n_orb)
    return jnp.transpose(Ct, (2, 0, 1))


__all__ = ['screened_mo_products', 'screened_mo_ref']
