"""jit'd wrapper: padding + dispatch for the batched SM update kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..platform import kernel_plan
from .kernel import sem_update_matmul
from .ref import sem_update_ref


def _pad_axis(x: jnp.ndarray, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def sem_rank1_update(minv: jnp.ndarray, u: jnp.ndarray, row: jnp.ndarray,
                     accept: jnp.ndarray, j, *,
                     tile_w: int = 8) -> jnp.ndarray:
    """Batched Sherman–Morrison rank-1 update + row replacement.

    Kernel-dispatching equivalent of ``ref.sem_update_ref`` (same
    signature, same semantics — tests pin the two together): pads the
    walker axis to ``tile_w`` and both matrix axes to the f32 VMEM lane
    tile (128 on the TPU; 8 in interpret mode, which has no tiling
    constraint), runs ``kernel.sem_update_matmul``, slices back.  Padding
    walkers carry ``accept=0`` so they pass through as zeros; ``j`` may be
    a traced scalar (it is scalar-prefetched, not baked into the grid).
    ``kernels.platform.kernel_plan`` decides interpret mode and, on the
    TPU, the walker and row tiles (``tile_w`` applies when interpreting).

    Args:
      minv: (W, n, n) running inverses.
      u: (W, n) ``minv @ phi_new``.
      row: (W, n) replacement row (already divided by the ratio).
      accept: (W,) bool per-walker Metropolis outcome.
      j: electron row index (python int or traced int32 scalar).

    Returns the updated (W, n, n) inverses.
    """
    plan = kernel_plan('sem_update', (tile_w, 0), n=minv.shape[-1])
    return _update(minv, u, row, accept, j, tiles=plan.tiles,
                   interpret=plan.interpret)


@functools.partial(jax.jit, static_argnames=('tiles', 'interpret'))
def _update(minv, u, row, accept, j, *, tiles, interpret):
    W, n, _ = minv.shape
    tile_w, tile_r = tiles
    # the TPU needs the trailing two block dims on the (8, 128) f32 tile;
    # interpret mode has no tiling constraint, so pad only to 8 there and
    # skip the ~(128/n)^2 traffic blow-up for small spin blocks
    lane = 8 if interpret else 128
    minv_p = _pad_axis(_pad_axis(minv, 1, lane), 2, lane)
    n_p = minv_p.shape[-1]
    u_p = _pad_axis(u, 1, lane)[:, :, None]
    row_p = _pad_axis(row, 1, lane)[:, None, :]
    minv_p = _pad_axis(minv_p, 0, tile_w)
    u_p = _pad_axis(u_p, 0, tile_w)
    row_p = _pad_axis(row_p, 0, tile_w)
    acc = _pad_axis(accept.astype(jnp.int32)[:, None, None], 0, tile_w)
    j_arr = jnp.asarray(j, jnp.int32).reshape((1,))
    out = sem_update_matmul(minv_p, u_p, row_p, acc, j_arr, tile_w=tile_w,
                            tile_r=tile_r or n_p, interpret=interpret)
    return out[:W, :n, :n]


__all__ = ['sem_rank1_update', 'sem_update_ref']
