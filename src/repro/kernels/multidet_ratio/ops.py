"""jit'd wrapper: gather + padding + dispatch for the multidet kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import multidet

from ..platform import kernel_plan
from .kernel import multidet_ratio_matmul
from .ref import multidet_ratios_ref


def _pad_axis(x: jnp.ndarray, axis: int, mult: int):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def normalized_excitations(holes, parts, n_occ: int, n_orb: int):
    """Sentinel-pad (n_det, k<=2) excitation lists to exactly k = 2.

    The kernel's plane layout is fixed at rank 2 (CIS/CISD-style
    expansions); singles-only expansions gain one inert sentinel slot
    (pad slot ``a`` is (n_occ + a, n_orb + a) — ``core.multidet``'s
    convention, landing on ``extend_table``'s identity corner).  Rank > 2
    is not representable: callers fall back to the jnp reference.
    """
    holes = np.asarray(holes); parts = np.asarray(parts)
    k = holes.shape[1]
    if k > 2:
        raise ValueError(f'multidet ratio kernel supports excitation rank '
                         f'<= 2, got k={k}')
    if k == 2:
        return holes, parts
    n_det = holes.shape[0]
    pad_h = np.full((n_det, 2 - k), 0, np.int32)
    pad_p = np.full((n_det, 2 - k), 0, np.int32)
    for a in range(k, 2):
        pad_h[:, a - k] = n_occ + a
        pad_p[:, a - k] = n_orb + a
    return (np.concatenate([holes, pad_h], axis=1).astype(np.int32),
            np.concatenate([parts, pad_p], axis=1).astype(np.int32))


@functools.partial(jax.jit, static_argnames=('holes', 'parts', 'tile_w',
                                             'tile_d', 'interpret'))
def _dispatch(P, g, row, holes, parts, coeffs, r_other, tile_w, tile_d,
              interpret):
    holes = jnp.asarray(np.asarray(holes))
    parts = jnp.asarray(np.asarray(parts))
    P_ext = multidet.extend_table(P, 2)
    g_ext = multidet._pad_zero_rows(g, axis=-1, k=2)
    row_ext = multidet._pad_zero_rows(row, axis=-1, k=2)
    Tg = multidet.gather_t_blocks(P_ext, holes, parts)   # (W, n_det, 2, 2)
    gp = g_ext[..., parts]                               # (W, n_det, 2)
    rh = row_ext[..., holes]
    W, n_det = Tg.shape[0], Tg.shape[1]
    planes = jnp.stack([Tg[..., 0, 0], Tg[..., 0, 1],
                        Tg[..., 1, 0], Tg[..., 1, 1],
                        gp[..., 0], gp[..., 1],
                        rh[..., 0], rh[..., 1]], axis=1)  # (W, 8, n_det)
    planes = _pad_axis(_pad_axis(planes, 0, tile_w), 2, tile_d)
    ro = _pad_axis(_pad_axis(r_other, 0, tile_w), 1, tile_d)
    c = _pad_axis(jnp.asarray(coeffs)[None, :], 1, tile_d)
    ratios, sums = multidet_ratio_matmul(planes, ro, c, tile_w=tile_w,
                                         tile_d=tile_d, interpret=interpret)
    return ratios[:W, :n_det], sums[:W, 0]


def multidet_ratios(P: jnp.ndarray, g: jnp.ndarray, row: jnp.ndarray,
                    holes, parts, coeffs, r_other: jnp.ndarray, *,
                    tile_w: int = 8, tile_d: int = 128):
    """Batched multideterminant move ratios + CI sum (kernel dispatch).

    Kernel-dispatching equivalent of ``ref.multidet_ratios_ref`` (same
    signature, same semantics — tests pin the two together): normalizes
    the excitation rank to the kernel's fixed k = 2, gathers the base
    table blocks and the rank-1 correction factors into one (W, 8, n_det)
    plane stack (one XLA take per plane), pads the walker axis to
    ``tile_w`` and the determinant axis to ``tile_d`` (padded dets carry
    zero planes AND zero coefficients, so they contribute exact zeros),
    runs ``kernel.multidet_ratio_matmul``, and slices back.
    ``kernels.platform.kernel_plan`` decides interpret mode and, on the
    TPU, the (8, 128) tiles (``tile_w``/``tile_d`` apply when
    interpreting).

    Returns (ratios (W, n_det), ci (W,)).
    """
    n_occ, n_orb = P.shape[-1], P.shape[-2]
    holes, parts = normalized_excitations(holes, parts, n_occ, n_orb)
    plan = kernel_plan('multidet_ratio', (tile_w, tile_d))
    return _dispatch(P, g, row,
                     tuple(map(tuple, holes)), tuple(map(tuple, parts)),
                     coeffs, r_other, *plan.tiles, plan.interpret)


__all__ = ['multidet_ratios', 'multidet_ratios_ref',
           'normalized_excitations']
