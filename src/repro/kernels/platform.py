"""Where a Pallas kernel runs: compiled on a TPU, interpreted elsewhere.

``kernel_plan`` is the one place that decides, for every kernel under
``repro.kernels``, whether its ``pallas_call`` runs compiled or in the
Python interpreter, and with which tiles.  Each ``ops.py`` wrapper asks it
before dispatching, so no caller picks interpret mode itself:

* on a TPU the kernel is compiled, with tiles aligned to the f32 (8, 128)
  VMEM tile and sized so that every grid step fits the scoped VMEM budget.
  A kernel whose smallest legal tile does not fit at the requested width
  is refused with ``KernelRefused``, which names the kernel and the width;
  nothing falls back to ``ref.py``;
* on any other platform the kernel is interpreted, with the caller's tiles
  (the interpreter has no tiling constraint).

``platform`` defaults to ``jax.default_backend()``; passing ``'tpu'`` asks
for the chip's choice from a machine without one.
"""
from __future__ import annotations

from typing import NamedTuple

import jax

SUBLANE, LANE = 8, 128
# Scoped VMEM a kernel may use by default on a TPU v5e (16 MiB); the
# estimates below count double-buffered blocks against it.
VMEM_BUDGET = 16 * 1024 * 1024
_F32 = 4


class KernelPlan(NamedTuple):
    """How one kernel call runs: interpreted or compiled, and its tiles."""

    interpret: bool
    tiles: tuple


class KernelRefused(ValueError):
    """The kernel cannot run compiled at this width on this platform."""


def default_platform() -> str:
    """The platform kernels are planned for when none is given."""
    return jax.default_backend()


def round_up(n: int, mult: int) -> int:
    """Smallest multiple of ``mult`` that is >= n (and >= mult)."""
    return max(mult, -(-n // mult) * mult)


def _refuse(kernel: str, need: int, **dims) -> None:
    if need > VMEM_BUDGET:
        width = ', '.join(f'{k}={v}' for k, v in dims.items())
        raise KernelRefused(
            f'{kernel}: a grid step needs {need / 2**20:.1f} MiB of VMEM at '
            f'{width}, more than the {VMEM_BUDGET / 2**20:.0f} MiB a TPU '
            f'kernel may use')


def _sparse_mo(tiles, **_):
    # (tile_o, tile_k, tile_e): A (128, 128), B (128, 640) and C (128, 640)
    # blocks — 5 * 128 lanes per electron tile, well inside VMEM
    return (LANE, LANE, LANE)


def _screened_mo(tiles, *, n_ao, **_):
    # (tile_o, tile_k, tile_e): the resident (n_ao, tile_o) panel of A^T
    # dominates the grid step; 8 electrons x 5 components fill the output
    # block's 40 sublanes
    _refuse('screened_mo', 2 * n_ao * LANE * _F32, n_ao=n_ao)
    return (LANE, LANE, SUBLANE)


def _sem_update(tiles, *, n, **_):
    # (tile_w, tile_r): 8 walkers x tile_r rows of the lane-padded inverse.
    # Rows are tiled so the block stays ~2 MiB at any width.
    n_p = round_up(n, LANE)
    cap = max(SUBLANE, (2 * 1024 * 1024) // (SUBLANE * n_p * _F32))
    tile_r = max(t for t in range(SUBLANE, n_p + 1, SUBLANE)
                 if n_p % t == 0 and t <= cap)
    _refuse('sem_update', 4 * SUBLANE * tile_r * n_p * _F32, n=n)
    return (SUBLANE, tile_r)


def _multidet_ratio(tiles, **_):
    # (tile_w, tile_d): one (8, 8, 128) plane stack per grid step
    return (SUBLANE, LANE)


def _fused_sweep(tiles, *, n, n_e, n_det=0, **_):
    # CI table gathers index true orbital rows, so the multideterminant
    # operands cannot take the lane padding the TPU's tiling needs
    if n_det:
        raise KernelRefused(f'fused_sweep: multideterminant sweeps '
                            f'(n_det={n_det}) are not laid out for the TPU')
    # (tile_w,): walker tiles are multiples of the 8-row sublane tile.  The
    # whole (tile_w, n, n) inverse and the proposal panel stay in VMEM for
    # the block's sweep: double-buffered in and out, plus the loop carry
    # and the rank-1 update the body materialises.
    n_p = round_up(n, LANE)

    def need(tw):
        minv = tw * n_p * n_p * _F32
        phi = tw * round_up(n, SUBLANE) * n_p * _F32
        r = tw * round_up(n_e, SUBLANE) * LANE * _F32
        return 6 * minv + 2 * phi + 4 * r

    tw = round_up(tiles[0], SUBLANE)
    while tw > SUBLANE and need(tw) > VMEM_BUDGET:
        tw -= SUBLANE
    _refuse('fused_sweep', need(tw), n=n, n_e=n_e)
    return (tw,)


_TPU_TILES = {
    'sparse_mo': _sparse_mo,
    'screened_mo': _screened_mo,
    'sem_update': _sem_update,
    'multidet_ratio': _multidet_ratio,
    'fused_sweep': _fused_sweep,
}


def kernel_plan(kernel: str, tiles: tuple, *, platform: str | None = None,
                **dims) -> KernelPlan:
    """Interpret mode and tiles for one call of ``kernel``.

    Args:
      kernel: one of ``sparse_mo``, ``screened_mo``, ``sem_update``,
        ``multidet_ratio``, ``fused_sweep``.
      tiles: the caller's tiles, used as given when interpreting.
      platform: ``'tpu'`` compiles; anything else interprets.  Defaults to
        ``default_platform()``.
      **dims: the problem widths the TPU tiles and VMEM check depend on
        (``n_ao`` for ``screened_mo``; ``n`` for ``sem_update``; ``n``,
        ``n_e`` and ``n_det`` for ``fused_sweep``, whose requested
        ``tile_w`` shrinks until the grid step fits).

    Raises ``KernelRefused`` on a TPU when the kernel's grid step cannot fit
    VMEM at these widths.
    """
    if kernel not in _TPU_TILES:
        raise ValueError(f'unknown kernel {kernel!r} '
                         f'(choose from {sorted(_TPU_TILES)})')
    if (platform or default_platform()) != 'tpu':
        return KernelPlan(True, tuple(tiles))
    return KernelPlan(False, _TPU_TILES[kernel](tuple(tiles), **dims))


__all__ = ['KernelPlan', 'KernelRefused', 'VMEM_BUDGET', 'default_platform',
           'kernel_plan', 'round_up']
