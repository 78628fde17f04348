"""Batched multideterminant ratio Pallas kernel (walker × det tiled).

The multideterminant single-electron-move hot path evaluates, per walker
and per proposed move, the 2×2 determinants

    det( Tg_I - gp_I ⊗ rh_I )        for all n_det excitations I

plus the CI reduction  S = sum_I c_I det_I r_other_I  — O(W n_det)
memory-bound arithmetic repeated n_e times per sweep.  XLA lowers the jnp
reference to several passes over the (W, n_det) plane (rank-1 correction,
four products, two FMA chains, the weighted reduction); the kernel fuses
the whole chain into one read of each tile.

Tile layout: the operand is ONE (W, 8, n_det) plane stack —

    planes 0..3:  gathered base entries Tg00, Tg01, Tg10, Tg11
    planes 4..5:  gp (rank-1 row factor gathered at particles)
    planes 6..7:  rh (rank-1 column factor gathered at holes)

so a (tile_w, 8, tile_d) block is exactly one f32 VMEM tile stack per
walker row (the sublane dim is the plane axis, the lane dim the
determinant axis; gathers stay outside in XLA, where they are one take
per plane — see ``ops.multidet_ratios``).  The walker grid dimension
reuses the ``sem_update`` walker tiling and is fully parallel; the
determinant dimension is innermost and accumulates the CI partial sums
into a (tile_w, 128) scratch-free output block revisited across det
tiles (lane 0 carries the sum).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(planes_ref, ro_ref, c_ref, out_r_ref, out_s_ref):
    p = planes_ref[...]                                # (tile_w, 8, tile_d)
    t00 = p[:, 0] - p[:, 4] * p[:, 6]
    t01 = p[:, 1] - p[:, 4] * p[:, 7]
    t10 = p[:, 2] - p[:, 5] * p[:, 6]
    t11 = p[:, 3] - p[:, 5] * p[:, 7]
    det = t00 * t11 - t01 * t10                        # (tile_w, tile_d)
    out_r_ref[...] = det
    part = jnp.sum(c_ref[...] * det * ro_ref[...], axis=-1)   # (tile_w,)
    lane = jax.lax.broadcasted_iota(jnp.int32, out_s_ref.shape, 1)
    update = jnp.where(lane == 0, part[:, None], 0.0)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_s_ref[...] = jnp.zeros_like(out_s_ref)

    out_s_ref[...] += update


@functools.partial(jax.jit, static_argnames=('tile_w', 'tile_d',
                                             'interpret'))
def multidet_ratio_matmul(planes: jnp.ndarray, r_other: jnp.ndarray,
                          coeffs: jnp.ndarray, *, tile_w: int = 8,
                          tile_d: int = 128, interpret: bool):
    """Raw kernel dispatch on pre-gathered, pre-padded plane stacks.

    Args:
      planes: (W, 8, n_det) f32, W a multiple of ``tile_w`` and n_det of
        ``tile_d`` (padded dets carry zero planes and zero coeffs).
      r_other: (W, n_det) f32 other-spin ratios.
      coeffs: (1, n_det) f32 CI coefficients.
      interpret: run the Python interpreter backend (off the TPU; see
        ``kernels.platform.kernel_plan``); False compiles for the TPU.

    Returns (ratios (W, n_det), sums (W, 128)) — per-determinant ratios
    and the CI partial sums accumulated into lane 0.
    """
    W, _, n_det = planes.shape
    assert W % tile_w == 0 and n_det % tile_d == 0
    grid = (W // tile_w, n_det // tile_d)
    kwargs = {}
    if not interpret:
        # walker tiles are independent; det tiles accumulate sequentially
        kwargs['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=('parallel', 'arbitrary'))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_w, 8, tile_d), lambda w, d: (w, 0, d)),
            pl.BlockSpec((tile_w, tile_d), lambda w, d: (w, d)),
            pl.BlockSpec((1, tile_d), lambda w, d: (0, d)),
        ],
        out_specs=[
            pl.BlockSpec((tile_w, tile_d), lambda w, d: (w, d)),
            pl.BlockSpec((tile_w, 128), lambda w, d: (w, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((W, n_det), jnp.float32),
            jax.ShapeDtypeStruct((W, 128), jnp.float32),
        ],
        interpret=interpret,
        **kwargs,
    )(planes, r_other, coeffs)
