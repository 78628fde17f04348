"""jit'd wrapper: padding + ref/kernel dispatch for the fused sweep.

``fused_sweep_block`` is the single entry point ``core.sem`` calls per
spin block.  ``use_kernel=False`` (cfg.method == 'fused') runs the
``lax.scan`` oracle directly; ``use_kernel=True`` (cfg.method ==
'fused-kernel') pads the walker axis to the autotuned ``tile_w``
(padded walkers carry ``logu = +1e30`` so they never accept and pass
through untouched), pads the matrix lanes to the f32 VMEM tile on the TPU
(interpret mode has no tiling constraint and skips the blow-up),
dispatches ``kernel.fused_sweep_call`` and slices back.

The multidet path keeps its table dimensions unpadded (the CI gathers
index true orbital rows); it runs in interpret mode only and is refused
on the TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..platform import kernel_plan
from .kernel import fused_sweep_call
from .ref import fused_sweep_ref


def _pad_axis(x: jnp.ndarray, axis: int, mult: int, value=0.0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def fused_sweep_block(minv, phi, r, r_prop, en_delta, logu, b_ee,
                      ci_ops=None, *, offset, n_up, use_kernel=False,
                      tile_w=8):
    """One spin block's fused sweep: scan oracle or Pallas kernel.

    Args:
      minv: (W, n, n) f32 maintained inverse of THIS block.
      phi: (W, n_blk, n_cols) proposal MO values (full orbital panel when
        multidet); r: (W, n_e, 3) current positions (both blocks);
      r_prop: (W, n_blk, 3); en_delta/logu: (W, n_blk).
      b_ee: () e-e Padé denominator.
      ci_ops: None or (P, rdet, r_other, holes, parts, coeffs).
      offset/n_up: static block geometry; use_kernel/tile_w: static
        dispatch knobs.  ``kernels.platform.kernel_plan`` decides interpret
        mode and, on the TPU, shrinks ``tile_w`` to fit VMEM or refuses the
        width.

    Returns (r, minv, P, rdet, accept) with accept a
    (W, n_blk) bool matrix (move-for-move Metropolis outcomes).
    """
    if not use_kernel:
        return _ref_block(minv, phi, r, r_prop, en_delta, logu, b_ee, ci_ops,
                          offset=offset, n_up=n_up)
    n_det = 0 if ci_ops is None else ci_ops[1].shape[-1]
    plan = kernel_plan('fused_sweep', (tile_w,), n=minv.shape[-1],
                       n_e=r.shape[1], n_det=n_det)
    return _kernel_block(minv, phi, r, r_prop, en_delta, logu, b_ee, ci_ops, offset=offset, n_up=n_up,
                         tile_w=plan.tiles[0], interpret=plan.interpret)


@functools.partial(jax.jit, static_argnames=('offset', 'n_up'))
def _ref_block(minv, phi, r, r_prop, en_delta, logu, b_ee, ci_ops, *,
               offset, n_up):
    P = rdet = None
    ci_args = None
    if ci_ops is not None:
        P, rdet, r_other, holes, parts, coeffs = ci_ops
        ci_args = (jnp.asarray(holes, jnp.int32),
                   jnp.asarray(parts, jnp.int32),
                   jnp.asarray(coeffs, jnp.float32), r_other)
    (r, minv, P, rdet), acc = fused_sweep_ref(
        r, minv, phi, r_prop, en_delta, logu, b_ee, offset=offset,
        n_up=n_up, P=P, rdet=rdet, ci_args=ci_args)
    return r, minv, P, rdet, acc


@functools.partial(jax.jit, static_argnames=('offset', 'n_up', 'tile_w',
                                             'interpret'))
def _kernel_block(minv, phi, r, r_prop, en_delta, logu, b_ee, ci_ops, *,
                  offset, n_up, tile_w, interpret):
    W, n, _ = minv.shape
    n_e = r.shape[1]
    # the TPU wants the trailing two block dims of the inverse and the
    # proposal panel on the (8, 128) f32 tile; interpret mode has no
    # constraint and pads only the walker axis.  Positions keep their
    # full (n_e, 3) trailing dims, which the tiling rule also accepts.
    lane = 1 if interpret else 128
    minv_p = _pad_axis(_pad_axis(minv, 1, lane), 2, lane)
    phi_p = _pad_axis(phi, 2, lane)
    args = [minv_p, phi_p, r, r_prop, en_delta, logu]
    args = [_pad_axis(a, 0, tile_w) for a in args]
    # padded walkers must never accept: +1e30 beats any finite log-ratio
    args[5] = _pad_axis(logu, 0, tile_w, value=1e30)
    ci_p = None
    if ci_ops is not None:
        P, rdet, r_other, holes, parts, coeffs = ci_ops
        ci_p = (_pad_axis(P, 0, tile_w), _pad_axis(rdet, 0, tile_w),
                _pad_axis(r_other, 0, tile_w), holes, parts, coeffs)
    out = fused_sweep_call(*args, jnp.asarray(b_ee, jnp.float32), ci_p,
                           offset=offset, n_up=n_up, n_occ=n,
                           n_e_valid=n_e, tile_w=tile_w,
                           interpret=interpret)
    if ci_ops is not None:
        minv_o, r_o, acc, P_o, rdet_o = out
        P_o, rdet_o = P_o[:W], rdet_o[:W]
    else:
        minv_o, r_o, acc = out
        P_o = jnp.zeros((W, 0, 0), minv.dtype)
        rdet_o = jnp.zeros((W, 0), minv.dtype)
    return (r_o[:W], minv_o[:W, :n, :n], P_o, rdet_o,
            acc[:W].astype(bool))


__all__ = ['fused_sweep_block', 'fused_sweep_ref']
