"""Fused single-electron-move sweep Pallas kernel (walker-tiled).

One ``pallas_call`` executes an ENTIRE spin block's sweep — for each
electron in order: determinant ratio against the maintained inverse,
electron-electron Jastrow delta against the current (in-tile) positions,
Metropolis accept, Sherman–Morrison rank-1 inverse update, position
update, and (multidet) the shared P-table / determinant-ratio update —
instead of the per-move path's n_e separate XLA dispatches.  Everything a
move needs that is *precomputable* (proposed positions, their MO values,
e-n Jastrow deltas, log-uniform draws) is evaluated batched outside the
kernel and streamed in as walker-tiled operands (``ref.py`` explains why
that split is exact).

Tile layout: a single grid dimension over walker tiles; each grid step
owns a ``(tile_w, ...)`` slice of every walker-major operand and loops
over the block's electrons with ``fori_loop``, carrying the evolving
``(r, minv, P, rdet)`` state in registers/VMEM and calling
the SAME ``ref._move_step`` math as the scan oracle — kernel-vs-ref
parity is bitwise by construction.  Excitation lists / CI coefficients /
the e-e Padé denominator are tiny replicated operands (every grid step
maps block 0).  Walker tiles are independent, so the grid dimension is
declared ``parallel`` on the TPU; ``kernels.platform.kernel_plan``
decides interpret mode, and refuses widths whose walker tile of the
inverse cannot fit VMEM.

``tile_w`` is chosen by the measured autotuner (``autotune.best_tile_w``,
persisted per (n_e, W, dtype, backend)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import _move_step, take


def _body(refs, *, n_blk, offset, n_up, n_occ, n_e_valid, multidet):
    """Kernel body: unpack tile refs, loop the sweep, write final state."""
    if multidet:
        (minv_ref, phi_ref, r_ref, rp_ref, en_ref, logu_ref, bee_ref,
         p_ref, rdet_ref, roth_ref, holes_ref, parts_ref, coeffs_ref,
         minv_out, r_out, acc_out, p_out, rdet_out) = refs
        ci_args = (holes_ref[...], parts_ref[...], coeffs_ref[...],
                   roth_ref[...])
        P0, rdet0 = p_ref[...], rdet_ref[...]
    else:
        (minv_ref, phi_ref, r_ref, rp_ref, en_ref, logu_ref, bee_ref,
         minv_out, r_out, acc_out) = refs
        # no CI state: None leaves keep zero-size vectors out of the body
        ci_args = P0 = rdet0 = None

    b_ee = bee_ref[0, 0]
    phi = phi_ref[...]                      # (tw, n_blk, n_cols)
    rp = rp_ref[...]                        # (tw, n_blk, 3)
    en = en_ref[...]                        # (tw, n_blk)
    logu = logu_ref[...]
    tw = phi.shape[0]
    acc0 = jnp.zeros((tw, n_blk), jnp.float32)

    def _step(e, carry):
        state, acc = carry
        state, accept = _move_step(
            state, e, take(phi, e), take(rp, e), take(en, e),
            take(logu, e), b_ee,
            offset=offset, n_up=n_up, n_occ=n_occ, n_e_valid=n_e_valid,
            ci_args=ci_args)
        move = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
        acc = jnp.where(move == e, accept[:, None].astype(acc.dtype), acc)
        return state, acc

    state0 = (r_ref[...], minv_ref[...], P0, rdet0)
    (r, minv, P, rdet), acc = jax.lax.fori_loop(
        0, n_blk, _step, (state0, acc0))
    minv_out[...] = minv
    r_out[...] = r
    acc_out[...] = acc
    if multidet:
        p_out[...] = P
        rdet_out[...] = rdet


@functools.partial(jax.jit, static_argnames=('offset', 'n_up', 'n_occ',
                                             'n_e_valid', 'tile_w',
                                             'interpret'))
def fused_sweep_call(minv, phi, r, r_prop, en_delta, logu, b_ee,
                     ci_ops=None, *, offset, n_up, n_occ, n_e_valid,
                     tile_w=8, interpret):
    """Raw kernel dispatch on pre-padded walker-major operands.

    Args:
      minv: (W, n, n) f32, W a multiple of ``tile_w``.
      phi: (W, n_blk, n_cols); r: (W, n_e, 3); r_prop: (W, n_blk, 3);
      en_delta/logu: (W, n_blk); b_ee: (1, 1).
      ci_ops: None or (P (W, n_orb, n_occ), rdet (W, n_det),
        r_other (W, n_det), holes (n_det, k) i32, parts, coeffs (n_det,)).
      offset/n_up/n_occ/n_e_valid: static block geometry (true sizes under
        lane padding — see ``ops.fused_sweep_block``).
      interpret: run the Python interpreter backend (off the TPU; see
        ``kernels.platform.kernel_plan``); False compiles for the TPU.

    Returns (minv, r, acc (W, n_blk) f32 [, P, rdet]).
    """
    W, n, _ = minv.shape
    n_e = r.shape[1]
    n_blk, n_cols = phi.shape[1], phi.shape[2]
    assert W % tile_w == 0
    grid = (W // tile_w,)

    def _w(*block):                        # walker-tiled operand
        return pl.BlockSpec((tile_w,) + tuple(block),
                            lambda w: (w,) + (0,) * len(block))

    def _rep(*block):                      # replicated (small) operand
        return pl.BlockSpec(tuple(block), lambda w: (0,) * len(block))

    in_specs = [_w(n, n), _w(n_blk, n_cols), _w(n_e, 3), _w(n_blk, 3),
                _w(n_blk), _w(n_blk), _rep(1, 1)]
    out_specs = [_w(n, n), _w(n_e, 3), _w(n_blk)]
    out_shape = [jax.ShapeDtypeStruct((W, n, n), minv.dtype),
                 jax.ShapeDtypeStruct((W, n_e, 3), r.dtype),
                 jax.ShapeDtypeStruct((W, n_blk), jnp.float32)]
    operands = [minv, phi, r, r_prop, en_delta, logu, jnp.asarray(b_ee, jnp.float32).reshape(1, 1)]
    multidet = ci_ops is not None
    if multidet:
        P, rdet, r_other, holes, parts, coeffs = ci_ops
        n_orb, n_det = P.shape[1], rdet.shape[1]
        k = holes.shape[-1]
        in_specs += [_w(n_orb, n_occ), _w(n_det), _w(n_det),
                     _rep(n_det, k), _rep(n_det, k), _rep(n_det)]
        out_specs += [_w(n_orb, n_occ), _w(n_det)]
        out_shape += [jax.ShapeDtypeStruct((W, n_orb, n_occ), P.dtype),
                      jax.ShapeDtypeStruct((W, n_det), rdet.dtype)]
        operands += [P, rdet, r_other, jnp.asarray(holes, jnp.int32),
                     jnp.asarray(parts, jnp.int32),
                     jnp.asarray(coeffs, jnp.float32)]

    kwargs = {}
    if not interpret:
        # walker tiles write disjoint output blocks: fully parallel
        kwargs['compiler_params'] = pltpu.CompilerParams(
            dimension_semantics=('parallel',))
    body = functools.partial(_body, n_blk=n_blk, offset=offset, n_up=n_up,
                             n_occ=n_occ, n_e_valid=n_e_valid,
                             multidet=multidet)
    return pl.pallas_call(
        lambda *refs: body(refs),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        **kwargs,
    )(*operands)
