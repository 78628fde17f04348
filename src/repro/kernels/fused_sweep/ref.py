"""Pure-jnp reference for the fused single-electron-move sweep kernel.

The per-move SEM path (``core.sem._sweep_spin_block``) dispatches one AO
evaluation, one MO panel GEMM, one Jastrow vmap and one rank-1 update PER
ELECTRON — n_e small XLA computations per sweep.  The fused sweep exploits
a structural fact of sweep kinetics: every electron is trialed exactly
once, at its sweep-start position, so ALL proposed positions — and
therefore all proposal AO/MO values and all electron-nucleus Jastrow
deltas — are computable up front in one batched pass.  What remains
sequential is only the accept/update algebra (determinant ratio against
the maintained inverse, electron-electron Jastrow delta against the
*current* positions, Sherman–Morrison update, multidet P-table update),
which this module runs as a single ``lax.scan`` over electrons and
``kernel.py`` runs as one walker-tiled Pallas call per spin block.

``_move_step`` is the shared per-move math: the scan here and the kernel's
``fori_loop`` body both call it on identical arrays, which is what makes
the kernel-vs-ref parity tests bitwise (``tests/test_fused_sweep_kernel``).

Sampling statistics match the per-move path in distribution (same proposal
density, same acceptance rule, both sample |Psi_T|^2) but not
move-for-move — the batched AO evaluation is a differently-scheduled XLA
computation.  DESIGN.md §13.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.multidet_ratio.ref import multidet_ratios_ref

# the ratio and Sherman–Morrison products feed the maintained inverse,
# which must track a fresh recompute to 1e-4 (DESIGN.md §6): full fp32
# on the TPU's MXU, whose default for fp32 operands is one bf16 pass
_HIGHEST = jax.lax.Precision.HIGHEST


def _pade_u(r, a, b):
    """Padé value u = a r / (1 + b r) (``core.jastrow._pade`` value part)."""
    return a * r / (1.0 + b * r)


def _ee_sum(r, j, point, n_up, b_ee, n_e_valid):
    """sum_{i != j} U_ee(|point - r_i|) over the current configuration.

    The electron-electron half of ``jastrow_delta_one_electron`` batched
    over walkers: spin-dependent cusp strengths (0.25 parallel / 0.5
    anti-parallel), the self pair masked out, the same ``+1e-20``
    guarded distance.  ``n_e_valid`` masks lane-padded electron rows
    (no-op when r is unpadded).

    r: (W, n_e, 3); point: (W, 3); j: traced electron index.
    Returns (W,).
    """
    d = point[:, None, :] - r                             # (W, n_e, 3)
    dist = jnp.sqrt(jnp.sum(d * d, axis=-1) + 1e-20)
    i = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    a = jnp.where((i < n_up) == (j < n_up), jnp.asarray(0.25, r.dtype),
                  jnp.asarray(0.5, r.dtype))
    u = _pade_u(dist, a, b_ee)
    keep = ((i != j) & (i < n_e_valid)).astype(r.dtype)
    return jnp.sum(u * keep, axis=-1)


def take(x, i, axis=1):
    """``x`` at index ``i`` of ``axis`` (traced ``i``), as a masked sum.

    The TPU kernel body cannot slice a value at a traced index; selecting
    with an iota mask and summing adds only exact zeros, so the result is
    the slice itself, bit for bit.
    """
    hit = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis) == i
    return jnp.sum(jnp.where(hit, x, jnp.zeros_like(x)), axis=axis)


def _move_step(state, e, phi_e, rp_e, en_e, logu_e, b_ee, *, offset, n_up,
               n_occ, n_e_valid, ci_args=None):
    """One electron's Metropolis trial + state update, all walkers.

    The single source of truth for fused-sweep move semantics: called per
    scan step by ``fused_sweep_ref`` and per ``fori_loop`` step inside the
    Pallas kernel body, on the same arrays — bitwise-identical by
    construction.

    Args:
      state: (r, minv, P, rdet) — P/rdet are zero-size
        arrays (scan) or None (kernel) in the single-determinant case.
      e: block-local electron index (traced).
      phi_e: (W, n_cols) proposal MO values (occupied panel = [:, :n_occ];
        full orbital panel with ``ci_args``).
      rp_e: (W, 3) proposed position; en_e: (W,) precomputed e-n Jastrow
        delta; logu_e: (W,) log of the Metropolis uniform draw.
      b_ee: () e-e Padé denominator (traced).
      offset/n_up/n_occ/n_e_valid: static block geometry (``n_occ`` and
        ``n_e_valid`` are the TRUE sizes — lane-padded columns/rows beyond
        them are masked/ignored).
      ci_args: (holes, parts, coeffs, r_other) arrays or None.

    Returns (new_state, accept (W,) bool).
    """
    r, minv, P, rdet = state
    j = offset + e
    r_old = take(r, j)                                    # (W, 3)
    # lanes past n_occ are the zero padding of minv and phi (the CI panel's
    # virtual orbitals are sliced off), so full-lane sums are exact
    phi = phi_e[:, :minv.shape[-1]]
    minv_e = take(minv, e)                                # (W, n_lanes)
    ratio = jnp.einsum('wxo,wyo->wxy', minv_e[:, None], phi[:, None],
                       precision=_HIGHEST)[:, 0, 0]
    ee_new = _ee_sum(r, j, rp_e, n_up, b_ee, n_e_valid)
    ee_old = _ee_sum(r, j, r_old, n_up, b_ee, n_e_valid)
    d_jas = ee_new - ee_old + en_e
    log_ratio = jnp.log(jnp.abs(ratio) + 1e-30)
    if ci_args is not None:
        holes, parts, coeffs, r_other = ci_args
        g_vec = jnp.einsum('woh,wh->wo', P, phi,
                           precision=_HIGHEST) - phi_e
        row_t = minv_e[:, :n_occ] / ratio[:, None]
        rdet_new, S_new = multidet_ratios_ref(P, g_vec, row_t, holes,
                                              parts, coeffs, r_other)
        S_old = jnp.einsum('d,wd,wd->w', coeffs, rdet, r_other)
        log_ci = (jnp.log(jnp.abs(S_new) + 1e-30)
                  - jnp.log(jnp.abs(S_old) + 1e-30))
    else:
        log_ci = 0.0
    accept = logu_e < 2.0 * (log_ratio + log_ci + d_jas)
    if ci_args is not None:
        # near-reference-node guard — see core.sem._sweep_spin_block
        accept = accept & (jnp.abs(ratio) > 1e-20)

    u_vec = jnp.einsum('weo,wo->we', minv, phi,
                       precision=_HIGHEST)                # (W, n_rows)
    safe = jnp.where(jnp.abs(ratio) > 1e-20, ratio, 1.0)
    row = minv_e / safe[:, None]
    # rank-1 update + row replacement via iota select (kernel-safe store)
    upd = minv - u_vec[:, :, None] * row[:, None, :]
    elec = jax.lax.broadcasted_iota(jnp.int32, upd.shape, 1)
    upd = jnp.where(elec == e, row[:, None, :], upd)
    minv = jnp.where(accept[:, None, None], upd, minv)
    r_sel = jnp.where(accept[:, None], rp_e, r_old)       # (W, 3)
    ri = jax.lax.broadcasted_iota(jnp.int32, r.shape, 1)
    r = jnp.where(ri == j, r_sel[:, None, :], r)
    if ci_args is not None:
        P = jnp.where(accept[:, None, None],
                      P - g_vec[:, :, None] * row[:, None, :n_occ], P)
        rdet = jnp.where(accept[:, None], rdet_new, rdet)
    return (r, minv, P, rdet), accept


def fused_sweep_ref(r, minv, phi, r_prop, en_delta, logu, b_ee, *, offset, n_up, n_occ=None, n_e_valid=None,
                    P=None, rdet=None, ci_args=None):
    """One spin block's whole sweep as a single scan — the fused oracle.

    Args:
      r: (W, n_e, 3) current positions (BOTH spin blocks — the e-e Jastrow
        delta needs them); minv: (W, n, n).
      phi: (W, n_blk, n_cols) precomputed proposal MO values.
      r_prop: (W, n_blk, 3) precomputed proposals; en_delta/logu:
        (W, n_blk) precomputed e-n Jastrow deltas / log-uniform draws.
      b_ee: () e-e Padé denominator.
      offset: first electron of this block; n_up: spin boundary.
      n_occ/n_e_valid: true occupied/electron counts when lane-padded
        (default: unpadded sizes).
      P/rdet + ci_args=(holes, parts, coeffs, r_other): multidet state.

    Returns ((r, minv, P, rdet), accept (W, n_blk) bool).
    """
    W, n_blk = r_prop.shape[:2]
    if n_occ is None:
        n_occ = minv.shape[-1]
    if n_e_valid is None:
        n_e_valid = r.shape[1]
    if P is None:
        P = jnp.zeros((W, 0, 0), minv.dtype)
    if rdet is None:
        rdet = jnp.zeros((W, 0), minv.dtype)

    def _move(state, e):
        return _move_step(state, e, phi[:, e], r_prop[:, e],
                          en_delta[:, e], logu[:, e], b_ee, offset=offset,
                          n_up=n_up, n_occ=n_occ, n_e_valid=n_e_valid,
                          ci_args=ci_args)

    state, acc = jax.lax.scan(_move, (r, minv, P, rdet), jnp.arange(n_blk))
    return state, acc.T                                   # (W, n_blk)
