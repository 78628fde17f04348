"""Single-electron-move VMC: Sherman–Morrison-updated Slater inverses.

The paper's scaling argument (and the classic improved-scaling QMC line:
Ahuja et al.'s insulator updates, Alfè & Gillan's localized orbitals) rests
on moving ONE electron at a time: the determinant ratio for a proposed move
of electron ``j`` is a single dot product against the maintained inverse
Slater matrix, and an accepted move is a rank-1 Sherman–Morrison inverse
update — O(n) accept/reject and O(n^2) update instead of the O(n^3)
factorization the all-electron propagator pays every step.

One ``propagate`` call is one *sweep*: every electron gets one Metropolis
trial, batched over the whole walker ensemble (the ``(W, n, n)`` rank-1
axpy is the hot path — jnp reference in ``kernels.sem_update.ref``, Pallas
kernel in ``kernels.sem_update.kernel``, selected by
``cfg.method == 'kernel'``).  Per move only AO *values* at the proposed
point are needed (``aos.eval_ao_values``) plus an O(n_e) Jastrow delta
(``jastrow.jastrow_delta_one_electron``).  After the sweep one full MO
tensor pass assembles the local energy through the *maintained* inverses
(``slater.ratios_from_inverse``), with

* a Newton–Schulz ``refine_inverse`` corrector every sweep,
* a full ``inv`` refresh every ``cfg.sem_refresh`` sweeps, and
* sign and log|det| from one batched LU of the sweep's Slater matrices,

bounding fp32 drift of the running inverse (DESIGN.md §6 has the
error-bound argument; tests pin <=1e-4 agreement with a fresh recompute
between refreshes).

``SEMVMCPropagator`` is a standard ``driver.Propagator`` plug-in: the same
``EnsembleDriver`` block loop, ``--shards N`` walker-mesh sharding, runtime
``BlockSampler``, and ``qmc_run --method sem-vmc`` all work unchanged.
Sampling statistics match the all-electron VMC propagator in distribution
(both sample |Psi_T|^2) but not move-for-move — see DESIGN.md §6.

Multideterminant trial functions (``cfg.ci``) ride the same sweeps: the
ensemble additionally maintains the shared ratio tables P = V @ Minv and
all determinants' current ratios, each proposal's CI factor comes from a
rank-1 table update evaluated by ``kernels.multidet_ratio`` (Pallas when
``cfg.method == 'kernel'``), and an accepted move applies
``P <- P - g (x) row`` next to the Sherman–Morrison inverse update — the
per-move cost stays O(n_orb n + n_det k^2), never O(n_det n^3)
(DESIGN.md §8).

k_max contract: per-move ratios use the *exact* (radius-screened) AO
values, while the sparse/kernel post-sweep pipeline packs at most
``cfg.k_max`` active AOs per electron.  These coincide only while k_max
covers every electron's active set — the same no-overflow regime the rest
of the sparse pipeline assumes (``aos.active_ao_indices`` returns the true
counts for monitoring).  Under overflow the refresh would snap the state
to a *truncated* wavefunction the move ratios never sampled; size k_max
like the paper (~1.1x the measured max active count) to stay exact.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import aos, multidet, slater
from .driver import (BlockStats as DriverStats, Population, register_method,
                     restart_ensemble)
from .jastrow import jastrow_delta_one_electron, jastrow_state
from .hamiltonian import potential_energy
from .vmc import evaluate_ensemble, sample_positions
from .wavefunction import (WavefunctionConfig, WavefunctionParams,
                           _ci_blocks, _mo_tensor_ensemble, _slater_blocks)


# Products that feed the maintained inverses (proposal MO values, the
# determinant ratio, the Sherman–Morrison column, the CI table update) run
# at full fp32: a TPU's default for fp32 operands is one bf16 pass, far
# short of the 1e-4 drift contract against a fresh recompute (DESIGN.md §6).
_HIGHEST = jax.lax.Precision.HIGHEST


class SEMEnsemble(NamedTuple):
    """Walker-major single-electron-move state (driver-sharded leading axis).

    Unlike the all-electron ``WalkerEnsemble`` this carries the running
    inverse Slater matrices per spin block — the state Sherman–Morrison
    updates maintain across sweeps — and, for multideterminant
    wavefunctions (``cfg.ci``), the shared ratio tables P = V @ M plus all
    determinants' current ratios (the SMW state the per-move CI evaluation
    reads; zero-size arrays in the single-determinant case).
    """

    r: jnp.ndarray          # (W, n_e, 3)
    minv_up: jnp.ndarray    # (W, n_up, n_up) running inverse (elec, orb)
    minv_dn: jnp.ndarray    # (W, n_dn, n_dn)
    sign: jnp.ndarray       # (W,) sign of Det_up * Det_dn (ref det)
    logdet: jnp.ndarray     # (W,) sum of log|det| over spins (ref det)
    log_psi: jnp.ndarray    # (W,) logdet [+ log|CI sum|] + J
    e_loc: jnp.ndarray      # (W,)
    p_up: jnp.ndarray       # (W, n_orb, n_up) shared table (ci; else (W,0,0))
    p_dn: jnp.ndarray       # (W, n_orb, n_dn)
    rdet_up: jnp.ndarray    # (W, n_det) per-det ratios to the reference
    rdet_dn: jnp.ndarray    # (W, n_det)


class SEMState(NamedTuple):
    """Driver state: walker ensemble + replicated sweep counter."""

    ens: SEMEnsemble
    sweeps: jnp.ndarray     # () int32 sweeps since the last full refresh


def _mo_blocks(cfg: WavefunctionConfig, params: WavefunctionParams):
    """Per-spin MO coefficient panels (rows of the 'A' matrix).

    With ``cfg.ci`` both spins get the FULL shared orbital set (the
    per-move CI evaluation needs virtual-orbital values too); the
    occupied panel is its leading slice.
    """
    if cfg.ci is not None:
        A_full = params.mo[:cfg.ci.n_orb]
        return A_full, A_full
    A_up = params.mo[:cfg.n_up]
    A_dn = (params.mo[:cfg.n_dn] if cfg.shared_orbitals
            else params.mo[cfg.n_up:cfg.n_up + cfg.n_dn])
    return A_up, A_dn


def _apply_update(cfg, minv, u_vec, row, accept, e):
    """Batched SM update: Pallas kernel when cfg.method == 'kernel'."""
    if cfg.method == 'kernel':
        from repro.kernels.sem_update.ops import sem_rank1_update
        return sem_rank1_update(minv, u_vec, row, accept, e)
    from repro.kernels.sem_update.ref import sem_update_ref
    return sem_update_ref(minv, u_vec, row, accept, e)


def _move_ci_ratios(cfg, P, g, row, holes, parts, r_other):
    """All-excitation move ratios + CI sum: Pallas kernel when
    cfg.method == 'kernel' and the excitation rank allows (k <= 2)."""
    ci = cfg.ci
    if cfg.method == 'kernel' and ci.k <= 2:
        from repro.kernels.multidet_ratio.ops import multidet_ratios
        return multidet_ratios(P, g, row, holes, parts, ci.coeffs, r_other)
    from repro.kernels.multidet_ratio.ref import multidet_ratios_ref
    return multidet_ratios_ref(P, g, row, holes, parts, ci.coeffs, r_other)


# fp16 resting state is stored with this exact power-of-two exponent
# shift: minv entries reach ~1e5 on the bench systems while float16
# saturates at 65504, so the raw cast would overflow to inf.  A
# power-of-two scale only moves the exponent — zero mantissa error on
# both cast directions — and extends the representable range to ~1e6.
# bf16 carries the full fp32 exponent range and needs no shift.
_FP16_SCALE = 16.0


def _to_compute(x, cfg):
    """Storage -> fp32 compute dtype at the sweep/use boundary.

    The mixed-precision contract (DESIGN.md §13): ratios, Sherman–Morrison
    updates, Newton–Schulz refinement and energy contractions all
    accumulate in fp32; only the resting (W, n, n) state is quantized.
    At the default ``precision='fp32'`` this returns ``x`` itself — the
    policy is structurally bitwise-inert (tests/test_precision.py).
    """
    if cfg.precision == 'fp32':
        return x
    x32 = x.astype(jnp.float32)
    return x32 * _FP16_SCALE if cfg.precision == 'fp16' else x32


def _to_storage(x, cfg):
    """fp32 compute -> storage dtype (no-op object passthrough at fp32)."""
    if cfg.precision == 'fp32':
        return x
    if cfg.precision == 'fp16':
        return (x * (1.0 / _FP16_SCALE)).astype(jnp.float16)
    return x.astype(slater.storage_dtype(cfg.precision))


def _empty_ci_state(W, dtype):
    """Zero-size CI leaves for the single-determinant ensemble.

    Four DISTINCT arrays: the driver donates the state buffers, and two
    fields aliasing one buffer is a double donation."""
    return (jnp.zeros((W, 0, 0), dtype), jnp.zeros((W, 0, 0), dtype),
            jnp.zeros((W, 0), dtype), jnp.zeros((W, 0), dtype))


@jax.named_scope('qmc.jastrow_energy')
def _energy_ensemble(cfg: WavefunctionConfig, params: WavefunctionParams,
                     R, Cw, minv_up, minv_dn, sign, logdet) -> SEMEnsemble:
    """Assemble the SEM ensemble from maintained inverses (no inversion).

    The factorization-free sibling of ``wavefunction._finish_state``:
    drift/Laplacian ratios come from ``slater.ratios_from_inverse`` against
    the running ``minv`` blocks; ``sign``/``logdet`` are passed in (the
    propagator takes them from one batched LU per sweep).  With ``cfg.ci`` the
    shared ratio tables and all determinant ratios are (re)built from the
    same maintained inverses (one GEMM + gathered k×k dets per spin —
    still zero factorizations) and grad/lap become the CI-weighted
    contractions of ``multidet.ci_corrections``.
    """
    ci = cfg.ci
    if ci is not None:
        up_all, dn_all = _ci_blocks(cfg, Cw)
        p_up = multidet.reference_table(up_all[..., 0], minv_up)
        rdet_up = multidet.det_ratios(p_up, ci.holes_up, ci.parts_up)
        if cfg.n_dn > 0:
            p_dn = multidet.reference_table(dn_all[..., 0], minv_dn)
            rdet_dn = multidet.det_ratios(p_dn, ci.holes_dn, ci.parts_dn)
        else:
            p_dn = jnp.zeros(minv_dn.shape[:-2] + (0, 0), p_up.dtype)
            rdet_dn = jnp.ones_like(rdet_up)
        w, S = multidet.ci_weights(ci.coeffs, rdet_up, rdet_dn)
        cu = multidet.ci_corrections(ci.holes_up, ci.parts_up, up_all,
                                     minv_up, p_up, w)
        gu, qu = slater.ratios_from_inverse(up_all[..., :cfg.n_up, :, :],
                                            minv_up)
        gu, qu = gu + cu[..., :3], qu + cu[..., 3]
        if cfg.n_dn > 0:
            cd = multidet.ci_corrections(ci.holes_dn, ci.parts_dn, dn_all,
                                         minv_dn, p_dn, w)
            gd, qd = slater.ratios_from_inverse(
                dn_all[..., :cfg.n_dn, :, :], minv_dn)
            gd, qd = gd + cd[..., :3], qd + cd[..., 3]
            sgrad = jnp.concatenate([gu, gd], axis=1)
            slap = jnp.concatenate([qu, qd], axis=1)
        else:
            sgrad, slap = gu, qu
        _, log_ci = multidet.ci_log_sum(S)
    else:
        up, dn = _slater_blocks(cfg, Cw)
        gu, qu = slater.ratios_from_inverse(up, minv_up)
        if cfg.n_dn > 0:
            gd, qd = slater.ratios_from_inverse(dn, minv_dn)
            sgrad = jnp.concatenate([gu, gd], axis=1)
            slap = jnp.concatenate([qu, qd], axis=1)
        else:
            sgrad, slap = gu, qu
        p_up, p_dn, rdet_up, rdet_dn = _empty_ci_state(R.shape[0],
                                                       minv_up.dtype)
        log_ci = jnp.zeros_like(logdet)

    def _tail(r, g, q):
        jas = jastrow_state(params.jastrow, r, params.coords,
                            params.charges, cfg.n_up)
        lap_ratio = (q + jas.lap + jnp.sum(jas.grad * jas.grad, axis=-1)
                     + 2.0 * jnp.sum(jas.grad * g, axis=-1))
        e_kin = -0.5 * jnp.sum(lap_ratio)
        e_pot = potential_energy(r, params.coords, params.charges)
        return jas.value, e_kin, e_pot

    jv, e_kin, e_pot = jax.vmap(_tail)(R, sgrad, slap)
    # storage boundary: the (W, n, n) inverses and P-tables rest in the
    # precision policy's dtype; everything above accumulated in fp32
    return SEMEnsemble(r=R, minv_up=_to_storage(minv_up, cfg),
                       minv_dn=_to_storage(minv_dn, cfg), sign=sign,
                       logdet=logdet, log_psi=logdet + log_ci + jv,
                       e_loc=e_kin + e_pot, p_up=_to_storage(p_up, cfg),
                       p_dn=_to_storage(p_dn, cfg),
                       rdet_up=rdet_up, rdet_dn=rdet_dn)


def evaluate_sem(cfg: WavefunctionConfig, params: WavefunctionParams,
                 R: jnp.ndarray) -> SEMEnsemble:
    """Full recompute of the SEM state for a walker batch R: (W, n_e, 3).

    The cold-start / restart / refresh oracle: batched ``slogdet`` + ``inv``
    (+ Newton–Schulz) per spin block, then the shared energy assembly.
    """
    W = R.shape[0]
    Cw, _ = _mo_tensor_ensemble(cfg, params, R)
    up, dn = _slater_blocks(cfg, Cw)
    with jax.named_scope('qmc.slater'):
        su, lu, _, _, mu = slater._spin_block_batched(up, cfg.ns_steps)
        if cfg.n_dn > 0:
            sd, ld, _, _, md = slater._spin_block_batched(dn, cfg.ns_steps)
            sign, logdet = su * sd, lu + ld
        else:
            sign, logdet = su, lu
            md = jnp.zeros((W, 0, 0), Cw.dtype)
    return _energy_ensemble(cfg, params, R, Cw, mu, md, sign, logdet)


@jax.named_scope('qmc.sem_move')
def _sweep_spin_block(cfg, params, A_blk, offset, n_blk, wkeys, step_size,
                      carry, ci_args=None):
    """One Metropolis trial per electron of one spin block, all walkers.

    ``carry`` is ``(r, minv)`` with ``minv`` the running
    inverse of THIS spin block; electrons ``offset .. offset+n_blk-1`` are
    scanned in order, so a later electron sees the earlier accepted moves
    of the same sweep (sequential-sweep semantics, batched over walkers).
    Returns the updated carry and the per-move local acceptance fractions.

    Multideterminant sweeps (``ci_args = (holes, parts, r_other)``) extend
    the carry with ``(P, rdet)`` — this spin's shared table and all
    determinants' running ratios.  ``A_blk`` is then the FULL orbital
    panel; per move the CI factor of the acceptance ratio comes from the
    rank-1-updated table (``kernels.multidet_ratio``) and an accepted move
    applies  P <- P - g ⊗ row  alongside the Sherman–Morrison ``minv``
    update (DESIGN.md §8).
    """
    coords, charges = params.coords, params.charges
    ci = cfg.ci if ci_args is not None else None
    if ci is not None:
        holes, parts, r_other = ci_args
        coeffs = jnp.asarray(ci.coeffs)

    def _move(carry, e):
        if ci is not None:
            r, minv, P, rdet = carry
        else:
            r, minv = carry
        j = offset + e
        keys = jax.vmap(lambda k: jax.random.fold_in(k, j))(wkeys)

        def _draw(k):
            ke, ku = jax.random.split(k)
            return (jax.random.normal(ke, (3,), r.dtype),
                    jax.random.uniform(ku, (), r.dtype))

        eta, u_rand = jax.vmap(_draw)(keys)
        r_old = r[:, j]                                   # (W, 3)
        r_new = r_old + step_size * eta
        scr = cfg.screening
        if scr is not None and not scr.exhaustive:
            # screened per-move path: only active (electron, AO) pairs are
            # evaluated, and with MO reach radii only active orbital rows
            # are contracted — O(budget) per proposal instead of O(n_ao)
            from . import screening as scr_mod
            a_idx, a_act, _ = scr_mod.active_ao_lists(scr, r_new)
            vals_p = aos.eval_ao_values_screened(cfg.basis, coords, r_new,
                                                 a_idx, a_act)   # (W, K)
            if scr.mo_cells is not None:
                mo_idx, mo_valid = scr_mod.active_mo_lists(scr, r_new)
                v_all = scr_mod.gather_phi(A_blk, a_idx, vals_p, mo_idx,
                                           mo_valid)
            else:
                v_all = scr_mod.phi_from_packed(A_blk, a_idx, vals_p,
                                                cfg.basis.n_ao)
        else:
            vals, _ = aos.eval_ao_values(cfg.basis, coords, r_new)  # (ao,W)
            v_all = jnp.matmul(A_blk, vals, precision=_HIGHEST).T
        phi = v_all[:, :minv.shape[-1]]          # occupied panel
        ratio = jnp.einsum('wo,wo->w', minv[:, e, :], phi,
                           precision=_HIGHEST)
        d_jas = jax.vmap(
            lambda rw, rn: jastrow_delta_one_electron(
                params.jastrow, rw, j, rn, coords, charges, cfg.n_up)
        )(r, r_new)
        log_ratio = jnp.log(jnp.abs(ratio) + 1e-30)
        if ci is not None:
            # CI factor: all excitation ratios off the rank-1-updated
            # table (un-guarded 1/ratio: a near-node reference move makes
            # the comparison NaN -> rejected, like the log barrier)
            g_vec = jnp.einsum('woh,wh->wo', P, phi,
                               precision=_HIGHEST) - v_all
            row_t = minv[:, e, :] / ratio[:, None]
            rdet_new, S_new = _move_ci_ratios(cfg, P, g_vec, row_t,
                                              holes, parts, r_other)
            S_old = jnp.einsum('d,wd,wd->w', coeffs, rdet, r_other)
            log_ci = (jnp.log(jnp.abs(S_new) + 1e-30)
                      - jnp.log(jnp.abs(S_old) + 1e-30))
        else:
            log_ci = 0.0
        accept = jnp.log(jnp.maximum(u_rand, 1e-38)) < \
            2.0 * (log_ratio + log_ci + d_jas)
        if ci is not None:
            # Near-REFERENCE-node guard: unlike the single-det path
            # (where log_ratio alone makes |ratio| <= 1e-20 unacceptable),
            # the CI factor S_new ~ 1/ratio can cancel the log barrier —
            # the full wavefunction is finite where only the reference is
            # singular.  The SMW representation itself (P = V @ Minv)
            # breaks down there, so such moves are rejected outright; the
            # excluded set has vanishing measure and the rejection keeps
            # the guarded ``row`` below exact on every ACCEPTED walker.
            accept = accept & (jnp.abs(ratio) > 1e-20)

        u_vec = jnp.einsum('weo,wo->we', minv, phi,
                           precision=_HIGHEST)            # (W, n_blk)
        safe = jnp.where(jnp.abs(ratio) > 1e-20, ratio, 1.0)
        row = minv[:, e, :] / safe[:, None]
        minv = _apply_update(cfg, minv, u_vec, row, accept, e)
        r = r.at[:, j].set(jnp.where(accept[:, None], r_new, r_old))
        acc_frac = jnp.mean(accept.astype(jnp.float32))
        if ci is not None:
            P = jnp.where(accept[:, None, None],
                          P - g_vec[:, :, None] * row[:, None, :], P)
            rdet = jnp.where(accept[:, None], rdet_new, rdet)
            return (r, minv, P, rdet), acc_frac
        return (r, minv), acc_frac

    return jax.lax.scan(_move, carry, jnp.arange(n_blk))


def _fused_phi_block(cfg, params, A_blk, pts):
    """Proposal MO values for a whole block's sweep in ONE batched pass.

    ``pts``: (N, 3) flattened proposed positions (N = W * n_blk).  Returns
    (N, n_occ | n_orb) — the same screened-or-dense arithmetic as the
    per-move path of ``_sweep_spin_block``, evaluated once instead of
    n_blk times.
    """
    scr = cfg.screening
    if scr is not None and not scr.exhaustive:
        from . import screening as scr_mod
        a_idx, a_act, _ = scr_mod.active_ao_lists(scr, pts)
        vals_p = aos.eval_ao_values_screened(cfg.basis, params.coords, pts,
                                             a_idx, a_act)
        if scr.mo_cells is not None:
            mo_idx, mo_valid = scr_mod.active_mo_lists(scr, pts)
            return scr_mod.gather_phi(A_blk, a_idx, vals_p, mo_idx,
                                      mo_valid)
        return scr_mod.phi_from_packed(A_blk, a_idx, vals_p,
                                       cfg.basis.n_ao)
    vals, _ = aos.eval_ao_values(cfg.basis, params.coords, pts)  # (ao, N)
    return jnp.matmul(A_blk, vals, precision=_HIGHEST).T


def _fused_phi_all(cfg, params, A_up, A_dn, r_prop):
    """Proposal MO values for BOTH spin blocks from one shared AO pass.

    The AO-side work (cell lookup, screened or dense AO evaluation) does
    not depend on the MO panel, so all W * n_e proposals go through a
    single batched pass and only the final panel product is per-spin.
    Two half-population ``_fused_phi_block`` calls measure ~3x slower
    than this combined pass on CPU — XLA schedules the two separate AO
    evaluations far worse than one — which is most of the fused sweep's
    advantage at large W.

    r_prop: (W, n_e, 3).  Returns (phi_up (W, n_up, cols),
    phi_dn (W, n_dn, cols) or None when n_dn == 0).
    """
    W, n_e = r_prop.shape[:2]
    n_up, n_dn = cfg.n_up, cfg.n_dn
    pts = r_prop.reshape(W * n_e, 3)
    scr = cfg.screening

    def _split(x):
        xb = x.reshape((W, n_e) + x.shape[1:])
        return (xb[:, :n_up].reshape((W * n_up,) + x.shape[1:]),
                xb[:, n_up:].reshape((W * n_dn,) + x.shape[1:]))

    if scr is not None and not scr.exhaustive:
        from . import screening as scr_mod
        a_idx, a_act, _ = scr_mod.active_ao_lists(scr, pts)
        vals = aos.eval_ao_values_screened(cfg.basis, params.coords, pts,
                                           a_idx, a_act)
        iu, idn = _split(a_idx)
        vu, vdn = _split(vals)
        if scr.mo_cells is not None:
            mo_idx, mo_valid = scr_mod.active_mo_lists(scr, pts)
            miu, midn = _split(mo_idx)
            mvu, mvdn = _split(mo_valid)
            phi_up = scr_mod.gather_phi(A_up, iu, vu, miu, mvu)
            phi_dn = (scr_mod.gather_phi(A_dn, idn, vdn, midn, mvdn)
                      if n_dn > 0 else None)
        else:
            phi_up = scr_mod.phi_from_packed(A_up, iu, vu, cfg.basis.n_ao)
            phi_dn = (scr_mod.phi_from_packed(A_dn, idn, vdn,
                                              cfg.basis.n_ao)
                      if n_dn > 0 else None)
        return (phi_up.reshape(W, n_up, -1),
                phi_dn.reshape(W, n_dn, -1) if phi_dn is not None else None)
    vals, _ = aos.eval_ao_values(cfg.basis, params.coords, pts)  # (ao, N)
    if n_dn == 0:
        return jnp.matmul(A_up, vals, precision=_HIGHEST).T.reshape(
            W, n_up, -1), None
    if (A_up.shape == A_dn.shape
            and (A_up is A_dn or A_up.shape[0] == cfg.n_up == cfg.n_dn
                 and cfg.shared_orbitals)):
        # closed shell / CI: one panel serves both blocks -> ONE GEMM in
        # the AO-major layout, split afterwards
        phi = jnp.matmul(A_up, vals, precision=_HIGHEST).T.reshape(
            W, n_e, -1)
        return phi[:, :n_up], phi[:, n_up:]
    chi = vals.T.reshape(W, n_e, -1)
    phi_up = jnp.einsum('wea,oa->weo', chi[:, :n_up], A_up,
                        precision=_HIGHEST)
    phi_dn = jnp.einsum('wea,oa->weo', chi[:, n_up:], A_dn,
                        precision=_HIGHEST)
    return phi_up, phi_dn


@jax.named_scope('qmc.sem_move')
def _fused_sweeps(cfg, params, ens, minv_up, minv_dn, p_up, p_dn, wkeys,
                  step_size):
    """Both spin blocks' sweeps through the fused path (DESIGN.md §13).

    Precomputes, in one batched pass each, everything the sweep needs that
    does not depend on intra-sweep state — each electron is trialed
    exactly once, at its sweep-start position, so all proposals, their MO
    values and the e-n Jastrow deltas are known up front.  The remaining
    sequential accept/update algebra runs as one ``lax.scan``
    (cfg.method == 'fused') or one Pallas kernel call
    (cfg.method == 'fused-kernel', walker tile from the measured
    autotuner) per spin block.  RNG consumption matches the per-move path
    (``fold_in(walker_key, j)`` then normal/uniform), so the proposal
    stream is the same; statistics agree with the per-move sweep in
    distribution, not move-for-move.

    Returns (r, minv_up, minv_dn, accepts) — ``accepts`` the
    (n_e,) per-move mean acceptance fractions.
    """
    from repro.kernels.fused_sweep.ops import fused_sweep_block
    ci = cfg.ci
    W, n_e = ens.r.shape[:2]
    n_up, n_dn = cfg.n_up, cfg.n_dn
    A_up, A_dn = _mo_blocks(cfg, params)
    jas = params.jastrow

    def _draw_all(k):
        def _one(j):
            ke, ku = jax.random.split(jax.random.fold_in(k, j))
            return (jax.random.normal(ke, (3,), ens.r.dtype),
                    jax.random.uniform(ku, (), ens.r.dtype))
        return jax.vmap(_one)(jnp.arange(n_e))

    eta, u_rand = jax.vmap(_draw_all)(wkeys)        # (W, n_e, 3), (W, n_e)
    r_prop = ens.r + step_size * eta
    logu = jnp.log(jnp.maximum(u_rand, 1e-38))

    # e-n Jastrow delta per proposal: depends only on the endpoints
    def _en_sum(pts):
        d = pts[..., None, :] - params.coords
        rn = jnp.sqrt(jnp.sum(d * d, axis=-1) + 1e-20)
        a = -params.charges * jas.a_en
        return jnp.sum(a * rn / (1.0 + jas.b_en * rn), axis=-1)

    en_delta = _en_sum(r_prop) - _en_sum(ens.r)     # (W, n_e)

    use_kernel = cfg.method == 'fused-kernel'
    tile_w = 8
    if use_kernel:
        from repro.kernels.fused_sweep.autotune import best_tile_w
        tile_w = best_tile_w(n_e, W, cfg.precision)

    phi_up, phi_dn = _fused_phi_all(cfg, params, A_up, A_dn, r_prop)
    ci_up = (p_up, ens.rdet_up, ens.rdet_dn, ci.holes_up, ci.parts_up,
             ci.coeffs) if ci is not None else None
    r, minv_up, _, rdet_up, acc_up = fused_sweep_block(
        minv_up, phi_up, ens.r, r_prop[:, :n_up], en_delta[:, :n_up],
        logu[:, :n_up], jas.b_ee, ci_up,
        offset=0, n_up=n_up, use_kernel=use_kernel, tile_w=tile_w)

    if n_dn > 0:
        ci_dn = (p_dn, ens.rdet_dn, rdet_up, ci.holes_dn, ci.parts_dn,
                 ci.coeffs) if ci is not None else None
        r, minv_dn, _, _, acc_dn = fused_sweep_block(
            minv_dn, phi_dn, r, r_prop[:, n_up:], en_delta[:, n_up:],
            logu[:, n_up:], jas.b_ee, ci_dn,
            offset=n_up, n_up=n_up, use_kernel=use_kernel, tile_w=tile_w)
        accepts = jnp.concatenate([
            jnp.mean(acc_up.astype(jnp.float32), axis=0),
            jnp.mean(acc_dn.astype(jnp.float32), axis=0)])
    else:
        accepts = jnp.mean(acc_up.astype(jnp.float32), axis=0)
    return r, minv_up, minv_dn, accepts


class SEMVMCPropagator:
    """Metropolis sampling of |Psi_T|^2 by single-electron sweeps (§II.A).

    A drop-in ``driver.Propagator``: same |Psi_T|^2 target distribution as
    ``VMCPropagator`` (stats agree in distribution, not move-for-move), at
    O(n^2) update cost per electron move instead of a full recompute.
    """

    aux_fields = ('accept', 'ao_fill', 'e_kin', 'e_pot')

    def __init__(self, cfg: WavefunctionConfig, step_size: float = 0.3,
                 spread: float = 1.5):
        """``step_size`` is the isotropic Gaussian proposal width (bohr)."""
        self.cfg = cfg
        self.step_size = float(step_size)
        self.spread = float(spread)

    def init(self, params, key, n_walkers: int, walkers=None):
        """Cold start (sampled positions) or reservoir restart."""
        if walkers is not None:
            ens = restart_ensemble(
                walkers, n_walkers,
                lambda r: evaluate_sem(self.cfg, params, r))
        else:
            r = sample_positions(params, key, n_walkers, self.cfg.n_elec,
                                 self.spread)
            ens = evaluate_sem(self.cfg, params, r)
        return SEMState(ens=ens, sweeps=jnp.int32(0))

    def propagate(self, params, state: SEMState, key, pop: Population):
        """One sweep: n_e single-electron trials + energy + drift control."""
        cfg = self.cfg
        ci = cfg.ci
        ens = state.ens
        wkeys = pop.walker_keys(key, ens.r.shape[0])
        # compute boundary: stored (possibly quantized) state -> fp32; at
        # precision='fp32' these are the stored arrays themselves
        minv_up = _to_compute(ens.minv_up, cfg)
        minv_dn = _to_compute(ens.minv_dn, cfg)
        p_up = _to_compute(ens.p_up, cfg)
        p_dn = _to_compute(ens.p_dn, cfg)

        if cfg.method in ('fused', 'fused-kernel'):
            r, minv_up, minv_dn, accepts = _fused_sweeps(
                cfg, params, ens, minv_up, minv_dn, p_up, p_dn, wkeys,
                self.step_size)
        else:
            A_up, A_dn = _mo_blocks(cfg, params)
            if ci is not None:
                carry = (ens.r, minv_up, p_up, ens.rdet_up)
                (r, minv_up, _, rdet_up), acc_up = \
                    _sweep_spin_block(
                        cfg, params, A_up, 0, cfg.n_up, wkeys,
                        self.step_size, carry,
                        ci_args=(ci.holes_up, ci.parts_up, ens.rdet_dn))
            else:
                (r, minv_up), acc_up = _sweep_spin_block(
                    cfg, params, A_up, 0, cfg.n_up, wkeys, self.step_size,
                    (ens.r, minv_up))
            if cfg.n_dn > 0:
                if ci is not None:
                    carry = (r, minv_dn, p_dn, ens.rdet_dn)
                    (r, minv_dn, _, _), acc_dn = \
                        _sweep_spin_block(
                            cfg, params, A_dn, cfg.n_up, cfg.n_dn, wkeys,
                            self.step_size, carry,
                            ci_args=(ci.holes_dn, ci.parts_dn, rdet_up))
                else:
                    (r, minv_dn), acc_dn = _sweep_spin_block(
                        cfg, params, A_dn, cfg.n_up, cfg.n_dn, wkeys,
                        self.step_size, (r, minv_dn))
                accepts = jnp.concatenate([acc_up, acc_dn])
            else:
                accepts = acc_up

        # one full MO tensor pass: the energy needs it, and its D blocks
        # feed the corrector/refresh that bound fp32 drift
        Cw, _ = _mo_tensor_ensemble(cfg, params, r)
        up, dn = _slater_blocks(cfg, Cw)
        sweeps = state.sweeps + 1

        def _fresh(D):
            return slater.refine_inverse(D, jnp.linalg.inv(D), cfg.ns_steps)

        def _refresh(_):
            return (_fresh(up[..., 0]),
                    _fresh(dn[..., 0]) if cfg.n_dn > 0 else minv_dn)

        def _correct(_):
            mu = slater.refine_inverse(up[..., 0], minv_up)
            md = (slater.refine_inverse(dn[..., 0], minv_dn)
                  if cfg.n_dn > 0 else minv_dn)
            return mu, md

        # sign and log|det| come from a batched LU of the sweep's Slater
        # matrices, not from a running sum of the moves' log-ratios: at
        # |log det| ~ 600 (the paper's 158 electrons) one fp32 ulp is 6e-5,
        # and ~100 accepted moves per sweep drift such a sum past the 1e-4
        # contract (DESIGN.md §6).  Only the inverse is maintained: Newton–
        # Schulz corrected every sweep, rebuilt every ``sem_refresh``.
        with jax.named_scope('qmc.slater'):
            sign, logdet = jnp.linalg.slogdet(up[..., 0])
            if cfg.n_dn > 0:
                sd, ld = jnp.linalg.slogdet(dn[..., 0])
                sign, logdet = sign * sd, logdet + ld
            minv_up, minv_dn = jax.lax.cond(
                sweeps % cfg.sem_refresh == 0, _refresh, _correct, None)

        ens_new = _energy_ensemble(cfg, params, r, Cw, minv_up, minv_dn,
                                   sign, logdet)
        out = (pop.mean(ens_new.e_loc), pop.mean(ens_new.e_loc ** 2),
               pop.mean(jnp.mean(accepts)))
        return SEMState(ens=ens_new, sweeps=sweeps % cfg.sem_refresh), out

    def block_stats(self, params, state: SEMState, outs,
                    pop: Population) -> DriverStats:
        """Reduce per-sweep outputs; sparsity/energy split from the final
        configuration (same convention as the all-electron VMC)."""
        e, e2, acc = outs                    # (steps,) global per-sweep means
        ens = state.ens
        _, st = evaluate_ensemble(self.cfg, params, ens.r)
        w = jnp.float32(e.shape[0] * pop.size(ens.r))
        return DriverStats(
            weight=w, e_mean=jnp.mean(e), e2_mean=jnp.mean(e2),
            aux=dict(accept=jnp.mean(acc),
                     ao_fill=pop.mean(st.ao_count.astype(jnp.float32)),
                     e_kin=pop.mean(st.e_kin), e_pot=pop.mean(st.e_pot)))


# for sem-vmc the step size is a per-electron Gaussian proposal width,
# not a drift-diffusion time step
register_method('sem-vmc',
                lambda cfg, tau, e_trial, equil_steps:
                SEMVMCPropagator(cfg, step_size=tau),
                default_tau=0.3)


def _fused_cfg(cfg: WavefunctionConfig) -> WavefunctionConfig:
    """Route the sweep through the fused path, honoring kernel selection.

    ``fused-vmc`` is the same propagator with ``cfg.method`` rewritten:
    'kernel' upgrades to 'fused-kernel' (one Pallas call per spin block),
    anything else to 'fused' (one ``lax.scan``).  The pre-rewrite method
    is recorded in ``mo_method`` so the post-sweep energy pass keeps the
    ORIGINAL MO-product pipeline — a dense config's batched GEMM, a
    kernel config's Pallas product — instead of silently degrading to
    the sparse default (wavefunction._mo_product_method).
    """
    if cfg.method in ('fused', 'fused-kernel'):
        return cfg
    method = 'fused-kernel' if cfg.method == 'kernel' else 'fused'
    return dataclasses.replace(cfg, method=method,
                               mo_method=cfg.mo_method or cfg.method)


register_method('fused-vmc',
                lambda cfg, tau, e_trial, equil_steps:
                SEMVMCPropagator(_fused_cfg(cfg), step_size=tau),
                default_tau=0.3)
