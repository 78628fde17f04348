"""Trial wavefunction Psi_T = e^J * Det_up * Det_dn: assembly + local energy.

The computational pipeline per walker (paper §II.C / §III):

    AOs B1..B5  ->  (sparsify)  ->  C_i = A B_i  ->  Slater inverse  ->
    drift (eq. 14), laplacian (eq. 15)  ->  E_L = -1/2 lap Psi/Psi + V

``method`` selects the product implementation: 'dense' (O(N^3) oracle),
'sparse' (paper's algorithm, gather form), 'kernel' (Pallas tile-sparse).
'sparse' packs the active AOs only where that shortens the contraction,
``0 < k_max < n_ao``; otherwise it runs the dense product
(``_product_route``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import aos, mos, slater
from .basis import BasisSet
from .hamiltonian import potential_energy
from .jastrow import JastrowParams, jastrow_state, jastrow_value


@dataclasses.dataclass(frozen=True)
class WavefunctionConfig:
    """Static (trace-time) configuration."""

    basis: BasisSet
    n_up: int
    n_dn: int
    k_max: int = 0                 # padded active-AO count of the packed
    #                                'sparse' product, which runs only for
    #                                0 < k_max < n_ao; 0 or k_max >= n_ao
    #                                -> the dense product (nothing to pack)
    shared_orbitals: bool = True   # closed-shell: one MO block for both spins
    method: str = 'sparse'         # 'dense' | 'sparse' | 'kernel' |
    #                                'fused' | 'fused-kernel' (the latter
    #                                two select the fused-sweep SEM
    #                                propagator in core/sem.py; the MO
    #                                product pipeline then follows
    #                                ``mo_method``)
    mo_method: str = ''            # MO-product pipeline override for the
    #                                AO->MO tensor passes ('dense' |
    #                                'sparse' | 'kernel').  Empty (the
    #                                default) means: follow ``method``,
    #                                except the fused sweep methods fall
    #                                back to 'sparse'.  ``core.sem``
    #                                records the pre-rewrite method here
    #                                when building a fused config, so a
    #                                dense/kernel energy pass survives the
    #                                propagator rewrite.
    ns_steps: int = 1              # Newton–Schulz refinement of the inverse
    kernel_tiles: tuple = (8, 8, 8)  # (tile_o, tile_k, tile_e) in interpret
    #                                mode; kernels.platform picks the TPU's
    ensemble_eval: bool = True     # VMC/DMC walker batches: one flattened
    #                                AO->MO->Slater pass instead of per-walker
    #                                vmap (DESIGN.md §4)
    kernel_ensemble_tile_cap: int = 0  # interpret-mode tile_e cap for
    #                                ensemble kernel calls; 0 -> 2048 (see
    #                                kernels.sparse_mo.ops.ensemble_tiles)
    sem_refresh: int = 8           # single-electron-move propagator: full
    #                                slater_state recompute every this many
    #                                sweeps; Newton–Schulz corrector between
    #                                refreshes bounds fp32 drift (DESIGN §6)
    screening: object = None       # screening.Screening or None.  When set
    #                                (and not exhaustive), the MO tensor is
    #                                built through the cell-list packed-CSR
    #                                pipeline: per-electron candidate AO
    #                                lists with a static budget, screened
    #                                AO evaluation, and (when the structure
    #                                carries MO reach radii) doubly
    #                                screened A-panel products — the
    #                                paper's linear-scaling path (DESIGN.md
    #                                §11).  ``exhaustive`` structures
    #                                (cutoff = infinity) route back here
    #                                bitwise.  Built ONCE at setup by
    #                                ``screening.build_screening``.
    precision: str = 'fp32'        # storage policy for the maintained SEM
    #                                inverses / CI P-tables: 'fp32' | 'bf16'
    #                                | 'fp16'.  Reduced dtypes store the
    #                                (W, n, n) state low-width; every sweep
    #                                upcasts and accumulates ratios/updates
    #                                in fp32, and the Newton–Schulz
    #                                corrector + periodic refresh bound the
    #                                quantization drift per
    #                                ``slater.drift_tolerance`` (DESIGN.md
    #                                §13).  'fp32' is bitwise-inert: no
    #                                casts are inserted at the default.
    ci: object = None              # multidet.MultiDetWavefunction or None
    #                                (single determinant).  When set, the
    #                                Slater tail of every evaluation runs
    #                                the shared-inverse CI machinery of
    #                                core/multidet.py (DESIGN.md §8);
    #                                params.mo must then carry the full
    #                                orbital set (ci.n_orb rows) and
    #                                shared_orbitals must be True.

    @property
    def n_elec(self) -> int:
        """Total electron count (n_up + n_dn)."""
        return self.n_up + self.n_dn


class WavefunctionParams(NamedTuple):
    """Dynamic parameters (constant during a *block* — the paper's 'A').

    ``ci_coeffs`` is an optional traced override of the static CI
    coefficients baked into ``cfg.ci``: ``None`` (the default, an empty
    pytree leaf) means "use ``cfg.ci.coeffs``" and reproduces the fixed
    trial wavefunction exactly; the wavefunction-optimization subsystem
    (``repro.optimize``) sets it so CI coefficients become differentiable
    and updatable between blocks without retracing (they ride the same
    traced-params argument as the Jastrow parameters).
    """

    coords: jnp.ndarray     # (n_at, 3)
    charges: jnp.ndarray    # (n_at,)
    mo: jnp.ndarray         # (n_rows, n_ao) MO coefficients ('A' matrix)
    jastrow: JastrowParams
    ci_coeffs: jnp.ndarray | None = None   # (n_det,) traced CI override


class PsiState(NamedTuple):
    """Per-walker evaluation summary: value, drift, local energy."""

    sign: jnp.ndarray        # ()
    log_psi: jnp.ndarray     # () log|Psi_T|
    drift: jnp.ndarray       # (n_e, 3) grad log Psi_T
    e_loc: jnp.ndarray       # () local energy
    e_kin: jnp.ndarray       # ()
    e_pot: jnp.ndarray       # ()
    ao_count: jnp.ndarray    # (n_e,) active AOs per electron (sparsity stats)


def _screening_active(cfg: WavefunctionConfig) -> bool:
    """True when the cell-list screened pipeline should be used.

    Exhaustive structures (cutoff = infinity) fall back to the unscreened
    branches so the feature flag at infinite cutoff is bitwise inert.
    """
    return cfg.screening is not None and not cfg.screening.exhaustive


def _mo_product_method(cfg: WavefunctionConfig) -> str:
    """Resolve the MO-product pipeline ('dense' | 'sparse' | 'kernel').

    ``cfg.mo_method`` wins when set.  The fused sweep methods are
    propagator selectors, not product pipelines — without an explicit
    override they use the sparse product (the repo default).
    """
    if cfg.mo_method:
        return cfg.mo_method
    if cfg.method in ('fused', 'fused-kernel'):
        return 'sparse'
    return cfg.method


# trace-time counts of the product route the unscreened, non-kernel MO
# tensor passes took (tests assert which route a configuration engages)
_ROUTE_COUNTS = {'dense': 0, 'packed': 0}


def product_route_counts() -> dict:
    """Traces that took the dense and the packed product route."""
    return dict(_ROUTE_COUNTS)


def _product_route(cfg: WavefunctionConfig) -> str:
    """'packed' or 'dense': the product of an unscreened, non-kernel pass.

    Packing each electron's active AOs into ``k_max`` slots shortens the
    contraction only where ``k_max < n_ao``.  At ``k_max >= n_ao`` nothing
    is ever truncated, and since B is an exact zero outside each atom's
    radius (``aos.eval_ao_block``) the packed C is the dense C up to the
    order of the fp32 additions; the dense product then takes it without
    the index build and the gather.  Reads static shapes only.
    """
    packed = (_mo_product_method(cfg) != 'dense'
              and 0 < cfg.k_max < cfg.basis.n_ao)
    route = 'packed' if packed else 'dense'
    _ROUTE_COUNTS[route] += 1
    return route


def _mo_tensor_screened(cfg: WavefunctionConfig,
                        params: WavefunctionParams, r_elec: jnp.ndarray,
                        chunk: int = 0):
    """Cell-list screened MO tensor: O(N * budget) instead of O(N * n_ao).

    The linear-scaling pipeline (DESIGN.md §11): per-electron candidate AO
    lists from the precomputed cell structure, screened AO evaluation at
    only those pairs, then either the doubly screened product (active MOs
    x active AOs, when the structure carries MO reach radii), the packed
    sparse product, or the ``screened_mo`` Pallas kernel.
    """
    from . import screening as scr_mod
    scr = cfg.screening
    with jax.named_scope('qmc.ao_index'):
        idx, active, count = scr_mod.active_ao_lists(scr, r_elec)
    with jax.named_scope('qmc.ao_eval'):
        Bp = aos.eval_ao_block_screened(cfg.basis, params.coords, r_elec,
                                        idx, active)
    with jax.named_scope('qmc.mo_product'):
        if _mo_product_method(cfg) == 'kernel':
            from repro.kernels.screened_mo.ops import screened_mo_products
            to, tk, te = cfg.kernel_tiles
            C = screened_mo_products(params.mo, Bp, idx, active, tile_o=to,
                                     tile_k=tk, tile_e=te)
        elif scr.mo_cells is not None:
            mo_idx, mo_valid = scr_mod.active_mo_lists(scr, r_elec)
            C = mos.mo_products_screened(params.mo, Bp, idx, mo_idx,
                                         mo_valid, chunk=chunk)
        else:
            C = mos.mo_products_sparse(params.mo, Bp, idx, chunk=chunk)
    return C, count


def _mo_tensor(cfg: WavefunctionConfig, params: WavefunctionParams,
               r_elec: jnp.ndarray):
    """Compute C: (n_rows, N, 5) by the selected method + sparsity stats.

    ``r_elec`` may be one walker's electrons (N = n_e) or an ensemble
    flattened walker-major (N = W * n_e) — every method treats electrons as
    independent columns.  The walker-shaped fast path used by
    ``psi_state_batched`` is ``_mo_tensor_ensemble``.
    """
    if _screening_active(cfg):
        return _mo_tensor_screened(cfg, params, r_elec)
    with jax.named_scope('qmc.ao_eval'):
        B, atom_active = aos.eval_ao_block(cfg.basis, params.coords, r_elec)
        ao_mask = aos.atom_to_ao(atom_active, cfg.basis.ao_atom)
        count = jnp.sum(ao_mask, axis=-1).astype(jnp.int32)
    if _mo_product_method(cfg) == 'kernel':
        from repro.kernels.sparse_mo.ops import sparse_mo_products
        to, tk, te = cfg.kernel_tiles
        with jax.named_scope('qmc.mo_product'):
            return sparse_mo_products(params.mo, B, ao_mask, tile_o=to,
                                      tile_k=tk, tile_e=te), count
    if _product_route(cfg) == 'dense':
        with jax.named_scope('qmc.mo_product'):
            return mos.mo_products_dense(params.mo, B), count
    with jax.named_scope('qmc.ao_index'):
        idx, valid, _ = aos.active_ao_indices(cfg.basis, atom_active,
                                              cfg.k_max, ao_mask=ao_mask)
        Bp = aos.pack_b(B, idx, valid)
    with jax.named_scope('qmc.mo_product'):
        return mos.mo_products_sparse(params.mo, Bp, idx), count


def _mo_tensor_ensemble(cfg: WavefunctionConfig, params: WavefunctionParams,
                        R: jnp.ndarray):
    """Ensemble MO tensor: one fused pass over all walkers.

    R: (W, n_e, 3).  Returns Cw: (W, n_rows, n_e, 5) and count: (W, n_e).

    One AO evaluation covers the whole population (B keeps the walker axis —
    the cheap layout); each product method then flattens exactly the axis it
    profits from:

      * dense  — one batched GEMM against the shared A (no layout change);
        'sparse' takes it too wherever ``k_max >= n_ao`` (``_product_route``);
      * sparse — per-electron gather flattened walker-major, so the scan's
        gathered-A working set stays cache-sized instead of growing by W
        (per-walker vmap multiplies the per-chunk gather by W);
      * kernel — B merged to the electron-major (n_ao, W*n_e*5) 2-D layout,
        and tiles re-tuned (``ensemble_tiles``) because the flattened column
        axis can fill far wider tiles than one walker's n_e ever could.
    """
    W, n_e, _ = R.shape
    if _screening_active(cfg):
        n_rows = params.mo.shape[0]
        C, count = _mo_tensor_screened(
            cfg, params, R.reshape(W * n_e, 3),
            chunk=mos.default_chunk(W * n_e, ensemble=True))
        return (jnp.moveaxis(C.reshape(n_rows, W, n_e, 5), 1, 0),
                count.reshape(W, n_e))
    with jax.named_scope('qmc.ao_eval'):
        Bw, atom_active = aos.eval_ao_block(cfg.basis, params.coords, R)
        ao_mask = aos.atom_to_ao(atom_active, cfg.basis.ao_atom)  # (W,n_e,ao)
        count = jnp.sum(ao_mask, axis=-1).astype(jnp.int32)       # (W, n_e)
    n_rows = params.mo.shape[0]

    if _mo_product_method(cfg) == 'kernel':
        from repro.kernels.sparse_mo.ops import (ensemble_tiles,
                                                 sparse_mo_products)
        to, tk, te = ensemble_tiles(cfg.kernel_tiles, n_rows, W * n_e,
                                    cap_e=cfg.kernel_ensemble_tile_cap)
        with jax.named_scope('qmc.mo_product'):
            B2 = jnp.moveaxis(Bw, 0, 1).reshape(Bw.shape[1], W * n_e, 5)
            C = sparse_mo_products(params.mo, B2,
                                   ao_mask.reshape(W * n_e, -1),
                                   tile_o=to, tile_k=tk, tile_e=te)
            return jnp.moveaxis(C.reshape(n_rows, W, n_e, 5), 1, 0), count
    if _product_route(cfg) == 'dense':
        with jax.named_scope('qmc.mo_product'):
            Cw = jnp.einsum('oa,waec->woec', params.mo, Bw,
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
        return Cw, count
    with jax.named_scope('qmc.ao_index'):
        idx, valid, _ = aos.active_ao_indices(
            cfg.basis, atom_active.reshape(W * n_e, -1), cfg.k_max,
            ao_mask=ao_mask.reshape(W * n_e, -1))
        Bp = jax.vmap(aos.pack_b)(Bw, idx.reshape(W, n_e, -1),
                                  valid.reshape(W, n_e, -1))    # (W,n_e,K,5)
    with jax.named_scope('qmc.mo_product'):
        C = mos.mo_products_sparse(params.mo, Bp.reshape(W * n_e, -1, 5),
                                   idx, chunk=mos.default_chunk(
                                       W * n_e, ensemble=True))
        return jnp.moveaxis(C.reshape(n_rows, W, n_e, 5), 1, 0), count


def _slater_blocks(cfg: WavefunctionConfig, C: jnp.ndarray):
    """Rearrange C rows into the stacked (..., orb, elec, 5) det layout.

    C may carry a leading walker axis: the split only touches the last three
    dims (rows, electrons, components).
    """
    if cfg.shared_orbitals:
        up = C[..., :cfg.n_up, :cfg.n_up, :]
        dn = C[..., :cfg.n_dn, cfg.n_up:, :]
    else:
        up = C[..., :cfg.n_up, :cfg.n_up, :]
        dn = C[..., cfg.n_up:, cfg.n_up:, :]
    return up, dn


def _ci_blocks(cfg: WavefunctionConfig, C: jnp.ndarray):
    """Full per-spin MO tensors (ALL orbital rows) for the CI machinery.

    Multideterminant evaluation needs the virtual-orbital rows alongside
    the occupied reference block, so the split keeps every row of C
    (``cfg.ci.n_orb``) and only divides the electron axis.  Requires
    ``shared_orbitals`` (one MO set addressed by both spins' excitation
    lists).
    """
    if not cfg.shared_orbitals:
        raise NotImplementedError(
            'multideterminant expansions require shared_orbitals=True '
            '(one MO row space for both spins)')
    up = C[..., :cfg.ci.n_orb, :cfg.n_up, :]
    dn = (C[..., :cfg.ci.n_orb, cfg.n_up:, :] if cfg.n_dn > 0 else None)
    return up, dn


def _finish_state(cfg: WavefunctionConfig, params: WavefunctionParams,
                  C: jnp.ndarray, r_elec: jnp.ndarray,
                  count: jnp.ndarray) -> PsiState:
    """Per-walker tail shared by ``psi_state`` and ``psi_state_batched``:
    Slater blocks -> drift/Laplacian ratios -> Jastrow -> local energy.

    C: (n_rows, n_e, 5); r_elec: (n_e, 3).  The batched path vmaps this, so
    the Slater/Jastrow/energy math has a single source of truth.  With
    ``cfg.ci`` set the Slater tail is the shared-inverse CI sum of
    ``core.multidet`` (same output contract, ``grad``/``lap`` become the
    CI-weighted contractions).
    """
    with jax.named_scope('qmc.slater'):
        if cfg.ci is not None:
            from . import multidet
            up_all, dn_all = _ci_blocks(cfg, C)
            sign, logdet, sgrad, slap = multidet.ci_assemble(
                cfg.ci, up_all, dn_all, cfg.ns_steps,
                coeffs=params.ci_coeffs)
        else:
            up, dn = _slater_blocks(cfg, C)
            su, lu, gu, qu, _ = slater._spin_block(up, cfg.ns_steps)
            if cfg.n_dn > 0:
                sd, ld, gd, qd, _ = slater._spin_block(dn, cfg.ns_steps)
                sign = su * sd
                logdet = lu + ld
                sgrad = jnp.concatenate([gu, gd], axis=0)
                slap = jnp.concatenate([qu, qd], axis=0)
            else:
                sign, logdet, sgrad, slap = su, lu, gu, qu

    with jax.named_scope('qmc.jastrow_energy'):
        jas = jastrow_state(params.jastrow, r_elec, params.coords,
                            params.charges, cfg.n_up)
        drift = sgrad + jas.grad
        # lap Psi/Psi = lapD/D + lapJ + |gradJ|^2 + 2 gradJ . gradD/D, per elec
        lap_psi_ratio = (slap + jas.lap
                         + jnp.sum(jas.grad * jas.grad, axis=-1)
                         + 2.0 * jnp.sum(jas.grad * sgrad, axis=-1))
        e_kin = -0.5 * jnp.sum(lap_psi_ratio)
        e_pot = potential_energy(r_elec, params.coords, params.charges)
        return PsiState(sign=sign, log_psi=logdet + jas.value, drift=drift,
                        e_loc=e_kin + e_pot, e_kin=e_kin, e_pot=e_pot,
                        ao_count=count)


def psi_state(cfg: WavefunctionConfig, params: WavefunctionParams,
              r_elec: jnp.ndarray) -> PsiState:
    """Full per-walker evaluation: value, drift, local energy."""
    C, count = _mo_tensor(cfg, params, r_elec)
    return _finish_state(cfg, params, C, r_elec, count)


def log_psi(cfg: WavefunctionConfig, params: WavefunctionParams,
            r_elec: jnp.ndarray):
    """(sign, log|Psi|) only — Metropolis ratios and autodiff oracles."""
    C, _ = _mo_tensor(cfg, params, r_elec)
    jv = jastrow_value(params.jastrow, r_elec, params.coords,
                       params.charges, cfg.n_up)
    if cfg.ci is not None:
        from . import multidet
        up_all, dn_all = _ci_blocks(cfg, C)
        up = multidet.spin_block_ci(up_all, cfg.ci.holes_up,
                                    cfg.ci.parts_up, cfg.ns_steps)
        if dn_all is not None:
            dn = multidet.spin_block_ci(dn_all, cfg.ci.holes_dn,
                                        cfg.ci.parts_dn, cfg.ns_steps)
            r_dn, sd, ld = dn.ratios, dn.sign, dn.logdet
        else:
            r_dn = jnp.ones_like(up.ratios)
            sd, ld = jnp.ones_like(up.sign), jnp.zeros_like(up.logdet)
        coeffs = (cfg.ci.coeffs if params.ci_coeffs is None
                  else params.ci_coeffs)
        S = multidet.ci_sum(coeffs, up.ratios, r_dn)
        sign_S, log_S = multidet.ci_log_sum(S)
        return up.sign * sd * sign_S, up.logdet + ld + log_S + jv
    up, dn = _slater_blocks(cfg, C)
    su, lu = jnp.linalg.slogdet(up[..., 0])
    if cfg.n_dn > 0:
        sd, ld = jnp.linalg.slogdet(dn[..., 0])
    else:
        sd, ld = jnp.ones_like(su), jnp.zeros_like(lu)
    return su * sd, lu + ld + jv


def local_energy_autodiff(cfg: WavefunctionConfig,
                          params: WavefunctionParams,
                          r_elec: jnp.ndarray):
    """Autodiff oracle: E_L from grad/laplacian of log|Psi| (tests only)."""
    flat = r_elec.reshape(-1)

    def _f(x):
        return log_psi(cfg, params, x.reshape(r_elec.shape))[1]

    grad = jax.grad(_f)(flat)
    n = flat.shape[0]
    eye = jnp.eye(n, dtype=flat.dtype)
    hdiag = jax.vmap(
        lambda v: jax.jvp(jax.grad(_f), (flat,), (v,))[1] @ v)(eye)
    lap_log = jnp.sum(hdiag)
    e_kin = -0.5 * (lap_log + jnp.sum(grad * grad))
    return e_kin + potential_energy(r_elec, params.coords, params.charges)


def psi_state_batched(cfg: WavefunctionConfig, params: WavefunctionParams,
                      R: jnp.ndarray) -> PsiState:
    """Ensemble-flattened evaluation of a walker batch R: (W, n_e, 3).

    Semantically identical to ``vmap(psi_state)`` (every field grows a
    leading W axis) but structured as ONE fused pass over the flattened
    ``W * n_e`` electron batch:

      * one AO evaluation instead of W small ones;
      * one MO product whose A-panel loads amortize over the whole
        population and whose electron tiles/chunks actually fill
        (paper §III's load amortization, scaled to the ensemble);
      * one batched Slater solve: the shared per-walker tail
        (``_finish_state``) is vmapped over the precomputed MO tensors, so
        slogdet/inv/Newton–Schulz lower to batched LAPACK/GEMM streams over
        (W, n, n) instead of W unbatched factorizations (the explicit API
        for that batching is ``slater._spin_block_batched``).

    The O(n_e^2) Jastrow and potential terms ride along in the same vmap —
    they are pairwise in shape and a negligible share of the cost.
    """
    Cw, count = _mo_tensor_ensemble(cfg, params, R)   # (W, rows, n_e, 5)
    return jax.vmap(partial(_finish_state, cfg, params))(Cw, R, count)


def make_batched(cfg: WavefunctionConfig):
    """Walker-batch evaluator for R: (W, n_e, 3).

    Ensemble-flattened fused pass by default; set
    ``cfg.ensemble_eval=False`` for the legacy per-walker ``vmap``.
    """
    if cfg.ensemble_eval:
        return partial(psi_state_batched, cfg)
    fn = partial(psi_state, cfg)
    return jax.vmap(fn, in_axes=(None, 0))
