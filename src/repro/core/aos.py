"""Atomic-orbital evaluation: values, gradients, Laplacians + sparsity lists.

Produces the paper's B matrices:
    B1[j, i] = chi_j(r_i)            (values)
    B2..B4   = d chi_j / dx,dy,dz    (gradients)
    B5       = laplacian chi_j       (Laplacians)
stacked as ``B: (n_ao, n_elec, 5)``, plus the per-electron *active AO* index
lists that make B sparse (paper §III: AOs whose spherical part is < EPS are
exact zeros; whole atoms are skipped via the atomic radius).

Everything is analytic; ``tests/test_aos.py`` checks value/grad/lap against a
jax autodiff oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .basis import BasisSet, EPS_AO, MAX_POW


def _monomial_1d(x: jnp.ndarray, n: jnp.ndarray):
    """f(x)=x^n and df, d2f for integer n in [0, MAX_POW].

    x: (..., n_ao) floats, n: (n_ao,) int32 broadcast along leading dims.
    Returns (f, df, d2f), each (..., n_ao); derivative factors are exact for
    n==0/1 (coefficients vanish rather than evaluating negative powers).
    """
    # powers[k] = x^k, k = 0..MAX_POW
    powers = [jnp.ones_like(x)]
    for _ in range(MAX_POW):
        powers.append(powers[-1] * x)
    nf = n.astype(x.dtype)

    def _take(k):  # x^{clip(n+k, 0)}: a select per power, not a gather
        # (on a TPU v5e the gather of this table from the walker-batched
        # MO tensor pass halted the chip)
        kk = jnp.clip(n + k, 0, MAX_POW)
        out = powers[0]
        for p in range(1, MAX_POW + 1):
            out = jnp.where(kk == p, powers[p], out)
        return out

    f = _take(0)
    df = nf * _take(-1)
    d2f = nf * (nf - 1.0) * _take(-2)
    return f, df, d2f


def atom_to_ao(x: jnp.ndarray, ao_atom) -> jnp.ndarray:
    """``x[..., ao_atom]`` for the basis's host ``ao_atom``, without a gather.

    Each run of AOs on one atom is a static slice of ``x`` broadcast to the
    run's length; the TPU then sees slices and one concatenate instead of
    a gather of a walker-batched operand by constant indices (the pattern
    that halted a TPU v5e in the MO tensor pass).  Bitwise equal to the
    gather.  One slice per atom: for per-pass masks, not per-move work.
    """
    ao_atom = np.asarray(ao_atom)
    starts = np.flatnonzero(np.r_[True, ao_atom[1:] != ao_atom[:-1]])
    ends = np.r_[starts[1:], ao_atom.size]
    parts = [jnp.broadcast_to(x[..., int(ao_atom[s]), None],
                              x.shape[:-1] + (int(e - s),))
             for s, e in zip(starts, ends)]
    return jnp.concatenate(parts, axis=-1)


def _ao_geometry(basis: BasisSet, coords: jnp.ndarray, r_elec: jnp.ndarray):
    """Electron-AO displacements without gathering walker-batched arrays.

    Returns ``d`` (..., n_ao, 3) = r - R_atom(ao), its ``r2``, the per-AO
    in-radius mask and the per-atom mask (..., n_atoms).  The per-AO terms
    are computed from the (n_atoms, 3) nuclear table expanded to AOs, with
    the same arithmetic as the per-atom terms, so they are bitwise equal to
    indexing the per-atom arrays by ``ao_atom``.
    """
    ao_atom = np.asarray(basis.ao_atom)
    radius2 = jnp.asarray(basis.atom_radius2, jnp.float32)
    dxyz_at = r_elec[..., None, :] - coords                  # (..., n_at, 3)
    atom_active = jnp.sum(dxyz_at * dxyz_at, axis=-1) < radius2
    d = r_elec[..., None, :] - coords[ao_atom]               # (..., n_ao, 3)
    r2 = jnp.sum(d * d, axis=-1)                             # (..., n_ao)
    active = r2 < jnp.asarray(basis.atom_radius2[ao_atom], jnp.float32)
    return d, r2, active, atom_active


def _basis_consts(basis: BasisSet):
    """Basis constants pinned to (int32, int32, f32, f32, f32).

    ``BasisSet`` holds host numpy (float64) arrays; a bare ``jnp.asarray``
    under ``jax_enable_x64`` promotes them — and every AO intermediate
    downstream, i.e. the whole SEM per-move sweep — to fp64.  Explicit pins
    keep the evaluation pipeline fp32 regardless of the ambient
    default-dtype config (regression:
    ``tests/test_precision.py::test_sweep_jaxpr_has_no_fp64``).
    """
    return (jnp.asarray(basis.ao_atom, jnp.int32),
            jnp.asarray(basis.ao_pow, jnp.int32),
            jnp.asarray(basis.prim_coeff, jnp.float32),
            jnp.asarray(basis.prim_exp, jnp.float32),
            jnp.asarray(basis.atom_radius2, jnp.float32))


def eval_ao_block(basis: BasisSet, coords: jnp.ndarray, r_elec: jnp.ndarray):
    """Evaluate all AOs at electron positions.

    AO evaluation is independent per electron, so ``r_elec`` may carry any
    leading batch shape: a single walker's electrons ``(n_e, 3)``, a whole
    ensemble flattened walker-major ``(W * n_e, 3)`` (one big B for the
    fused ensemble pass), or the unflattened ``(W, n_e, 3)`` batch.  The
    unflattened form keeps the walker axis leading in the outputs — the
    cheapest layout on CPU/TPU (per-walker 2-D transposes instead of one
    large 3-D permutation); callers flatten per consumer (see
    ``wavefunction._mo_tensor_ensemble``).

    Args:
      basis: BasisSet (host numpy arrays; closed over as constants).
      coords: (n_atoms, 3) nuclear positions.
      r_elec: (..., 3) electron positions.

    Returns:
      B: (n_ao, N, 5) float32 for 2-D input, (W, n_ao, n_e, 5) for 3-D input
        — value, ddx, ddy, ddz, laplacian.
      atom_active: (N, n_atoms) / (W, n_e, n_atoms) bool — electron within
        atomic radius.
    """
    if r_elec.ndim == 3:
        # vmap over walkers rather than flattening: identical math, but XLA
        # schedules the batched elementwise pipeline measurably better than
        # the same graph with a single fused W*n_e axis (CPU and TPU).
        return jax.vmap(lambda r: eval_ao_block(basis, coords, r))(r_elec)
    _, ao_pow, prim_c, prim_a, _ = _basis_consts(basis)
    d, r2, active, atom_active = _ao_geometry(basis, coords, r_elec)

    # Radial part and its radial derivatives:
    #   g   = sum_k c_k e^{-a_k r^2}
    #   gp  = dg/d(r^2) = sum_k -a_k c_k e^{-a_k r^2}
    #   gpp = d2g/d(r^2)^2
    expo = jnp.exp(-prim_a[None] * r2[..., None])            # (n_e, n_ao, P)
    g = jnp.sum(prim_c[None] * expo, axis=-1)
    gp = jnp.sum(-prim_a[None] * prim_c[None] * expo, axis=-1)
    gpp = jnp.sum(prim_a[None] ** 2 * prim_c[None] * expo, axis=-1)

    # Angular monomial factors per coordinate.
    fs, dfs, d2fs = [], [], []
    for l in range(3):
        f, df, d2f = _monomial_1d(d[..., l], ao_pow[:, l])
        fs.append(f); dfs.append(df); d2fs.append(d2f)
    poly = fs[0] * fs[1] * fs[2]                              # (n_e, n_ao)

    # chi = poly * g;  d chi/dx = df_x f_y f_z g + poly * 2 x gp
    val = poly * g
    grads = []
    for l in range(3):
        others = fs[(l + 1) % 3] * fs[(l + 2) % 3]
        grads.append(dfs[l] * others * g + poly * 2.0 * d[..., l] * gp)
    # laplacian: sum_l [ d2f_l*others*g + 2 df_l*others*2x gp
    #                    + poly*(2 gp + 4 x^2 gpp) ]
    lap = jnp.zeros_like(val)
    for l in range(3):
        others = fs[(l + 1) % 3] * fs[(l + 2) % 3]
        x = d[..., l]
        lap = lap + (d2fs[l] * others * g
                     + 2.0 * dfs[l] * others * 2.0 * x * gp
                     + poly * (2.0 * gp + 4.0 * x * x * gpp))

    B = jnp.stack([val] + grads + [lap], axis=-1)            # (..., n_ao, 5)
    # screening: exact zeros outside the atomic radius (paper's sparsity)
    B = jnp.where(active[..., None], B, 0.0)
    # (..., n_e, n_ao, 5) -> (..., n_ao, n_e, 5): per-walker 2-D transposes
    return jnp.swapaxes(B, -3, -2), atom_active


def eval_ao_values(basis: BasisSet, coords: jnp.ndarray,
                   r_elec: jnp.ndarray):
    """AO *values only* at a batch of points — the per-move fast path.

    Single-electron-move kinetics (``core.sem``) accept/reject on the
    determinant ratio, which needs just B1 (values) at one proposed position
    per walker; gradients and Laplacians are only assembled once per sweep.
    Skipping the derivative pipeline makes this ~3x cheaper than
    ``eval_ao_block``.

    Args:
      basis: BasisSet (host numpy arrays; closed over as constants).
      coords: (n_atoms, 3) nuclear positions.
      r_elec: (N, 3) evaluation points (one proposed move per walker).

    Returns:
      vals: (n_ao, N) float32 AO values, exact zeros outside atomic radii.
      atom_active: (N, n_atoms) bool — point within atomic radius.
    """
    _, ao_pow, prim_c, prim_a, _ = _basis_consts(basis)
    d, r2, active, atom_active = _ao_geometry(basis, coords, r_elec)
    expo = jnp.exp(-prim_a[None] * r2[..., None])            # (N, n_ao, P)
    g = jnp.sum(prim_c[None] * expo, axis=-1)                # radial part
    poly = jnp.ones_like(g)
    for l in range(3):
        # value component of the monomial table; the derivative factors
        # returned alongside are dead code XLA prunes under jit
        f, _, _ = _monomial_1d(d[..., l], ao_pow[:, l])
        poly = poly * f
    val = jnp.where(active, poly * g, 0.0)
    return val.T, atom_active


# trace-time counter of ao_mask fallback rebuilds in ``active_ao_indices``
# (tests assert the per-sweep pipeline always passes the hoisted mask)
_MASK_FALLBACKS = 0


def mask_fallback_count() -> int:
    """Times ``active_ao_indices`` rebuilt the (n_e, n_ao) mask itself."""
    return _MASK_FALLBACKS


def eval_ao_block_screened(basis: BasisSet, coords: jnp.ndarray,
                           r_elec: jnp.ndarray, idx: jnp.ndarray,
                           active: jnp.ndarray):
    """Screened AO evaluation: only the candidate (electron, AO) pairs.

    The packed-CSR sibling of ``eval_ao_block``: instead of the full
    (n_ao, N, 5) B it evaluates value/gradient/Laplacian at the gathered
    candidate AOs of each electron — O(N * budget) work and memory, the
    linear-scaling pipeline of ``core.screening``.  Per-element arithmetic
    is identical to the dense path, so an active slot's value is bitwise
    equal to the corresponding dense B entry.

    Args:
      basis: BasisSet (host numpy arrays; closed over as constants).
      coords: (n_atoms, 3) nuclear positions.
      r_elec: (N, 3) electron positions (any walker-flattened batch).
      idx: (N, K) candidate AO ids (``screening.active_ao_lists``).
      active: (N, K) bool — inside-cutoff mask; inactive slots zero.

    Returns Bp: (N, K, 5) float32 packed values (zeros at inactive slots).
    """
    ao_atom, ao_pow, prim_c, prim_a, _ = _basis_consts(basis)
    ao_atom, ao_pow = ao_atom[idx], ao_pow[idx]           # (N, K), (N, K, 3)
    prim_c, prim_a = prim_c[idx], prim_a[idx]             # (N, K, P)

    d = r_elec[..., None, :] - coords[ao_atom]            # (N, K, 3)
    r2 = jnp.sum(d * d, axis=-1)                          # (N, K)
    expo = jnp.exp(-prim_a * r2[..., None])               # (N, K, P)
    g = jnp.sum(prim_c * expo, axis=-1)
    gp = jnp.sum(-prim_a * prim_c * expo, axis=-1)
    gpp = jnp.sum(prim_a ** 2 * prim_c * expo, axis=-1)

    fs, dfs, d2fs = [], [], []
    for l in range(3):
        f, df, d2f = _monomial_1d(d[..., l], ao_pow[..., l])
        fs.append(f); dfs.append(df); d2fs.append(d2f)
    poly = fs[0] * fs[1] * fs[2]

    val = poly * g
    grads = []
    for l in range(3):
        others = fs[(l + 1) % 3] * fs[(l + 2) % 3]
        grads.append(dfs[l] * others * g + poly * 2.0 * d[..., l] * gp)
    lap = jnp.zeros_like(val)
    for l in range(3):
        others = fs[(l + 1) % 3] * fs[(l + 2) % 3]
        x = d[..., l]
        lap = lap + (d2fs[l] * others * g
                     + 2.0 * dfs[l] * others * 2.0 * x * gp
                     + poly * (2.0 * gp + 4.0 * x * x * gpp))
    Bp = jnp.stack([val] + grads + [lap], axis=-1)        # (N, K, 5)
    return jnp.where(active[..., None], Bp, 0.0)


def eval_ao_values_screened(basis: BasisSet, coords: jnp.ndarray,
                            r_elec: jnp.ndarray, idx: jnp.ndarray,
                            active: jnp.ndarray):
    """Screened AO *values only* — the single-electron-move fast path.

    ``eval_ao_values`` restricted to each point's candidate list: O(K) per
    proposed move instead of O(n_ao).  Returns vals: (N, K), zeros at
    inactive slots.
    """
    ao_atom, ao_pow, prim_c, prim_a, _ = _basis_consts(basis)
    ao_atom, ao_pow = ao_atom[idx], ao_pow[idx]
    prim_c, prim_a = prim_c[idx], prim_a[idx]
    d = r_elec[..., None, :] - coords[ao_atom]
    r2 = jnp.sum(d * d, axis=-1)
    expo = jnp.exp(-prim_a * r2[..., None])
    g = jnp.sum(prim_c * expo, axis=-1)
    poly = jnp.ones_like(g)
    for l in range(3):
        f, _, _ = _monomial_1d(d[..., l], ao_pow[..., l])
        poly = poly * f
    return jnp.where(active, poly * g, 0.0)


def active_ao_indices(basis: BasisSet, atom_active: jnp.ndarray, k_max: int,
                      ao_mask: jnp.ndarray = None):
    """Per-electron padded active-AO index lists (paper's ``indices`` array).

    Args:
      atom_active: (n_e, n_atoms) bool.
      k_max: pad/truncate length (multiple of 128 for the TPU kernel).  The
        wavefunction builds these lists only where ``0 < k_max < n_ao``:
        at ``k_max >= n_ao`` it runs the dense product instead
        (``wavefunction._product_route``).
      ao_mask: optional precomputed ``atom_active[:, ao_atom]`` (n_e, n_ao)
        — callers that already expanded the atom mask (sparsity stats) pass
        it to skip the second gather.  Every per-sweep caller does; the
        fallback below re-materializes the (n_e, n_ao) product and exists
        only for API compatibility (``mask_fallback_count`` lets tests
        assert the hot path never takes it).

    Returns:
      idx: (n_e, k_max) int32 — active AO indices, ascending, padded with 0.
      valid: (n_e, k_max) bool — padding mask.
      count: (n_e,) int32 — true number of active AOs (may exceed k_max:
        callers assert/monitor overflow; the dense path is exact regardless).
    """
    if ao_mask is None:
        global _MASK_FALLBACKS
        _MASK_FALLBACKS += 1
        ao_mask = atom_to_ao(atom_active, basis.ao_atom)      # (n_e, n_ao)
    mask = ao_mask
    count = jnp.sum(mask.astype(jnp.int32), axis=-1)
    n_e, n_ao = mask.shape
    # Scatter-based stable compaction: active AO j lands at its rank among
    # the electron's active AOs (ascending AO order — maximizes tile density
    # in the Pallas kernel; the paper sorts columns by first active index
    # for cache blocking).  O(n_ao) per electron vs an argsort's
    # O(n_ao log n_ao) — this runs per MC step on the whole ensemble.
    pos = jnp.cumsum(mask.astype(jnp.int32), axis=-1) - 1    # rank if active
    pos = jnp.where(mask & (pos < k_max), pos, k_max)        # else dump slot
    idx = jnp.zeros((n_e, k_max + 1), jnp.int32)
    idx = idx.at[jnp.arange(n_e)[:, None], pos].set(
        jnp.broadcast_to(jnp.arange(n_ao, dtype=jnp.int32), mask.shape),
        mode='drop')
    idx = idx[:, :k_max]
    valid = jnp.arange(k_max)[None, :] < jnp.minimum(count, k_max)[:, None]
    return idx, valid, count


def pack_b(B: jnp.ndarray, idx: jnp.ndarray, valid: jnp.ndarray):
    """Gather B rows into the packed per-electron representation.

    B: (n_ao, n_e, 5) -> Bp: (n_e, k_max, 5) with zeros at padding.
    """
    n_e = B.shape[1]
    Bp = B[idx, jnp.arange(n_e)[:, None], :]                 # (n_e, k, 5)
    return jnp.where(valid[..., None], Bp, 0.0)
