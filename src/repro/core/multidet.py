"""Multideterminant wavefunctions: all determinants from ONE shared inverse.

Represents a CI expansion

    Psi_det = sum_I  c_I  D_I^up  D_I^dn

as a *reference* determinant (I = 0) plus per-determinant excitation lists:
determinant I replaces occupied ("hole") orbitals with virtual ("particle")
orbitals in each spin block.  Following Scemama et al., *"Quantum Monte
Carlo with very large multideterminant wavefunctions"* (PAPERS.md), every
excited determinant's ratio to the reference collapses onto the shared
maintained inverse ``M = D_ref^{-1}`` through one precomputed table

    P = V @ M        (n_orb, n_occ);  V[v, e] = phi_v(r_e), all orbitals

so that   det(D_I) / det(D_ref) = det(T_I),   T_I[a, b] = P[p_a, h_b]

— a k×k determinant of *gathered* table entries (k = excitation degree),
with NO per-determinant factorization.  Gradient and Laplacian ratios of
the CI sum come from the same table via the Woodbury form of each excited
inverse, contracted against the CI weights without materializing any
per-determinant inverse (see ``ci_corrections``; DESIGN.md §8 derives the
four terms).

Padding convention (static shapes): every excitation list is padded to the
expansion's max degree ``k`` with per-slot sentinels — pad slot ``a``
holds the pair (hole = n_occ + a, particle = n_orb + a), one block past
the real index ranges.  All tables are extended with k zero rows/columns
plus an identity corner block (``extend_table``), which makes padded
slots contribute an *exact* block-diagonal identity factor: det,
gradients, and the n_det = 1 reference-only expansion reproduce the
single-determinant pipeline bitwise.

Layout contract: everything is written with leading batch axes (``...``
einsums + trailing-axis gathers), so the same functions serve the
per-walker vmap tail of ``wavefunction._finish_state`` and the
walker-batched maintained-inverse path of ``core.sem``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import slater


class MultiDetWavefunction(NamedTuple):
    """A CI expansion over a shared MO set (the static excitation data).

    ``holes_*``/``parts_*`` are (n_det, k) int32 orbital indices into the
    shared MO row space (``parts`` >= the spin's occupied count for real
    slots); pad slot ``a`` holds the sentinels (n_occ_spin + a,
    n_orb + a).  Index 0 is the reference determinant (all padding).
    Arrays are plain numpy: the expansion is trace-time-static
    configuration, not traced state.
    """

    coeffs: np.ndarray       # (n_det,) f32 CI coefficients, c_0 = reference
    holes_up: np.ndarray     # (n_det, k) i32, pad = n_up
    parts_up: np.ndarray     # (n_det, k) i32, pad = n_orb
    holes_dn: np.ndarray     # (n_det, k) i32, pad = n_dn
    parts_dn: np.ndarray     # (n_det, k) i32, pad = n_orb
    n_orb: int               # rows of the shared MO coefficient matrix

    @property
    def n_det(self) -> int:
        """Number of determinants (including the reference)."""
        return int(self.coeffs.shape[0])

    @property
    def k(self) -> int:
        """Padded excitation rank (max degree over the expansion)."""
        return int(self.holes_up.shape[1])

def from_excitations(coeffs, excitations, n_up: int, n_dn: int,
                     n_orb: int) -> MultiDetWavefunction:
    """Build an expansion from per-determinant (holes, parts) lists.

    ``excitations``: one entry per determinant *after* the reference —
    ``((holes_up, parts_up), (holes_dn, parts_dn))`` index tuples (may be
    empty).  ``coeffs`` includes the reference coefficient first.  Lists
    are validated (holes occupied, particles virtual, no duplicates) and
    padded to the max degree with the sentinel convention.
    """
    coeffs = np.asarray(coeffs, np.float32)
    if coeffs.shape[0] != len(excitations) + 1:
        raise ValueError(f'{coeffs.shape[0]} coefficients for '
                         f'{len(excitations)} excitations + reference')
    k = max([1] + [max(len(up[0]), len(dn[0]))
                   for up, dn in excitations])

    def _pad(idx, base):
        idx = list(idx)
        return idx + [base + a for a in range(len(idx), k)]

    def _check(holes, parts, n_occ, spin):
        if len(holes) != len(parts):
            raise ValueError(f'{spin}: holes/particles length mismatch')
        if len(set(holes)) != len(holes) or len(set(parts)) != len(parts):
            raise ValueError(f'{spin}: duplicate hole/particle index')
        for h in holes:
            if not 0 <= h < n_occ:
                raise ValueError(f'{spin}: hole {h} not occupied '
                                 f'(n_occ={n_occ})')
        for p in parts:
            if not n_occ <= p < n_orb:
                raise ValueError(f'{spin}: particle {p} not virtual '
                                 f'(n_occ={n_occ}, n_orb={n_orb})')

    hu, pu = [_pad([], n_up)], [_pad([], n_orb)]   # det 0: the reference
    hd, pd = [_pad([], n_dn)], [_pad([], n_orb)]
    for (uh, up_), (dh, dp) in excitations:
        _check(uh, up_, n_up, 'up')
        _check(dh, dp, n_dn, 'dn')
        hu.append(_pad(uh, n_up)); pu.append(_pad(up_, n_orb))
        hd.append(_pad(dh, n_dn)); pd.append(_pad(dp, n_orb))
    return MultiDetWavefunction(
        coeffs=coeffs,
        holes_up=np.asarray(hu, np.int32), parts_up=np.asarray(pu, np.int32),
        holes_dn=np.asarray(hd, np.int32), parts_dn=np.asarray(pd, np.int32),
        n_orb=int(n_orb))


def _row_parity(holes, parts, n_occ: int) -> float:
    """Sign connecting the hole-row-replacement determinant to the
    sorted-occupation determinant.

    Internally determinant I places particle ``p_a``'s orbital row at its
    hole's row position; the canonical convention of CI coefficient files
    orders each determinant's occupied orbitals ascending.  The two
    determinants differ by the parity of the permutation that sorts the
    replaced row list (inversion count).
    """
    rows = list(range(n_occ))
    for h, p in zip(holes, parts):
        rows[h] = p
    inversions = sum(1 for i in range(len(rows))
                     for jj in range(i + 1, len(rows))
                     if rows[i] > rows[jj])
    return -1.0 if inversions % 2 else 1.0


def from_det_file(text: str, n_up: int, n_dn: int,
                  n_orb: int) -> MultiDetWavefunction:
    """Parse a simple determinant file into an expansion.

    One determinant per line:  ``coeff  o1 o2 ... | o1 o2 ...`` — the CI
    coefficient followed by the occupied orbital indices of the up block,
    a ``|`` separator, and the occupied indices of the down block.  Blank
    lines and ``#`` comments are skipped.  The FIRST determinant is the
    reference; later lines are stored as hole/particle substitutions
    relative to it (order-insensitive sets).

    Coefficients in the file follow the canonical sorted-occupation sign
    convention; parsing folds the permutation parity between that and the
    internal hole-row-replacement convention into each stored coefficient
    (``_row_parity``), so the represented wavefunction is exactly the
    file's.
    """
    dets = []
    for raw in text.splitlines():
        line = raw.split('#', 1)[0].strip()
        if not line:
            continue
        head, _, tail = line.partition('|')
        fields = head.split()
        coeff = float(fields[0])
        up_list = [int(x) for x in fields[1:]]
        dn_list = [int(x) for x in tail.split()]
        up_occ, dn_occ = frozenset(up_list), frozenset(dn_list)
        # check the RAW field counts: a duplicated index would collapse in
        # the set and silently parse as a different determinant
        if (len(up_list) != n_up or len(dn_list) != n_dn
                or len(up_occ) != n_up or len(dn_occ) != n_dn):
            raise ValueError(f'det line {raw!r}: occupation counts '
                             f'{len(up_list)}/{len(dn_list)} (unique '
                             f'{len(up_occ)}/{len(dn_occ)}) != '
                             f'{n_up}/{n_dn}')
        dets.append((coeff, up_occ, dn_occ))
    if not dets:
        raise ValueError('determinant file holds no determinants')
    _, ref_up, ref_dn = dets[0]
    if ref_up != frozenset(range(n_up)) or ref_dn != frozenset(range(n_dn)):
        raise ValueError('reference determinant must occupy orbitals '
                         '0..n_occ-1 of each spin (the maintained-inverse '
                         'reference)')
    coeffs, excitations = [dets[0][0]], []
    for coeff, up_occ, dn_occ in dets[1:]:
        exc_up = (sorted(ref_up - up_occ), sorted(up_occ - ref_up))
        exc_dn = (sorted(ref_dn - dn_occ), sorted(dn_occ - ref_dn))
        parity = (_row_parity(*exc_up, n_up) * _row_parity(*exc_dn, n_dn))
        coeffs.append(coeff * parity)
        excitations.append((exc_up, exc_dn))
    return from_excitations(coeffs, excitations, n_up, n_dn, n_orb)


# ---------------------------------------------------------------------------
# Shared-inverse tables and determinant ratios
# ---------------------------------------------------------------------------
def reference_table(C_vals: jnp.ndarray, Minv: jnp.ndarray) -> jnp.ndarray:
    """The shared ratio table P = V @ M for one spin block.

    C_vals: (..., n_orb, n_e) orbital VALUES at this spin's electrons
    (occupied rows first); Minv: (..., n_e, n_e) maintained reference
    inverse.  The occupied rows of V @ M equal D @ M = I analytically, so
    they are emitted as an *exact* identity — only the virtual rows pay a
    GEMM — keeping sentinel-padded excitation slots exactly inert.
    Returns (..., n_orb, n_occ).
    """
    n_occ = Minv.shape[-1]
    eye = jnp.broadcast_to(jnp.eye(n_occ, dtype=Minv.dtype),
                           C_vals.shape[:-2] + (n_occ, n_occ))
    if C_vals.shape[-2] == n_occ:
        return eye
    P_virt = jnp.einsum('...ve,...eh->...vh', C_vals[..., n_occ:, :], Minv,
                        precision=jax.lax.Precision.HIGHEST)
    return jnp.concatenate([eye, P_virt], axis=-2)


def extend_table(P: jnp.ndarray, k: int) -> jnp.ndarray:
    """Append k sentinel rows/columns (+ identity corner) to a
    (..., n_orb, n_occ) table so pad slot ``a``'s (n_occ+a, n_orb+a)
    indices land on an exact identity block."""
    batch = P.shape[:-2]
    P = jnp.concatenate(
        [P, jnp.zeros(batch + (k, P.shape[-1]), P.dtype)], axis=-2)
    P = jnp.concatenate(
        [P, jnp.zeros(batch + (P.shape[-2], k), P.dtype)], axis=-1)
    eye = jnp.broadcast_to(jnp.eye(k, dtype=P.dtype), batch + (k, k))
    return P.at[..., -k:, -k:].set(eye)


def _pad_zero_rows(x: jnp.ndarray, axis: int, k: int) -> jnp.ndarray:
    """Append k zero slices along ``axis`` (sentinel index targets)."""
    shape = list(x.shape)
    shape[axis] = k
    return jnp.concatenate([x, jnp.zeros(shape, x.dtype)], axis=axis)


def gather_t_blocks(P_ext: jnp.ndarray, holes, parts) -> jnp.ndarray:
    """Gather the (..., n_det, k, k) SMW blocks T_I[a,b] = P[p_a, h_b]
    from a sentinel-extended table."""
    holes = jnp.asarray(holes); parts = jnp.asarray(parts)
    return P_ext[..., parts[:, :, None], holes[:, None, :]]


def det_ratios(P: jnp.ndarray, holes, parts) -> jnp.ndarray:
    """All determinants' ratios to the reference, from the shared table.

    P: (..., n_orb, n_occ) un-extended table for one spin block.  Returns
    (..., n_det) with ratio 1 for the reference (identity padding).
    """
    holes = jnp.asarray(holes)
    return slater.det_small(
        gather_t_blocks(extend_table(P, holes.shape[-1]), holes, parts))


def ci_sum(coeffs, r_up: jnp.ndarray, r_dn: jnp.ndarray) -> jnp.ndarray:
    """S = sum_I c_I R_I^up R_I^dn — the CI sum relative to the reference
    (Psi_det = D_ref^up D_ref^dn * S)."""
    c = jnp.asarray(coeffs)
    return jnp.einsum('d,...d,...d->...', c, r_up, r_dn)


def ci_log_sum(S: jnp.ndarray):
    """(sign, guarded log|S|) of a CI sum — THE near-node guard.

    Single shared implementation for every consumer of log|Psi_det|
    (``ci_assemble``, ``wavefunction.log_psi``, ``sem._energy_ensemble``):
    |S| is floored at 1e-30 before the log, and an exactly-zero S reports
    sign +1.  Near a node of the full CI sum the local energy is singular
    for ANY trial function; the guard only keeps f32 arithmetic finite.
    """
    safe = jnp.where(jnp.abs(S) > 1e-30, jnp.abs(S), 1e-30)
    return jnp.sign(jnp.where(S == 0, 1.0, S)), jnp.log(safe)


def ci_weights(coeffs, r_up: jnp.ndarray, r_dn: jnp.ndarray):
    """Normalized per-determinant weights w_I = c_I R_I^up R_I^dn / S.

    Returns (w, S).  Near a node of the CI sum (S -> 0) the weights are
    guarded like every other near-node quantity in the f32 pipeline; the
    local energy there is singular for *any* trial wavefunction.
    """
    c = jnp.asarray(coeffs)
    prod = c * r_up * r_dn                       # (..., n_det)
    S = jnp.sum(prod, axis=-1)
    safe = jnp.where(jnp.abs(S) > 1e-30, S, jnp.ones_like(S))
    return prod / safe[..., None], S


# ---------------------------------------------------------------------------
# CI-weighted gradient/Laplacian contractions (Woodbury, no excited inverse)
# ---------------------------------------------------------------------------
def ci_corrections(holes, parts, C_blk: jnp.ndarray, Minv: jnp.ndarray,
                   P: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """CI-weighted correction to the reference grad/lap contractions.

    For one spin block, the weighted derivative contraction over the
    expansion is

        sum_I w_I  g_I[e]  =  g_ref[e]  +  corr[e]

    where g_I[e] = sum_rows dC_I[row, e] M_I[e, row] is electron e's
    grad/lap ratio of excited determinant I, and M_I is its Woodbury
    inverse  M_I = M - Y_I (W_I M - E_I)  with  Y_I = M[:, S_I] T_I^{-1}.
    Expanding and contracting against w gives four gather/einsum terms
    that never materialize M_I (DESIGN.md §8):

        corr = - w·Y·Z  +  w·dW·M_S  -  w·Y·(T−I)·dW

    with Z_I = (P dC)[p_I] − dC[h_I] and dW_I = dC_all[p_I] − dC[h_I].

    Args:
      holes, parts: (n_det, k) sentinel-padded excitation lists.
      C_blk: (..., n_orb, n_e, 5) full MO tensor for this spin block.
      Minv: (..., n_e, n_e) maintained reference inverse.
      P: (..., n_orb, n_occ) un-extended shared table.
      w: (..., n_det) normalized CI weights.

    Returns corr: (..., n_e, 4) — components (grad_x, grad_y, grad_z, lap).
    """
    holes = jnp.asarray(holes); parts = jnp.asarray(parts)
    n_occ = Minv.shape[-1]
    k = holes.shape[-1]
    dC = C_blk[..., :n_occ, :, 1:5]              # (..., n_occ, n_e, 4)

    # shared GEMMs (n_det-independent)
    Q = jnp.einsum('...ph,...hec->...pec', P, dC)   # (..., n_orb, n_e, 4)
    Q_ext = _pad_zero_rows(Q, axis=-3, k=k)
    dC_ext = _pad_zero_rows(dC, axis=-3, k=k)       # holes gather source
    dCall_ext = _pad_zero_rows(C_blk[..., 1:5], axis=-3, k=k)  # particles
    M_ext = _pad_zero_rows(Minv, axis=-1, k=k)      # sentinel hole columns

    # per-determinant gathers (static index arrays)
    dCh = dC_ext[..., holes, :, :]                  # (..., n_det, k, n_e, 4)
    dW = dCall_ext[..., parts, :, :] - dCh
    Z = Q_ext[..., parts, :, :] - dCh
    # M_ext[..., :, holes]: (..., n_e, n_det, k) -> (..., n_det, n_e, k)
    Mh = jnp.swapaxes(M_ext[..., :, holes], -3, -2)

    T = gather_t_blocks(extend_table(P, k), holes, parts)  # (...,n_det,k,k)
    Tinv = slater.inv_small(T)
    TmI = T - jnp.eye(k, dtype=T.dtype)
    Y = jnp.einsum('...dek,...dkl->...del', Mh, Tinv)

    term2 = jnp.einsum('...d,...dek,...dkec->...ec', w, Y, Z)
    term3 = jnp.einsum('...d,...dkec,...dek->...ec', w, dW, Mh)
    term4 = jnp.einsum('...d,...deb,...dba,...daec->...ec', w, Y, TmI, dW)
    return -term2 + term3 - term4


class CISpinBlock(NamedTuple):
    """One spin block's shared-inverse summary (reference + table + ratios)."""

    sign: jnp.ndarray       # (...,) reference determinant sign
    logdet: jnp.ndarray     # (...,) reference log|det|
    grad: jnp.ndarray       # (..., n_e, 3) reference grad contraction
    lap: jnp.ndarray        # (..., n_e) reference lap contraction
    minv: jnp.ndarray       # (..., n_e, n_e) maintained inverse
    table: jnp.ndarray      # (..., n_orb, n_occ) P = V @ M
    ratios: jnp.ndarray     # (..., n_det) det(D_I)/det(D_ref)


def spin_block_ci(C_blk: jnp.ndarray, holes, parts,
                  ns_steps: int = 1) -> CISpinBlock:
    """Factorize one spin block ONCE and derive every determinant from it.

    C_blk: (n_orb, n_e, 5) full MO tensor (all orbital rows) for one spin
    block of one walker (vmap for ensembles).  One slogdet + inv of the
    n_e×n_e reference, one GEMM for the table — n_det-independent.
    """
    n_e = C_blk.shape[-2]
    sign, logdet, grad, lap, M = slater._spin_block(
        C_blk[..., :n_e, :, :], ns_steps)
    P = reference_table(C_blk[..., 0], M)
    return CISpinBlock(sign=sign, logdet=logdet, grad=grad, lap=lap,
                       minv=M, table=P, ratios=det_ratios(P, holes, parts))


def ci_assemble(mdw: MultiDetWavefunction, C_up: jnp.ndarray,
                C_dn: jnp.ndarray | None, ns_steps: int = 1,
                coeffs: jnp.ndarray | None = None):
    """Full multideterminant Slater summary for one walker (vmap-ready).

    C_up/C_dn: (n_orb, n_e_spin, 5) full MO tensors per spin block
    (C_dn None when n_dn = 0).  Returns (sign, logdet, grad, lap) of
    Psi_det = sum_I c_I D_I^up D_I^dn, where ``logdet`` absorbs log|S| and
    ``sign`` the sign of S, so downstream Jastrow/energy assembly is
    identical to the single-determinant path.  ``coeffs`` optionally
    overrides ``mdw.coeffs`` with a *traced* coefficient vector (the
    wavefunction optimizer updates CI coefficients between blocks).
    """
    c = mdw.coeffs if coeffs is None else coeffs
    up = spin_block_ci(C_up, mdw.holes_up, mdw.parts_up, ns_steps)
    dn = (spin_block_ci(C_dn, mdw.holes_dn, mdw.parts_dn, ns_steps)
          if C_dn is not None else None)
    r_dn = dn.ratios if dn is not None else jnp.ones_like(up.ratios)
    w, S = ci_weights(c, up.ratios, r_dn)

    cu = ci_corrections(mdw.holes_up, mdw.parts_up, C_up, up.minv,
                        up.table, w)
    gu = up.grad + cu[..., :3]
    qu = up.lap + cu[..., 3]
    if dn is not None:
        cd = ci_corrections(mdw.holes_dn, mdw.parts_dn, C_dn, dn.minv,
                            dn.table, w)
        gd = dn.grad + cd[..., :3]
        qd = dn.lap + cd[..., 3]
        grad = jnp.concatenate([gu, gd], axis=-2)
        lap = jnp.concatenate([qu, qd], axis=-1)
        sign_ref = up.sign * dn.sign
        logdet_ref = up.logdet + dn.logdet
    else:
        grad, lap = gu, qu
        sign_ref, logdet_ref = up.sign, up.logdet

    sign_S, log_S = ci_log_sum(S)
    return sign_ref * sign_S, logdet_ref + log_S, grad, lap
