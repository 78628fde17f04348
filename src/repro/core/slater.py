"""Slater-determinant part: inverse, log|det|, drift and Laplacian ratios.

Given the MO product tensor ``C: (n_orb_tot, n_elec, 5)`` (values + 3 grads +
laplacian, from ``mos.py``) with the first ``n_up`` rows/electrons forming the
spin-up block and the rest spin-down (eq. 11), computes per-electron

    grad_i log Det   (eq. 14)   and   (lap_i Det)/Det   (eq. 15)

via the inverse Slater matrix (paper: O(N^3) inversion, DP; here f32 + one
Newton–Schulz refinement step — see DESIGN.md §3 on the fp64->fp32 move).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class SlaterState(NamedTuple):
    """Both spin determinants' value/derivative summary for one walker."""

    sign: jnp.ndarray      # () product of both spin signs
    logdet: jnp.ndarray    # () sum of log|det| over spins
    grad: jnp.ndarray      # (n_elec, 3) per-electron grad log Det
    lap_ratio: jnp.ndarray  # (n_elec,) per-electron (lap Det)/Det


def refine_inverse(D: jnp.ndarray, X: jnp.ndarray, steps: int = 1):
    """Newton–Schulz: X <- X (2I - D X); quadratic convergence.

    Both products run at full fp32 (the TPU's default for fp32 operands is
    one bf16 pass, far short of the 1e-4 drift contract of DESIGN.md §6).
    """
    hi = jax.lax.Precision.HIGHEST
    eye2 = 2.0 * jnp.eye(D.shape[-1], dtype=D.dtype)
    for _ in range(steps):
        X = jnp.matmul(X, eye2 - jnp.matmul(D, X, precision=hi),
                       precision=hi)
    return X


def ratios_from_inverse(C_blk: jnp.ndarray, Minv: jnp.ndarray):
    """Drift and Laplacian ratios (eqs. 14/15) from a maintained inverse.

    The factorization-free half of ``_spin_block``: single-electron-move
    propagators keep ``Minv`` current via Sherman–Morrison updates
    (``det_ratio_one_electron``) and only need these contractions to
    assemble the local energy — no O(n^3) inversion per step.

    C_blk: (..., orb, elec, 5); Minv: (..., elec, orb) (leading batch axes
    broadcast).  Returns grad (..., elec, 3) and lap (..., elec).
    """
    grad = jnp.einsum('...iej,...ei->...ej', C_blk[..., 1:4], Minv)
    lap = jnp.einsum('...ie,...ei->...e', C_blk[..., 4], Minv)
    return grad, lap


def _spin_block(C_blk: jnp.ndarray, ns_steps: int):
    """C_blk: (n, n, 5) one-spin block (orbital, electron, component)."""
    D = C_blk[..., 0]                                    # (orb, elec)
    sign, logdet = jnp.linalg.slogdet(D)
    M = jnp.linalg.inv(D)                                # (elec, orb)
    if ns_steps:
        M = refine_inverse(D, M, ns_steps)
    grad, lap = ratios_from_inverse(C_blk, M)
    return sign, logdet, grad, lap, M


def _spin_block_batched(C_blk: jnp.ndarray, ns_steps: int):
    """Ensemble variant of ``_spin_block``: one batched LAPACK/Newton–Schulz
    pass over the whole walker population instead of W tiny factorizations.

    C_blk: (W, n, n, 5) — (walker, orbital, electron, component).
    Returns sign (W,), logdet (W,), grad (W, n, 3), lap (W, n), M (W, n, n).

    Implemented as vmap of ``_spin_block``: slogdet/inv/matmul lower to the
    identical batched LAPACK/GEMM primitives, and the Slater math keeps a
    single source of truth.  Note the production ensemble path
    (``wavefunction.psi_state_batched``) gets the same batched lowering by
    vmapping its whole per-walker tail — this function is the standalone
    batched API, not a hook in that pipeline.
    """
    return jax.vmap(lambda C: _spin_block(C, ns_steps))(C_blk)


def slater_state(C: jnp.ndarray, n_up: int, ns_steps: int = 1) -> SlaterState:
    """Assemble both spin determinants. C: (n_orb_tot, n_elec, 5)."""
    n_elec = C.shape[1]
    n_dn = n_elec - n_up
    su, lu, gu, qu, _ = _spin_block(C[:n_up, :n_up, :], ns_steps)
    if n_dn > 0:
        sd, ld, gd, qd, _ = _spin_block(C[n_up:, n_up:, :], ns_steps)
    else:
        sd = jnp.ones_like(su); ld = jnp.zeros_like(lu)
        gd = jnp.zeros((0, 3), C.dtype); qd = jnp.zeros((0,), C.dtype)
    return SlaterState(
        sign=su * sd,
        logdet=lu + ld,
        grad=jnp.concatenate([gu, gd], axis=0),
        lap_ratio=jnp.concatenate([qu, qd], axis=0),
    )


def det_ratio_one_electron(Minv: jnp.ndarray, phi_new: jnp.ndarray, j: int):
    """Sherman–Morrison determinant ratio for moving electron j.

    Minv: (elec, orb) inverse Slater; phi_new: (orb,) new MO values at r_j'.
    Returns (ratio, updated Minv).  Beyond-paper fast path for
    single-electron moves (the paper recomputes; we keep both).  The rank-k
    generalization (k electrons at once, or the hole/particle column
    substitutions of a CI expansion) is ``det_ratio_rank_k``.
    """
    ratio = Minv[j] @ phi_new
    u = Minv @ phi_new                       # (elec,)
    row = Minv[j] / ratio                    # (orb,)
    Minv_new = Minv - jnp.outer(u, row)
    Minv_new = Minv_new.at[j].set(row)
    return ratio, Minv_new


def det_small(T: jnp.ndarray) -> jnp.ndarray:
    """Determinant of small (..., k, k) blocks, batched.

    Explicit cofactor formulas for k <= 3 (cheap, autodiff-friendly, and
    exact on identity padding blocks); ``jnp.linalg.det`` beyond.  The k×k
    blocks of the multideterminant Sherman–Morrison–Woodbury machinery are
    k = excitation degree (1–2 for CIS/CISD-style expansions), so the
    explicit path is the hot one.
    """
    k = T.shape[-1]
    if k == 0:
        return jnp.ones(T.shape[:-2], T.dtype)
    if k == 1:
        return T[..., 0, 0]
    if k == 2:
        return T[..., 0, 0] * T[..., 1, 1] - T[..., 0, 1] * T[..., 1, 0]
    if k == 3:
        return (T[..., 0, 0] * (T[..., 1, 1] * T[..., 2, 2]
                                - T[..., 1, 2] * T[..., 2, 1])
                - T[..., 0, 1] * (T[..., 1, 0] * T[..., 2, 2]
                                  - T[..., 1, 2] * T[..., 2, 0])
                + T[..., 0, 2] * (T[..., 1, 0] * T[..., 2, 1]
                                  - T[..., 1, 1] * T[..., 2, 0]))
    return jnp.linalg.det(T)


def inv_small(T: jnp.ndarray, det: jnp.ndarray | None = None,
              eps: float = 1e-20) -> jnp.ndarray:
    """Inverse of small (..., k, k) blocks via the adjugate, batched.

    ``det`` may be passed in (reuse from ``det_small``); near-singular
    blocks are guarded by ``eps`` — callers weight the result by the very
    determinant that vanishes (CI weights w_I ∝ det T_I), so the guarded
    1/det never amplifies a term that survives the product.
    """
    k = T.shape[-1]
    if det is None:
        det = det_small(T)
    safe = jnp.where(jnp.abs(det) > eps, det, jnp.ones_like(det))
    if k == 1:
        return (1.0 / safe)[..., None, None] * jnp.ones_like(T)
    if k == 2:
        adj = jnp.stack([
            jnp.stack([T[..., 1, 1], -T[..., 0, 1]], axis=-1),
            jnp.stack([-T[..., 1, 0], T[..., 0, 0]], axis=-1),
        ], axis=-2)
        return adj / safe[..., None, None]
    return jnp.linalg.inv(T)


def det_ratio_rank_k(Minv: jnp.ndarray, Phi_new: jnp.ndarray,
                     js: jnp.ndarray):
    """Sherman–Morrison–Woodbury ratio for replacing k Slater columns.

    The rank-k generalization of ``det_ratio_one_electron``: electrons
    ``js`` (k indices) simultaneously get new orbital-value columns
    ``Phi_new`` (k, orb).  With ``M = D^{-1}`` maintained,

        det(D') / det(D) = det(T),   T[a, b] = M[js[a]] @ Phi_new[b]

    and the updated inverse is the Woodbury correction

        M' = M - (M @ Phi_new^T - I[:, js]) T^{-1} M[js, :].

    Returns (ratio, updated Minv).  Cost O(k n^2) against the O(n^3)
    refactorization — the same collapse the multideterminant expansion
    exploits per excited determinant (``core.multidet``).
    """
    n = Minv.shape[0]
    Mj = Minv[js, :]                          # (k, orb)
    T = Mj @ Phi_new.T                        # T[a,b] = M[js[a]] . phi_b
    ratio = det_small(T)
    U = Minv @ Phi_new.T                      # (elec, k): columns M phi_b
    E = jnp.zeros((n, js.shape[0]), Minv.dtype).at[js, jnp.arange(
        js.shape[0])].set(1.0)                # unit columns e_{j_a}
    Minv_new = Minv - (U - E) @ (inv_small(T, ratio) @ Mj)
    return ratio, Minv_new


def state_bytes(n_up: int, n_dn: int, n_walkers: int = 1,
                bytes_per: int = 4) -> int:
    """Bytes of the maintained per-walker Slater state (paper idea ii.).

    The single-electron-move pipeline keeps one inverse Slater matrix per
    spin block plus the running sign/log-determinant scalars per walker —
    the irreducible O(n^2) footprint the screened pipeline's memory budget
    (``screening.memory_budget``, Table XIII) reports alongside the B/C
    working set.  ``bytes_per`` is the storage width of the maintained
    inverses — ``precision_bytes(cfg.precision)`` for the mixed-precision
    policy (sign/logdet scalars stay fp32 but are counted at ``bytes_per``
    too; the 4-scalar tail is noise next to the n^2 blocks).
    """
    return n_walkers * bytes_per * (n_up * n_up + n_dn * n_dn + 4)


# --- mixed-precision storage policy (DESIGN.md §13) -----------------------
# The maintained (W, n, n) inverses and CI P-tables may be STORED in a
# reduced dtype; every sweep upcasts to fp32, accumulates ratios/updates in
# fp32, and quantizes back at the storage boundary.  Scalars (positions,
# sign, logdet, energies) always stay fp32.
PRECISIONS = ('fp32', 'bf16', 'fp16')
_STORAGE_DTYPES = {'fp32': jnp.float32, 'bf16': jnp.bfloat16,
                   'fp16': jnp.float16}
_PRECISION_BYTES = {'fp32': 4, 'bf16': 2, 'fp16': 2}
# Per-dtype §6/§13 drift contract vs a fresh fp64 recompute between
# refreshes: (relative Minv error, absolute logdet error).  fp32 keeps the
# original §6 1e-4 bound; the reduced dtypes are bounded by the storage
# quantization step (bf16: 8-bit mantissa ~ 4e-3, fp16: 11-bit ~ 5e-4)
# times a random-walk accumulation factor over the <= sem_refresh * n_e
# moves between full refreshes — tests/test_precision.py pins these.
_DRIFT_TOLERANCE = {'fp32': (1e-4, 1e-4), 'bf16': (4e-2, 2e-1),
                    'fp16': (5e-3, 2.5e-2)}


def storage_dtype(precision: str):
    """Storage dtype of the maintained inverses / P-tables for a policy."""
    return _STORAGE_DTYPES[precision]


def precision_bytes(precision: str) -> int:
    """Bytes per element of the stored ensemble state for a policy."""
    return _PRECISION_BYTES[precision]


def drift_tolerance(precision: str) -> tuple[float, float]:
    """(relative Minv, absolute logdet) drift bound vs fp64 recompute."""
    return _DRIFT_TOLERANCE[precision]
