"""Distance-based AO/MO screening via O(n) cell lists (paper §II-§III).

The paper's headline idea i.) is that Gaussian AOs are local: an electron
only sees the AOs of nuclei within a finite cutoff radius, so the per-
electron active-AO count is *constant* in system size and the AO->MO->Slater
pipeline scales sub-quadratically.  This module turns that into an exact,
precomputed data structure:

* **Cell list** — a uniform grid over the nuclei with cell edge ``h >= max
  cutoff radius``.  Each cell stores the padded, ascending AO list of its
  27-cell neighborhood, built ONCE at wavefunction setup (host numpy).  An
  electron maps to a cell in O(1); its candidate list provably contains
  every AO within the cutoff (electrons outside the grid clip to the
  boundary cell, which is exact precisely because ``h`` >= every radius).
* **Padded CSR with a static budget** — the per-electron candidate list is
  a fixed-width row of AO indices (`budget` = the max neighborhood
  population over cells, rounded up).  Overflow is impossible by
  construction; jit shapes stay static.
* **Per-AO cutoffs** — candidates are distance-tested against
  ``min(ao_cutoff_radii(basis, eps), atom_radius)`` per AO.  ``eps == 0``
  keeps only the exact ``atom_radius2`` zero structure of the dense path
  (zero screening error, sub-quadratic cost); ``eps > 0`` additionally
  drops AOs whose radial part is below ``eps`` (error bounded in DESIGN.md
  §11).
* **MO support screening** — each MO row of A has finite support (the
  paper thresholds |a_ij| < 1e-5 to exact zeros).  From the support atoms
  we derive a center + reach radius per MO; electrons beyond the reach see
  an *exactly zero* C row (every contributing B element is zero in the
  dense path too), so MO screening introduces NO additional error.  A
  second cell list over MO centers serves per-electron active-MO lists;
  it auto-disables when the MOs are delocalized (budget ~ n_rows).

``build_screening`` increments a module-level construction counter so
tests can assert the structure is built once at setup and never inside the
per-sweep jit path (ISSUE 8 satellite: the old ``active_ao_indices``
fallback re-materialized an (n_e, n_ao) mask per call).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .basis import BasisSet, ao_cutoff_radii

# construction counter: tests assert one-time setup (no rebuilds per sweep)
_BUILD_COUNT = 0


def build_count() -> int:
    """Number of ``build_screening`` calls in this process (test hook)."""
    return _BUILD_COUNT


@dataclasses.dataclass(frozen=True)
class CellList:
    """Uniform grid with padded 27-neighborhood member lists.

    ``members[c]`` holds the ascending, zero-padded ids of every site whose
    own cell is within one cell of ``c`` along each axis; ``valid`` marks
    real entries.  ``h >= max site radius`` makes the clipped query exact.
    """

    origin: np.ndarray        # (3,) grid origin (min site corner)
    h: float                  # cell edge (bohr), >= max cutoff radius
    dims: tuple               # (nx, ny, nz) cell counts
    members: np.ndarray       # (n_cells, budget) int32, padded with 0
    valid: np.ndarray         # (n_cells, budget) bool
    budget: int               # padded row width (static CSR budget)


def _build_cell_list(points: np.ndarray, h: float,
                     pad_multiple: int = 8) -> CellList:
    """Cell list over ``points`` with edge ``h`` (host-side, build once)."""
    points = np.asarray(points, np.float64)
    origin = points.min(axis=0)
    h = float(max(h, 1e-6))
    dims = np.maximum(
        np.floor((points.max(axis=0) - origin) / h).astype(np.int64) + 1, 1)
    cell = np.clip(np.floor((points - origin) / h).astype(np.int64), 0,
                   dims - 1)
    nx, ny, nz = (int(d) for d in dims)
    cid = (cell[:, 0] * ny + cell[:, 1]) * nz + cell[:, 2]
    per_cell: dict[int, list[int]] = {}
    for i, c in enumerate(cid):
        per_cell.setdefault(int(c), []).append(i)
    n_cells = nx * ny * nz
    nbrs: list[np.ndarray] = []
    for cx in range(nx):
        for cy in range(ny):
            for cz in range(nz):
                got: list[int] = []
                for dx in (-1, 0, 1):
                    if not 0 <= cx + dx < nx:
                        continue
                    for dy in (-1, 0, 1):
                        if not 0 <= cy + dy < ny:
                            continue
                        for dz in (-1, 0, 1):
                            if not 0 <= cz + dz < nz:
                                continue
                            c = ((cx + dx) * ny + cy + dy) * nz + cz + dz
                            got += per_cell.get(c, [])
                nbrs.append(np.sort(np.asarray(got, np.int64)))
    budget = max(1, max(len(m) for m in nbrs))
    budget += (-budget) % pad_multiple
    members = np.zeros((n_cells, budget), np.int32)
    valid = np.zeros((n_cells, budget), bool)
    for c, m in enumerate(nbrs):
        members[c, :len(m)] = m
        valid[c, :len(m)] = True
    return CellList(origin=origin, h=h, dims=(nx, ny, nz), members=members,
                    valid=valid, budget=budget)


def _cell_ids(cl: CellList, r: jnp.ndarray) -> jnp.ndarray:
    """Map points ``r: (N, 3)`` to (clipped) cell ids — trace-time, O(N)."""
    nx, ny, nz = cl.dims
    c = jnp.floor((r - jnp.asarray(cl.origin, r.dtype)) / cl.h)
    c = jnp.clip(c.astype(jnp.int32), 0,
                 jnp.asarray([nx - 1, ny - 1, nz - 1], jnp.int32))
    return (c[..., 0] * ny + c[..., 1]) * nz + c[..., 2]


@dataclasses.dataclass(frozen=True)
class Screening:
    """Precomputed screening structure, built ONCE at wavefunction setup.

    All arrays are host numpy; they close over jit traces as constants
    (the same convention as ``BasisSet``).  ``exhaustive=True`` is the
    cutoff = infinity degenerate: the wavefunction code routes back to the
    unscreened pipeline, bitwise identical to screening off.
    """

    eps: float                 # AO tolerance (0: exact zero structure only)
    exhaustive: bool           # True -> no cutoff, use the dense pipeline
    ao_cells: CellList | None  # atom-grid cell list with AO member rows
    ao_radius2: np.ndarray | None   # (n_ao,) effective squared cutoffs
    ao_atom: np.ndarray | None      # (n_ao,) owning nucleus (basis copy)
    coords: np.ndarray | None       # (n_atoms, 3) nuclei (build geometry)
    mo_cells: CellList | None  # MO-center cell list (None: MO screen off)
    mo_center: np.ndarray | None    # (n_rows, 3) support centroids
    mo_reach2: np.ndarray | None    # (n_rows,) squared reach radii
    n_rows: int                # MO rows the structure was built for

    @property
    def ao_budget(self) -> int:
        """Static per-electron candidate-AO width (padded CSR row)."""
        return 0 if self.ao_cells is None else self.ao_cells.budget

    @property
    def mo_budget(self) -> int:
        """Static per-electron candidate-MO width (0: MO screening off)."""
        return 0 if self.mo_cells is None else self.mo_cells.budget


def build_screening(basis: BasisSet, coords, mo, eps: float = 0.0,
                    mo_screen: str | bool = 'auto') -> Screening:
    """Build the cell-list screening structure (host-side, one-time).

    Args:
      basis: the BasisSet (per-AO cutoffs derive from its primitives).
      coords: (n_atoms, 3) nuclear positions.
      mo: (n_rows, n_ao) MO coefficient matrix A — its exact-zero support
        defines the MO reach radii.
      eps: AO screening tolerance.  ``eps < 0`` -> exhaustive (cutoff
        infinity, routes to the dense pipeline bitwise); ``eps == 0`` ->
        drop only the dense path's exact zeros (``atom_radius2``);
        ``eps > 0`` -> per-AO radial cutoffs at that tolerance.
      mo_screen: True / False / 'auto' (disable when the candidate budget
        exceeds 3/4 of the rows — delocalized MOs, compact systems).

    Returns a frozen ``Screening``; attach it to
    ``WavefunctionConfig.screening``.
    """
    global _BUILD_COUNT
    _BUILD_COUNT += 1
    coords = np.asarray(coords, np.float64)
    A = np.asarray(mo)
    n_rows = int(A.shape[0])
    if eps < 0:
        return Screening(eps=float(eps), exhaustive=True, ao_cells=None,
                         ao_radius2=None, ao_atom=None, coords=None,
                         mo_cells=None, mo_center=None, mo_reach2=None,
                         n_rows=n_rows)

    ao_atom = np.asarray(basis.ao_atom, np.int64)
    atom_r = np.sqrt(np.asarray(basis.atom_radius2, np.float64))
    # effective per-AO radius: the tolerance cutoff, never beyond the atom
    # radius (the dense path zeroes there anyway -> screened subset dense)
    r_ao = np.minimum(ao_cutoff_radii(basis, eps), atom_r[ao_atom])
    h = float(r_ao.max())

    # atom-grid cell list, member rows expanded from atoms to their AOs
    atom_cl = _build_cell_list(coords, h)
    ao_of_atom: dict[int, list[int]] = {}
    for j, a in enumerate(ao_atom):
        ao_of_atom.setdefault(int(a), []).append(j)
    rows = []
    for c in range(atom_cl.members.shape[0]):
        atoms = atom_cl.members[c][atom_cl.valid[c]]
        aos = np.sort(np.concatenate(
            [np.asarray(ao_of_atom[int(a)], np.int64) for a in atoms]
            or [np.empty((0,), np.int64)]))
        rows.append(aos)
    budget = max(1, max(len(r) for r in rows))
    budget += (-budget) % 8
    members = np.zeros((len(rows), budget), np.int32)
    valid = np.zeros((len(rows), budget), bool)
    for c, m in enumerate(rows):
        members[c, :len(m)] = m
        valid[c, :len(m)] = True
    ao_cells = CellList(origin=atom_cl.origin, h=atom_cl.h,
                        dims=atom_cl.dims, members=members, valid=valid,
                        budget=budget)

    # MO support screening: center + reach from the exact-zero structure of
    # A.  Reach_m = max over support atoms of (dist(center, atom) + the
    # atom's largest AO cutoff) — beyond it every term A[m,j] * B[j,e] is
    # an exact zero of the DENSE path, so screening C rows is error-free.
    mo_cells = mo_center = mo_reach2 = None
    if mo_screen is True or mo_screen == 'auto':
        atom_r_eff = np.zeros_like(atom_r)
        np.maximum.at(atom_r_eff, ao_atom, r_ao)
        centers = np.zeros((n_rows, 3))
        reach = np.zeros((n_rows,))
        for m in range(n_rows):
            sup = np.unique(ao_atom[np.abs(A[m]) > 0])
            if len(sup) == 0:
                continue
            centers[m] = coords[sup].mean(axis=0)
            d = np.linalg.norm(coords[sup] - centers[m], axis=1)
            reach[m] = float((d + atom_r_eff[sup]).max())
        cl = _build_cell_list(centers, float(reach.max()))
        if mo_screen is True or cl.budget <= 0.75 * n_rows:
            mo_cells, mo_center = cl, centers
            mo_reach2 = (reach * reach)

    return Screening(eps=float(eps), exhaustive=False, ao_cells=ao_cells,
                     ao_radius2=(r_ao * r_ao), ao_atom=ao_atom.astype(
                         np.int32),
                     coords=coords, mo_cells=mo_cells, mo_center=mo_center,
                     mo_reach2=mo_reach2, n_rows=n_rows)


def active_ao_lists(scr: Screening, r: jnp.ndarray):
    """Per-point padded-CSR active-AO lists from the cell structure.

    Args:
      scr: a non-exhaustive Screening.
      r: (N, 3) electron positions (any walker-flattened batch).

    Returns:
      idx:    (N, budget) int32 candidate AO ids (ascending, padded 0).
      active: (N, budget) bool — candidate is within its AO cutoff.
      count:  (N,) int32 active count (diagnostics; <= budget always).
    """
    cl = scr.ao_cells
    cid = _cell_ids(cl, r)
    idx = jnp.asarray(cl.members)[cid]                    # (N, budget)
    cand = jnp.asarray(cl.valid)[cid]
    atom = jnp.asarray(scr.ao_atom)[idx]                  # (N, budget)
    d = r[..., None, :] - jnp.asarray(scr.coords, r.dtype)[atom]
    r2 = jnp.sum(d * d, axis=-1)
    active = cand & (r2 < jnp.asarray(scr.ao_radius2, r.dtype)[idx])
    return idx, active, jnp.sum(active.astype(jnp.int32), axis=-1)


def active_mo_lists(scr: Screening, r: jnp.ndarray):
    """Per-point active-MO candidate lists (exact support screening).

    Returns ``(mo_idx, mo_valid)``, each (N, mo_budget); rows of A beyond
    their reach radius are exact zeros of the dense C (DESIGN.md §11).
    """
    cl = scr.mo_cells
    cid = _cell_ids(cl, r)
    mo_idx = jnp.asarray(cl.members)[cid]
    cand = jnp.asarray(cl.valid)[cid]
    d = r[..., None, :] - jnp.asarray(scr.mo_center, r.dtype)[mo_idx]
    r2 = jnp.sum(d * d, axis=-1)
    mo_valid = cand & (r2 < jnp.asarray(scr.mo_reach2, r.dtype)[mo_idx])
    return mo_idx, mo_valid


def gather_phi(A_blk: jnp.ndarray, ao_idx: jnp.ndarray, vals: jnp.ndarray,
               mo_idx: jnp.ndarray, mo_valid: jnp.ndarray,
               chunk: int = 32) -> jnp.ndarray:
    """Screened per-move orbital values phi = A_blk @ chi (SEM hot path).

    Only active (MO, AO) pairs are touched: per walker a double-gathered
    (K_mo, K_ao) panel of A contracts the packed AO values, and the active
    results scatter into the dense phi row (inactive MOs are exact zeros).
    ``A_blk`` may be an occupied-panel slice of the full row space; active
    MO ids beyond it are dropped.  Walkers go through a chunked scan so the
    gathered panel stays cache-sized.

    Args:
      A_blk: (n_rows, n_ao) MO panel.
      ao_idx: (W, K_ao) candidate AO ids; vals: (W, K_ao) packed AO values
        (zero at inactive slots).
      mo_idx / mo_valid: (W, K_mo) active-MO lists from
        ``active_mo_lists``.
      chunk: walker-block size for the scan.

    Returns phi: (W, n_rows).
    """
    import jax

    n_rows = A_blk.shape[0]
    W = vals.shape[0]
    mv = mo_valid & (mo_idx < n_rows)
    mi = jnp.where(mv, mo_idx, 0)
    chunk = max(1, min(chunk, W))
    pad = (-W) % chunk
    av = jnp.pad(vals, ((0, pad), (0, 0)))
    ai = jnp.pad(ao_idx, ((0, pad), (0, 0)))
    mi_ = jnp.pad(mi, ((0, pad), (0, 0)))
    mv_ = jnp.pad(mv, ((0, pad), (0, 0)))
    nb = av.shape[0] // chunk

    def _body(carry, wb):
        v, ix, m, ok = wb
        Asub = A_blk[m[:, :, None], ix[:, None, :]]       # (chunk, Kmo, Kao)
        p = jnp.einsum('wmk,wk->wm', Asub, v, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        return carry, jnp.where(ok, p, 0.0)

    _, ps = jax.lax.scan(
        _body, 0., (av.reshape(nb, chunk, -1), ai.reshape(nb, chunk, -1),
                    mi_.reshape(nb, chunk, -1), mv_.reshape(nb, chunk, -1)))
    p = ps.reshape(nb * chunk, -1)[:W]                    # (W, Kmo)
    phi = jnp.zeros((W, n_rows), p.dtype)
    return phi.at[jnp.arange(W)[:, None], mi].add(p, mode='drop')


def phi_from_packed(A_blk: jnp.ndarray, ao_idx: jnp.ndarray,
                    vals: jnp.ndarray, n_ao: int) -> jnp.ndarray:
    """Per-move phi without MO screening: scatter chi, one dense GEMM.

    Fallback when MO support screening is off (delocalized MOs): the
    packed AO values scatter into a dense (W, n_ao) row — candidates are
    unique per point, so ``add`` places each value exactly once — and a
    single GEMM against the panel gives every orbital value.
    """
    W = vals.shape[0]
    dense = jnp.zeros((W, n_ao), vals.dtype)
    dense = dense.at[jnp.arange(W)[:, None], ao_idx].add(vals, mode='drop')
    return jnp.matmul(dense, A_blk.T, precision=jax.lax.Precision.HIGHEST)


def memory_budget(scr: Screening, basis: BasisSet, n_e: int, n_rows: int,
                  n_walkers: int = 1, bytes_per: int = 4) -> dict:
    """Peak-memory budget of one MO-pipeline pass (paper idea ii.).

    Dense path materializes B: (n_ao, W*n_e, 5) + C: (n_rows, W*n_e, 5);
    the screened path replaces B with the packed (W*n_e, budget, 5) CSR
    (+ int32 index rows) and, with MO screening, builds C's scattered
    active panel first.  Returns byte counts for both paths.
    """
    n = n_walkers * n_e
    n_ao = basis.n_ao
    dense_b = n_ao * n * 5 * bytes_per
    dense_c = n_rows * n * 5 * bytes_per
    kb = scr.ao_budget if not scr.exhaustive else n_ao
    packed_b = n * kb * 5 * bytes_per + n * kb * 4
    panel_c = (n * scr.mo_budget * 5 * bytes_per
               if scr.mo_budget else 0)
    return dict(dense_b_bytes=dense_b, dense_c_bytes=dense_c,
                packed_b_bytes=packed_b, screened_panel_bytes=panel_c,
                screened_c_bytes=dense_c, ao_budget=kb,
                mo_budget=scr.mo_budget,
                dense_total=dense_b + dense_c,
                screened_total=packed_b + panel_c + dense_c)
