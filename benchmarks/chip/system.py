"""The benchmark's own copy of a configuration: molecule, basis and MOs.

A configuration file under ``configs/`` holds its molecule as data (each
atom's element and position in bohr) with the recipe of its basis and of
its localized MO coefficients.  This module builds from it, in plain
numpy, everything the plain reference (``reference.py``) evaluates; the
program under test gets the same atoms, shells and MO coefficients as
data (``program.py``) and builds its basis with its own code, which
``tests/test_reference.py`` checks agrees with this one bit for bit.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

Z = {'H': 1.0, 'C': 6.0, 'N': 7.0, 'O': 8.0, 'S': 16.0}
DFACT = [1.0, 1.0, 3.0, 15.0]          # (2n-1)!! for n = 0..3


@dataclasses.dataclass(frozen=True)
class System:
    """Everything the reference evaluates, as host arrays."""

    coords: np.ndarray        # (n_at, 3) float32
    charges: np.ndarray       # (n_at,) float32
    n_up: int
    n_dn: int
    ao_atom: np.ndarray       # (n_ao,) int32
    ao_pow: np.ndarray        # (n_ao, 3) int32
    prim_coeff: np.ndarray    # (n_ao, P) float32, normalized
    prim_exp: np.ndarray      # (n_ao, P) float32
    atom_radius2: np.ndarray  # (n_at,) float32
    mo: np.ndarray            # (n_up, n_ao) float32
    jastrow: tuple            # (b_ee, b_en, a_en)
    mo_center: np.ndarray     # (n_up,) atom each localized MO is built on

    @property
    def n_elec(self) -> int:
        return self.n_up + self.n_dn

    @property
    def n_ao(self) -> int:
        return int(self.ao_atom.shape[0])


def _even_tempered(a0, beta, n):
    return tuple(a0 * beta ** k for k in range(n))


def _contraction(n):
    w = np.exp(-0.5 * ((np.arange(n) - (n - 1) / 2) / max(n / 3, 1)) ** 2)
    return tuple(float(x) for x in w / w.sum())


def shells_631gss(z: float):
    """(l, exponents, coefficients) of one atom's 6-31G**-like shells.

    Cartesian shell counts of 6-31G**: H 2s1p (5 AOs), C/N/O 3s2p1d (15),
    S 4s3p1d (19).  Exponents are even-tempered or scaled from carbon's;
    the most diffuse of each atom is about 0.5, so that the screening
    radius at eps = 1e-8 is about 6 bohr, the value that gives the
    paper's ~140 active AOs per electron (Table IV).
    """
    if z < 1.5:
        return [(0, (18.73, 2.825, 0.640), (0.033, 0.235, 0.814)),
                (0, (0.50,), (1.0,)),
                (1, (1.1,), (1.0,))]
    if z > 10.5:
        return [
            (0, _even_tempered(21917.0, 0.18, 6), _contraction(6)),
            (0, (339.9, 79.3, 25.8), (-0.08, 0.35, 0.75)),
            (0, (3.6, 1.4, 0.76), (-0.2, 0.5, 0.7)),
            (0, (0.55,), (1.0,)),
            (1, (339.9, 79.3, 25.8), (0.1, 0.45, 0.55)),
            (1, (3.6, 1.4, 0.76), (0.05, 0.5, 0.55)),
            (1, (0.55,), (1.0,)),
            (2, (0.65,), (1.0,)),
        ]
    s = z / 6.0
    return [
        (0, _even_tempered(3047.0 * s * s, 0.18, 6), _contraction(6)),
        (0, (7.87 * s, 1.88 * s, 0.66 * s), (-0.12, 0.44, 0.65)),
        (0, (0.55 * s,), (1.0,)),
        (1, (7.87 * s, 1.88 * s, 0.66 * s), (0.26, 0.55, 0.29)),
        (1, (0.55 * s,), (1.0,)),
        (2, (0.9 * s,), (1.0,)),
    ]


SHELL_SETS = {'631gss': shells_631gss}


def _components(l):
    out = []
    for nx in range(l, -1, -1):
        for ny in range(l - nx, -1, -1):
            out.append((nx, ny, l - nx - ny))
    return out


def _primitive_norm(gamma, n):
    nx, ny, nz = n
    l = nx + ny + nz
    pref = (2.0 * gamma / math.pi) ** 0.75 * (4.0 * gamma) ** (l / 2.0)
    return pref / math.sqrt(DFACT[nx] * DFACT[ny] * DFACT[nz])


def _radius(exponents, coefficients, eps):
    """Distance beyond which the contracted radial part is below eps."""
    r = 1.0

    def g(r):
        return sum(abs(c) * math.exp(-min(a * r * r, 700.0))
                   for c, a in zip(coefficients, exponents))

    while g(r) >= eps and r < 64.0:
        r *= 1.25
    return r


def _basis(shells, n_atoms, eps):
    max_prim = max(len(e) for _, _, e, _ in shells)
    ao_atom, ao_pow, coeffs, exps = [], [], [], []
    radius2 = np.zeros((n_atoms,), np.float64)
    for atom, l, ex, co in shells:
        r = _radius(ex, co, eps)
        radius2[atom] = max(radius2[atom], r * r)
        for n in _components(l):
            ao_atom.append(atom)
            ao_pow.append(n)
            c = np.zeros((max_prim,), np.float64)
            a = np.ones((max_prim,), np.float64)
            for k, (ck, ak) in enumerate(zip(co, ex)):
                c[k] = ck * _primitive_norm(ak, n)
                a[k] = ak
            coeffs.append(c)
            exps.append(a)
    return (np.asarray(ao_atom, np.int32), np.asarray(ao_pow, np.int32),
            np.asarray(coeffs, np.float32), np.asarray(exps, np.float32),
            radius2.astype(np.float32))


def _centers(n_atoms, n_orb):
    return np.arange(n_atoms)[np.linspace(0, n_atoms - 1, n_orb).astype(int)]


def _localized_mos(rng, ao_atom, n_ao, coords, n_orb, loc_length,
                   threshold):
    """Gaussian distance envelope around each MO's centre atom, and a
    dominant coefficient on one AO of that atom: its first AO for the
    first MO centred there, its second for the second, so that no two
    MOs share their dominant AO."""
    centers = _centers(coords.shape[0], n_orb)
    d = np.linalg.norm(coords[centers][:, None, :]
                       - coords[ao_atom][None, :, :], axis=-1)
    A = rng.standard_normal((n_orb, n_ao)) * np.exp(-(d / loc_length) ** 2)
    first_ao = np.searchsorted(ao_atom, np.arange(coords.shape[0]))
    rank = np.array([np.sum(centers[:k] == c) for k, c in enumerate(centers)])
    A[np.arange(n_orb), first_ao[centers] + rank] += 3.0
    A /= np.linalg.norm(A, axis=1, keepdims=True)
    A[np.abs(A) < threshold] = 0.0
    return A.astype(np.float32)


def shells(cfg: dict) -> list:
    """(atom, l, exponents, coefficients) of every shell, atom by atom."""
    shell_fn = SHELL_SETS[cfg['basis_kind']]
    return [(a, l, ex, co) for a, (el, *_) in enumerate(cfg['atoms'])
            for l, ex, co in shell_fn(Z[el])]


def build(cfg: dict) -> System:
    """A configuration file's dict -> the System it describes."""
    coords = np.asarray([xyz for _, *xyz in cfg['atoms']], np.float64)
    charges = np.asarray([Z[el] for el, *_ in cfg['atoms']], np.float64)
    n_elec = int(cfg['n_elec'])
    if int(charges.sum()) != n_elec:
        raise ValueError('the atoms do not hold n_elec electrons')
    ao_atom, ao_pow, pc, pe, rad2 = _basis(shells(cfg), coords.shape[0],
                                           float(cfg['eps_ao']))
    if ao_atom.shape[0] != int(cfg['n_ao']):
        raise ValueError('the shells do not give n_ao AOs')
    n_up = int(cfg['n_up'])
    rng = np.random.default_rng(int(cfg['generator_seed']))
    mo = _localized_mos(rng, ao_atom, ao_atom.shape[0], coords, n_up,
                        float(cfg['loc_length']), float(cfg['mo_threshold']))
    jas = cfg['jastrow']
    return System(coords=coords.astype(np.float32),
                  charges=charges.astype(np.float32), n_up=n_up,
                  n_dn=n_elec - n_up, ao_atom=ao_atom, ao_pow=ao_pow,
                  prim_coeff=pc, prim_exp=pe, atom_radius2=rad2, mo=mo,
                  jastrow=(float(jas['b_ee']), float(jas['b_en']),
                           float(jas['a_en'])),
                  mo_center=_centers(coords.shape[0], n_up))


def initial_walkers(sysd: System, walkers: int, seed: int,
                    spread: float = 1.0) -> np.ndarray:
    """Starting positions (walkers, n_e, 3) drawn from ``seed``.

    Electron k of each spin sits at the atom its localized orbital k is
    built on, displaced by a Gaussian of width ``spread`` bohr: the usual
    start of a QMC run, with every orbital occupied near its centre, so
    that the Slater matrices start well conditioned.  An electron drawn
    outside its atom's screening radius is drawn again: there it has no
    AO.
    """
    rng = np.random.default_rng(seed)
    coords = sysd.coords.astype(np.float64)
    at = np.concatenate([sysd.mo_center[:sysd.n_up],
                         sysd.mo_center[:sysd.n_dn]])
    at = np.broadcast_to(at, (walkers, sysd.n_elec))
    r = coords[at] + spread * rng.standard_normal(at.shape + (3,))
    while True:
        out = np.sum((r - coords[at]) ** 2, -1) >= sysd.atom_radius2[at]
        if not out.any():
            return r.astype(np.float32)
        r[out] = coords[at[out]] + spread * rng.standard_normal(
            (int(out.sum()), 3))
