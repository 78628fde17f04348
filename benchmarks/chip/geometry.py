"""Coordinates of a molecule from its bond graph, by distance relaxation.

    python3 benchmarks/chip/geometry.py > /tmp/atoms.json

Writes the ``atoms`` list of ``configs/smallest.json``: the Met-Phe
dipeptide C14H20N2O3S, whose 158 electrons and 404 6-31G**-like AOs are
the paper's Table IV counts for its smallest system (arXiv:1209.6630).
The paper's coordinates are not published with it, so these come from
standard bond lengths and angles: every bond and every angle becomes a
target distance (bond lengths, and 1-3 distances from sp3 or sp2
angles), the ring's para pairs keep it a regular hexagon, other pairs
are pushed apart below a contact distance, and a seeded random start is
relaxed to the least squares of all of them.  The result is a folded
conformer with the right bonded structure, not an optimized geometry.
"""
from __future__ import annotations

import json
import math

import numpy as np

ANGSTROM = 1.8897261245650618          # bohr
# heavy atoms: (name, element, hybridization, bonded heavy neighbours
# among those listed before it, hydrogens)
HEAVY = [
    ('N1', 'N', 'sp3', [], 2),
    ('CA1', 'C', 'sp3', ['N1'], 1),
    ('C1', 'C', 'sp2', ['CA1'], 0),
    ('O1', 'O', 'sp2', ['C1'], 0),
    ('CB1', 'C', 'sp3', ['CA1'], 2),
    ('CG1', 'C', 'sp3', ['CB1'], 2),
    ('SD', 'S', 'sp3', ['CG1'], 0),
    ('CE', 'C', 'sp3', ['SD'], 3),
    ('N2', 'N', 'sp2', ['C1'], 1),
    ('CA2', 'C', 'sp3', ['N2'], 1),
    ('C2', 'C', 'sp2', ['CA2'], 0),
    ('O2', 'O', 'sp2', ['C2'], 0),
    ('OXT', 'O', 'sp3', ['C2'], 1),
    ('CB2', 'C', 'sp3', ['CA2'], 2),
    ('CG2', 'C', 'ar', ['CB2'], 0),
    ('CD1', 'C', 'ar', ['CG2'], 1),
    ('CE1', 'C', 'ar', ['CD1'], 1),
    ('CZ', 'C', 'ar', ['CE1'], 1),
    ('CE2', 'C', 'ar', ['CZ'], 1),
    ('CD2', 'C', 'ar', ['CE2', 'CG2'], 1),
]
DOUBLE = {('C1', 'O1'), ('C2', 'O2')}
PEPTIDE = {('C1', 'N2')}
BOND = {'CC': 1.53, 'CN': 1.47, 'CO': 1.36, 'CS': 1.81, 'CH': 1.09,
        'NH': 1.01, 'OH': 0.97, 'SH': 1.34}
ANGLE = {'sp3': 109.5, 'sp2': 120.0, 'ar': 120.0}
Z = {'H': 1, 'C': 6, 'N': 7, 'O': 8, 'S': 16}


def graph():
    """(elements, bonds with lengths in angstrom, hybridization)."""
    names = [h[0] for h in HEAVY]
    el = [h[1] for h in HEAVY]
    hyb = [h[2] for h in HEAVY]
    bonds = {}
    for i, (name, e, _, nbrs, _) in enumerate(HEAVY):
        for nb in nbrs:
            j = names.index(nb)
            pair = (nb, name)
            if pair in DOUBLE:
                d = 1.23
            elif pair in PEPTIDE:
                d = 1.33
            elif hyb[i] == hyb[j] == 'ar':
                d = 1.39
            else:
                d = BOND[''.join(sorted(el[j] + e))]
            bonds[(j, i)] = d
    for i, (_, e, _, _, n_h) in enumerate(HEAVY):
        for _ in range(n_h):
            bonds[(i, len(el))] = BOND[e + 'H']
            el.append('H')
            hyb.append('')
    return el, bonds, hyb


def targets(el, bonds, hyb):
    """Target distances: bonds, angles, the ring's para pairs."""
    n = len(el)
    nbr = [[] for _ in range(n)]
    for (i, j), d in bonds.items():
        nbr[i].append((j, d))
        nbr[j].append((i, d))
    t = dict(bonds)
    for c in range(n):
        ang = ANGLE.get(hyb[c])
        if el[c] == 'S':
            ang = 100.0
        elif el[c] == 'O' and hyb[c] == 'sp3':
            ang = 105.0
        for a in range(len(nbr[c])):
            for b in range(a + 1, len(nbr[c])):
                (i, di), (j, dj) = nbr[c][a], nbr[c][b]
                t[(min(i, j), max(i, j))] = math.sqrt(
                    di * di + dj * dj
                    - 2 * di * dj * math.cos(math.radians(ang)))
    ring = [i for i in range(n) if hyb[i] == 'ar']
    order = [ring[k] for k in (0, 1, 2, 3, 4, 5)]     # CG CD1 CE1 CZ CE2 CD2
    for k in range(3):
        i, j = order[k], order[k + 3]
        t[(min(i, j), max(i, j))] = 2 * 1.39
    return t


def relax(el, t, seed=5, iters=20000):
    """Least squares of the targets plus contact repulsion, from a seeded
    random start; -> (n, 3) angstrom."""
    rng = np.random.default_rng(seed)
    n = len(el)
    x = rng.normal(scale=3.0, size=(n, 3))
    pairs = np.array(list(t), np.int64)
    d0 = np.array([t[p] for p in t])
    heavy = np.array([e != 'H' for e in el])
    contact = np.where(heavy[:, None] & heavy[None], 3.0,
                       np.where(heavy[:, None] | heavy[None], 2.4, 2.0))
    free = np.ones((n, n), bool)
    np.fill_diagonal(free, False)
    free[pairs[:, 0], pairs[:, 1]] = free[pairs[:, 1], pairs[:, 0]] = False
    lr = 0.02
    for _ in range(iters):
        v = x[pairs[:, 0]] - x[pairs[:, 1]]
        d = np.linalg.norm(v, axis=1)
        g = np.zeros_like(x)
        f = ((d - d0) / d)[:, None] * v
        np.add.at(g, pairs[:, 0], f)
        np.add.at(g, pairs[:, 1], -f)
        diff = x[:, None] - x[None]
        dd = np.linalg.norm(diff, axis=-1) + np.eye(n)
        push = free & (dd < contact)
        g += np.sum(np.where(push, (dd - contact) / dd, 0.0)[..., None]
                    * diff, axis=1)
        x -= lr * g
    return x


def main() -> None:
    el, bonds, hyb = graph()
    x = relax(el, targets(el, bonds, hyb)) * ANGSTROM
    x -= x.mean(axis=0)
    assert sum(Z[e] for e in el) == 158
    print(json.dumps([[e] + [round(float(c), 4) for c in p]
                      for e, p in zip(el, x)]))


if __name__ == '__main__':
    main()
