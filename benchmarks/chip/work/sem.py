"""Operations one single-electron-move sweep needs (SEM-VMC).

A sweep tries one move per electron.  Counted once each, from the shapes,
the measured count ``a`` of active AOs per electron, the mean number
``prims`` of primitives per AO and the acceptance, for every walker:

* per move: the AO values at the proposed point over the active AOs
  (2 * prims * a), the MO values of its spin block (2 * n * a), the
  determinant ratio (2 n);
* per accepted move: the rank-1 inverse update, 4 n^2;
* per sweep: one MO pass over all electrons for the local energy
  (2 * n_orb * a * 5 per electron), one LU per spin block (2/3 n^3) and
  the ratio contractions (8 n^2).

Not counted: padding to ``k_max``, Newton-Schulz correction, the periodic
refresh, the re-evaluation in ``block_stats``, Jastrow and potential.
"""


def flops(n_up: int, n_dn: int, a: float, walkers: int, accept: float = 0.0,
          prims: float = 0.0) -> float:
    """Operations per sweep over ``walkers`` walkers."""
    n_e, n_orb = n_up + n_dn, max(n_up, n_dn)
    per = 10.0 * n_orb * a * n_e
    for n in (n_up, n_dn):
        per += n * (2.0 * prims * a + 2.0 * n * a + 2.0 * n)
        per += accept * n * 4.0 * n ** 2
        per += 2.0 / 3.0 * n ** 3 + 8.0 * n ** 2
    return per * walkers
