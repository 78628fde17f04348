"""Operations one all-electron generation needs (VMC and DMC moves).

Counted once each, from the shapes and the measured count ``a`` of active
AOs per electron, for every walker:

* the AO -> MO product over the active AOs: value, three gradient
  components and Laplacian, 2 * n_orb * a * 5 per electron;
* per spin block of order n, one LU (2/3 n^3) and the inverse from it
  (4/3 n^3);
* per spin block, the drift and Laplacian ratio contractions, 8 n^2.

Not counted: padding of the active lists to ``k_max``, Newton-Schulz
refinement, the re-evaluation in ``block_stats``, AO evaluation
(exponentials), Jastrow and potential (O(n_e^2) elementwise).
"""


def flops(n_up: int, n_dn: int, a: float, walkers: int, accept: float = 0.0,
          prims: float = 0.0) -> float:
    """Operations per generation over ``walkers`` walkers."""
    n_e, n_orb = n_up + n_dn, max(n_up, n_dn)
    per = 10.0 * n_orb * a * n_e
    for n in (n_up, n_dn):
        per += 2.0 * n ** 3 + 8.0 * n ** 2
    return per * walkers
