"""MO matrix products C_i = A @ B_i, i=1..5 (paper §III — the hot spot).

Three implementations, all returning ``C: (n_orb, n_elec, 5)``:

* ``mo_products_dense``  — the O(N^3) oracle: one dense matmul against the
  stacked B.  This is also the best XLA path when B is not sparse.
* ``mo_products_sparse`` — the paper's algorithm, vectorized: per-electron
  gather of the active columns of A (A stays DENSE — the paper's key choice)
  against the packed B rows.  O(n_orb * n_elec * K) with K ~ const in N.
* ``kernels.sparse_mo.ops.sparse_mo_products`` — the Pallas TPU kernel with
  (8·k,128) tile blocking; bit-compared against these in tests.

The five products share one A-gather (the paper's fused unroll-and-jam:
amortize loads of A across the 5 right-hand sides).  They run at full fp32
(``Precision.HIGHEST``): their value rows are the Slater matrices whose
fresh inverses the single-electron-move state must track to 1e-4
(DESIGN.md §6), and a TPU's default for fp32 operands is one bf16 pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def mo_products_dense(A: jnp.ndarray, B: jnp.ndarray) -> jnp.ndarray:
    """A: (n_orb, n_ao), B: (n_ao, n_e, 5) -> C: (n_orb, n_e, 5)."""
    n_ao, n_e, five = B.shape
    C = jnp.matmul(A, B.reshape(n_ao, n_e * five), precision=jax.lax.Precision.HIGHEST)
    return C.reshape(A.shape[0], n_e, five)


def default_chunk(n_e: int, ensemble: bool = False) -> int:
    """Electron-block size for ``mo_products_sparse``.

    Single-walker calls always use 64 — the cache-blocking choice tuned on
    the paper systems (including the large 1ZE7/1AMB walkers).  Big
    ensemble-flattened batches use 256 so scan/dispatch overhead amortizes
    while the gathered-A working set stays cache-sized — per-walker ``vmap``
    instead multiplies the per-step gather by W, which is exactly the
    blow-up the flattened path avoids.  Only the ensemble entry point flags
    ``ensemble=True``; a large electron count alone does not reclassify a
    walker.
    """
    return 256 if ensemble and n_e > 512 else 64


def mo_products_sparse(A: jnp.ndarray, Bp: jnp.ndarray, idx: jnp.ndarray,
                       chunk: int = 0) -> jnp.ndarray:
    """Sparse product from packed B.

    ``Bp``/``idx`` may cover one walker's electrons or a whole ensemble
    flattened walker-major to ``n_e = W * n_elec`` rows — electrons are
    independent columns of C, and the flattened form amortizes each gathered
    A panel across the full population (paper's load amortization, scaled to
    the ensemble).

    Args:
      A:   (n_orb, n_ao) dense MO coefficients (constant during the run).
      Bp:  (n_e, K, 5) packed active-AO values (zero padded).
      idx: (n_e, K) active AO indices (padding -> 0; Bp is 0 there).
      chunk: electron-block size bounding the gathered-A working set
        (the paper's cache blocking over electrons); 0 -> ``default_chunk``.

    Returns C: (n_orb, n_e, 5).
    """
    n_e = Bp.shape[0]
    if chunk <= 0:
        chunk = default_chunk(n_e)
    pad = (-n_e) % chunk
    Bp_ = jnp.pad(Bp, ((0, pad), (0, 0), (0, 0)))
    idx_ = jnp.pad(idx, ((0, pad), (0, 0)))
    nb = Bp_.shape[0] // chunk

    def _body(carry, eb):
        bp, ix = eb                            # (chunk,K,5), (chunk,K)
        Ag = A[:, ix]                          # (n_orb, chunk, K)
        c = jnp.einsum('oek,ekf->oef', Ag, bp, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        return carry, c

    _, Cs = jax.lax.scan(
        _body, 0.,
        (Bp_.reshape(nb, chunk, *Bp.shape[1:]),
         idx_.reshape(nb, chunk, idx.shape[1])))
    C = jnp.moveaxis(Cs, 0, 1).reshape(A.shape[0], nb * chunk, 5)
    return C[:, :n_e]


def mo_products_screened(A: jnp.ndarray, Bp: jnp.ndarray, idx: jnp.ndarray,
                         mo_idx: jnp.ndarray, mo_valid: jnp.ndarray,
                         chunk: int = 0) -> jnp.ndarray:
    """Doubly screened product: active MOs x active AOs per electron.

    The linear-scaling hot path (paper §II + the Alfè–Gillan orbital
    cutoff): per electron only its active-MO rows are computed, each as a
    contraction over its active-AO columns — a double-gathered
    (chunk, K_mo, K_ao) panel of A against the packed B rows, then a
    scatter of the active panel into the dense C.  Rows outside an
    electron's MO reach are *exact zeros* of the dense product
    (``screening.build_screening`` derives the reach from A's support), so
    this path adds no error beyond the AO tolerance.  O(n_e * K_mo * K_ao)
    flops — constant per electron, linear in system size.

    Args:
      A:   (n_rows, n_ao) dense MO coefficients.
      Bp:  (n_e, K_ao, 5) packed active-AO values (zeros at padding).
      idx: (n_e, K_ao) candidate AO ids.
      mo_idx / mo_valid: (n_e, K_mo) active-MO lists
        (``screening.active_mo_lists``).
      chunk: electron-block size for the scan; 0 -> ``default_chunk``.

    Returns C: (n_rows, n_e, 5).
    """
    n_rows = A.shape[0]
    n_e = Bp.shape[0]
    if chunk <= 0:
        chunk = default_chunk(n_e)
    chunk = min(chunk, n_e)
    mi = jnp.where(mo_valid, mo_idx, 0)
    pad = (-n_e) % chunk
    Bp_ = jnp.pad(Bp, ((0, pad), (0, 0), (0, 0)))
    idx_ = jnp.pad(idx, ((0, pad), (0, 0)))
    mi_ = jnp.pad(mi, ((0, pad), (0, 0)))
    mv_ = jnp.pad(mo_valid, ((0, pad), (0, 0)))
    nb = Bp_.shape[0] // chunk

    def _body(carry, eb):
        bp, ix, m, ok = eb
        Asub = A[m[:, :, None], ix[:, None, :]]    # (chunk, K_mo, K_ao)
        c = jnp.einsum('emk,ekf->emf', Asub, bp, precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)
        return carry, jnp.where(ok[..., None], c, 0.0)

    _, Cs = jax.lax.scan(
        _body, 0.,
        (Bp_.reshape(nb, chunk, *Bp.shape[1:]),
         idx_.reshape(nb, chunk, -1),
         mi_.reshape(nb, chunk, -1), mv_.reshape(nb, chunk, -1)))
    Cp = Cs.reshape(nb * chunk, *Cs.shape[2:])[:n_e]     # (n_e, K_mo, 5)
    C = jnp.zeros((n_rows, n_e, 5), Cp.dtype)
    return C.at[mi, jnp.arange(n_e)[:, None]].add(Cp, mode='drop')
