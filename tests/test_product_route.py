"""Which MO product the unscreened passes run, and that both give one C.

Packing each electron's active AOs into ``k_max`` slots (the paper's sparse
product) shortens the contraction only where ``k_max < n_ao``; elsewhere the
wavefunction runs the dense product (``wavefunction._product_route``).  At
``k_max >= n_ao`` nothing is truncated and B is an exact zero outside each
atom's radius, so the dense C is the packed C up to the order of the fp32
additions.  The 80-electron micro-peptide has 172 AOs and at most 144
active at any point of a grid over the molecule, so ``k_max`` 160 packs
without truncating.
"""
import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aos, mos
from repro.core import wavefunction as wf
from repro.systems.bench import build_bench_wavefunction, make_bench_system

W = 3


@lru_cache(maxsize=None)
def _system():
    s = make_bench_system('micro-peptide', n_elec=80, seed=5)
    cfg, params = build_bench_wavefunction(s, method='sparse', k_max=512)
    return s, cfg, params


def _walkers(s, seed=0):
    rng = np.random.default_rng(seed)
    at = rng.integers(0, s.mol.coords.shape[0], (W, s.mol.n_elec))
    return jnp.asarray(s.mol.coords[at]
                       + rng.normal(scale=1.2, size=(W, s.mol.n_elec, 3)),
                       jnp.float32)


def _packed_tensor(cfg, params, R):
    """C: (W, n_orb, n_e, 5) from ``active_ao_indices`` -> ``pack_b`` ->
    ``mo_products_sparse``, called directly on each walker's B."""
    def one(r):
        B, atom_active = aos.eval_ao_block(cfg.basis, params.coords, r)
        ao_mask = aos.atom_to_ao(atom_active, cfg.basis.ao_atom)
        idx, valid, count = aos.active_ao_indices(cfg.basis, atom_active,
                                                  cfg.k_max, ao_mask=ao_mask)
        assert int(jnp.max(count)) <= cfg.k_max, 'packing truncated'
        return mos.mo_products_sparse(params.mo, aos.pack_b(B, idx, valid),
                                      idx)
    return jnp.stack([one(r) for r in R])


def _close(a, b, rtol):
    a, b = np.asarray(a), np.asarray(b)
    np.testing.assert_allclose(a, b, rtol=rtol,
                               atol=rtol * float(np.max(np.abs(b))))


@pytest.mark.parametrize('method,k_max,route', [
    ('sparse', 160, 'packed'), ('sparse', 171, 'packed'),
    ('sparse', 172, 'dense'), ('sparse', 173, 'dense'),
    ('sparse', 512, 'dense'), ('sparse', 0, 'dense'),
    ('dense', 160, 'dense')])
def test_route_follows_k_max_against_n_ao(method, k_max, route):
    """Both entry points (per walker and ensemble) take the dense route at
    ``k_max >= n_ao`` (or 0, or for the 'dense' method) and the packed
    route below it."""
    s, cfg, params = _system()
    assert cfg.basis.n_ao == 172
    cfg = dataclasses.replace(cfg, method=method, k_max=k_max)
    R = _walkers(s)
    other = 'dense' if route == 'packed' else 'packed'
    before = wf.product_route_counts()
    wf.psi_state(cfg, params, R[0])
    wf.psi_state_batched(cfg, params, R)
    after = wf.product_route_counts()
    assert after[route] == before[route] + 2
    assert after[other] == before[other]


def test_dense_route_tensor_matches_packed_functions():
    """At ``k_max >= n_ao`` the ensemble pass's C (dense route) equals the
    packed functions' C on the same B, within fp32 rounding; so does the
    per-walker pass's."""
    s, cfg, params = _system()
    R = _walkers(s, seed=1)
    before = wf.product_route_counts()
    Cw, count = wf._mo_tensor_ensemble(cfg, params, R)
    assert wf.product_route_counts()['dense'] == before['dense'] + 1
    C_packed = _packed_tensor(cfg, params, R)
    _close(Cw, C_packed, 1e-5)
    C1, count1 = wf._mo_tensor(cfg, params, R[1])
    _close(C1, C_packed[1], 1e-5)
    # the ao_fill counter reads the same active counts on either route
    B, atom_active = aos.eval_ao_block(cfg.basis, params.coords, R)
    want = aos.atom_to_ao(atom_active, cfg.basis.ao_atom).sum(-1)
    np.testing.assert_array_equal(np.asarray(count), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(count1), np.asarray(want[1]))


def test_dense_route_state_matches_packed_functions():
    """log psi, drift and E_L through ``psi_state_batched`` (dense route)
    agree with the same tail run on the packed functions' C, within C's
    fp32 rounding amplified by each walker's Slater matrices: to first
    order a relative error d in C moves log psi by at most cond * d and the
    drift and kinetic terms by cond * d of their size.  At these scattered
    positions the condition number reaches 1e7, and a half-ulp perturbation
    of C moves the drift as far as the change of route does."""
    s, cfg, params = _system()
    R = _walkers(s, seed=2)
    got = wf.psi_state_batched(cfg, params, R)
    C_packed = _packed_tensor(cfg, params, R)
    _, count = wf._mo_tensor_ensemble(cfg, params, R)
    want = jax.vmap(partial(wf._finish_state, cfg, params))(C_packed, R,
                                                            count)
    np.testing.assert_array_equal(np.asarray(got.sign),
                                  np.asarray(want.sign))
    vals = np.asarray(C_packed[..., 0], np.float64)       # (W, n_orb, n_e)
    n = cfg.n_up
    cond = np.maximum(np.linalg.cond(vals[:, :, :n]),
                      np.linalg.cond(vals[:, :, n:]))
    tol = cond * 2.0 ** -20                   # a few ulps of C, amplified
    drift = np.asarray(want.drift)
    kinetic = (np.abs(np.asarray(want.e_kin))
               + 0.5 * np.sum(drift ** 2, axis=(1, 2)))
    for w in range(W):
        assert abs(float(got.log_psi[w] - want.log_psi[w])) <= tol[w]
        assert (np.max(np.abs(np.asarray(got.drift[w]) - drift[w]))
                <= tol[w] * np.max(np.abs(drift[w])))
        assert abs(float(got.e_loc[w] - want.e_loc[w])) <= tol[w] * kinetic[w]
