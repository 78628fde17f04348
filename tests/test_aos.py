"""AO evaluation: analytic derivatives vs autodiff oracle + exact screening."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aos
from repro.core.basis import Shell, build_basis
from repro.systems.molecule import water

jax.config.update('jax_enable_x64', False)


def _random_basis(seed=0):
    rng = np.random.default_rng(seed)
    coords = jnp.asarray(rng.normal(scale=2.0, size=(3, 3)), jnp.float32)
    shells = []
    for atom in range(3):
        for l in range(3):  # s, p, d
            n_prim = int(rng.integers(1, 4))
            exps = tuple(float(x) for x in rng.uniform(0.3, 4.0, n_prim))
            cs = tuple(float(x) for x in rng.uniform(0.2, 1.0, n_prim))
            shells.append(Shell(atom, l, exps, cs))
    return build_basis(shells, 3), coords


def _ao_value_fn(basis, coords):
    def f(r):
        B, _ = aos.eval_ao_block(basis, coords, r[None, :])
        return B[:, 0, 0]  # (n_ao,) values only
    return f


@pytest.mark.parametrize('seed', [0, 1, 2])
def test_ao_gradients_match_autodiff(seed):
    basis, coords = _random_basis(seed)
    f = _ao_value_fn(basis, coords)
    rng = np.random.default_rng(seed + 10)
    r = jnp.asarray(rng.normal(scale=1.5, size=(3,)), jnp.float32)

    B, _ = aos.eval_ao_block(basis, coords, r[None, :])
    grad_analytic = B[:, 0, 1:4]                       # (n_ao, 3)
    grad_ad = jax.jacfwd(f)(r)                         # (n_ao, 3)
    np.testing.assert_allclose(grad_analytic, grad_ad, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize('seed', [0, 1])
def test_ao_laplacian_matches_autodiff(seed):
    basis, coords = _random_basis(seed)
    f = _ao_value_fn(basis, coords)
    rng = np.random.default_rng(seed + 20)
    r = jnp.asarray(rng.normal(scale=1.2, size=(3,)), jnp.float32)

    B, _ = aos.eval_ao_block(basis, coords, r[None, :])
    lap_analytic = B[:, 0, 4]
    hess = jax.jacfwd(jax.jacfwd(f))(r)                # (n_ao, 3, 3)
    lap_ad = jnp.trace(hess, axis1=1, axis2=2)
    np.testing.assert_allclose(lap_analytic, lap_ad, rtol=4e-3, atol=2e-3)


def test_screening_is_exact_zero():
    """Electrons beyond every atomic radius produce exactly-zero AO rows."""
    basis, coords = _random_basis(3)
    far = jnp.asarray([[50.0, 50.0, 50.0]], jnp.float32)
    B, atom_active = aos.eval_ao_block(basis, coords, far)
    assert not bool(jnp.any(atom_active))
    assert float(jnp.max(jnp.abs(B))) == 0.0


def test_screening_radius_conservative():
    """Just inside/outside the radius: outside is < EPS-scale, inside kept."""
    mol, shells = water()
    basis = build_basis(shells, mol.coords.shape[0])
    coords = jnp.asarray(mol.coords, jnp.float32)
    r_screen = float(np.sqrt(basis.atom_radius2[0]))
    probe = jnp.asarray([[0.0, 0.0, mol.coords[0, 2] + r_screen * 1.01]],
                        jnp.float32)
    _, active = aos.eval_ao_block(basis, coords, probe)
    assert not bool(active[0, 0])   # atom 0 screened out just past its radius


def test_active_indices_and_pack_roundtrip():
    basis, coords = _random_basis(4)
    rng = np.random.default_rng(5)
    r = jnp.asarray(rng.normal(scale=3.0, size=(6, 3)), jnp.float32)
    B, atom_active = aos.eval_ao_block(basis, coords, r)
    k_max = basis.n_ao  # exact
    idx, valid, count = aos.active_ao_indices(basis, atom_active, k_max)
    Bp = aos.pack_b(B, idx, valid)
    # scatter the packed rows back: must reproduce B exactly
    n_e = r.shape[0]
    B_rec = jnp.zeros_like(B)
    B_rec = B_rec.at[idx, jnp.arange(n_e)[:, None], :].add(
        jnp.where(valid[..., None], Bp, 0.0))
    np.testing.assert_array_equal(np.asarray(B_rec), np.asarray(B))
    assert bool(jnp.all(count <= basis.n_ao))


@pytest.mark.parametrize('batch', [(), (5,), (2, 5)], ids=['one', 'flat', 'walkers'])
def test_atom_to_ao_matches_gather(batch):
    """The gather-free per-atom -> per-AO expansion equals indexing by
    ``ao_atom``, bit for bit, for floats and masks of any batch shape."""
    basis, _ = _random_basis(3)
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=batch + (3,)), jnp.float32)
    ao_atom = np.asarray(basis.ao_atom)
    np.testing.assert_array_equal(np.asarray(aos.atom_to_ao(x, ao_atom)),
                                  np.asarray(x)[..., ao_atom])
    m = x > 0
    np.testing.assert_array_equal(np.asarray(aos.atom_to_ao(m, ao_atom)),
                                  np.asarray(m)[..., ao_atom])
