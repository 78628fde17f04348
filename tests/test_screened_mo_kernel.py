"""Pallas screened-gather MO kernel vs oracles: tiles, ragged lists, shards.

The kernel consumes packed-CSR candidate lists (``core.screening``), so the
cases that matter are exactly the ones dense-B kernels never see: ragged
active counts per electron, all-inactive electrons, padding slots at the
k-chunk boundary, and candidate ids repeating (padding id 0).  The jnp
oracle is ``kernels.screened_mo.ref.screened_mo_ref``; on the real pipeline
the kernel must also match the chunked ``mos.mo_products_sparse`` path
bitwise-free (allclose) and stay consistent under walker-axis sharding.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.screened_mo.ops import screened_mo_products
from repro.kernels.screened_mo.ref import screened_mo_ref

ROOT = Path(__file__).resolve().parents[1]


def _make_case(seed, n_orb, n_ao, n_e, K, frac_active=0.6, ragged=True):
    """Packed candidate lists with ragged per-electron active counts."""
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.normal(size=(n_orb, n_ao)), jnp.float32)
    idx = np.zeros((n_e, K), np.int32)
    active = np.zeros((n_e, K), bool)
    for e in range(n_e):
        n_act = int(rng.integers(0, K + 1)) if ragged \
            else int(frac_active * K)
        cand = np.sort(rng.choice(n_ao, size=min(n_act, n_ao),
                                  replace=False))
        idx[e, :len(cand)] = cand                      # padding stays id 0
        active[e, :len(cand)] = True
    Bp = jnp.asarray(rng.normal(size=(n_e, K, 5)), jnp.float32)
    return A, Bp, jnp.asarray(idx), jnp.asarray(active)


def _check(A, Bp, idx, active, **tiles):
    C_ref = screened_mo_ref(A, Bp, idx, active)
    C = screened_mo_products(A, Bp, idx, active, **tiles)
    np.testing.assert_allclose(np.asarray(C), np.asarray(C_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize('tiles', [
    dict(tile_o=8, tile_k=8, tile_e=8),
    dict(tile_o=16, tile_k=32, tile_e=4),
    dict(tile_o=64, tile_k=16, tile_e=16),
    dict(tile_o=128, tile_k=128, tile_e=8),    # TPU production shape
])
def test_kernel_tile_shapes(tiles):
    _check(*_make_case(0, n_orb=48, n_ao=160, n_e=24, K=40), **tiles)


@pytest.mark.parametrize('n_e,K', [
    (1, 1),        # degenerate: everything is padding
    (7, 13),       # both axes ragged vs the tile grid
    (8, 24),       # K not a multiple of tile_k -> padded k-chunk boundary
    (30, 65),      # one-past-chunk: last chunk almost all padding
])
def test_kernel_ragged_padding_boundaries(n_e, K):
    _check(*_make_case(1, n_orb=24, n_ao=96, n_e=n_e, K=K),
           tile_o=16, tile_k=16, tile_e=8)


def test_all_inactive_rows_are_zero():
    """Electrons with zero active candidates (and chunk-skip short-circuit)
    must produce exactly zero columns."""
    A, Bp, idx, active = _make_case(2, 32, 128, 12, 32)
    active = active.at[3].set(False).at[7].set(False)
    C = screened_mo_products(A, Bp, idx, active, tile_o=16, tile_k=16,
                             tile_e=4)
    assert float(jnp.max(jnp.abs(C[:, 3]))) == 0.0
    assert float(jnp.max(jnp.abs(C[:, 7]))) == 0.0
    _check(A, Bp, idx, active, tile_o=16, tile_k=16, tile_e=4)


def test_inactive_values_cannot_leak():
    """Garbage at inactive slots must not reach C (ops zeroes defensively)."""
    A, Bp, idx, active = _make_case(3, 16, 64, 8, 16)
    poisoned = jnp.where(active[..., None], Bp, 1e30)
    C_ref = screened_mo_ref(A, Bp, idx, active)
    C = screened_mo_products(A, poisoned, idx, active, tile_o=8, tile_k=8,
                             tile_e=4)
    np.testing.assert_allclose(np.asarray(C), np.asarray(C_ref),
                               rtol=1e-4, atol=1e-4)


def test_kernel_on_real_screening_structure():
    """End to end on a bench system: the kernel front door reproduces the
    unscreened sparse MO tensor (eps = 0 structure).  The 80-electron
    system has 172 AOs, so ``k_max`` 160 runs the packed product, and no
    electron has more than 160 active AOs, so it truncates nothing."""
    from repro.core import wavefunction as wf
    from repro.core.screening import active_ao_lists
    from repro.core import aos
    from repro.systems.bench import build_bench_wavefunction, \
        make_bench_system
    s = make_bench_system('micro-peptide', n_elec=80, seed=5)
    cfg_d, params = build_bench_wavefunction(s, method='sparse', k_max=160)
    cfg_k, _ = build_bench_wavefunction(s, method='kernel', k_max=160,
                                        screen_eps=0.0)
    rng = np.random.default_rng(0)
    at = rng.integers(0, s.mol.coords.shape[0], s.mol.n_elec)
    r = jnp.asarray(s.mol.coords[at]
                    + rng.normal(scale=1.2, size=(s.mol.n_elec, 3)),
                    jnp.float32)
    assert cfg_d.k_max < cfg_d.basis.n_ao
    packed = wf.product_route_counts()['packed']
    C_d, count = wf._mo_tensor(cfg_d, params, r)
    assert wf.product_route_counts()['packed'] == packed + 1
    assert int(jnp.max(count)) <= cfg_d.k_max
    idx, active, _ = active_ao_lists(cfg_k.screening, r)
    Bp = aos.eval_ao_block_screened(cfg_k.basis, params.coords, r, idx,
                                    active)
    C_k = screened_mo_products(params.mo, Bp, idx, active,
                               tile_o=32, tile_k=32, tile_e=8)
    np.testing.assert_allclose(np.asarray(C_k), np.asarray(C_d),
                               rtol=2e-4, atol=2e-4)


try:
    from hypothesis import given, settings, strategies as st

    @given(st.integers(min_value=0, max_value=10 ** 6))
    @settings(max_examples=15, deadline=None)
    def test_kernel_random_cases_property(seed):
        rng = np.random.default_rng(seed)
        _check(*_make_case(seed, n_orb=int(rng.integers(4, 40)),
                           n_ao=int(rng.integers(40, 120)),
                           n_e=int(rng.integers(1, 20)),
                           K=int(rng.integers(1, 48))),
               tile_o=8, tile_k=8, tile_e=4)
except ImportError:                                      # pragma: no cover
    @pytest.mark.parametrize('seed', range(8))
    def test_kernel_random_cases_property(seed):
        rng = np.random.default_rng(seed)
        _check(*_make_case(seed, n_orb=int(rng.integers(4, 40)),
                           n_ao=int(rng.integers(40, 120)),
                           n_e=int(rng.integers(1, 20)),
                           K=int(rng.integers(1, 48))),
               tile_o=8, tile_k=8, tile_e=4)


def _sharded_consistency_check():
    """Walker-sharded screened evaluation == single-device, bitwise.

    The kernel's electron axis is the flattened walker-major batch, so
    sharding the walker axis splits whole k-chunks — no cross-device
    contractions exist and the floats must not move.
    """
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import wavefunction as wf
    from repro.sharding import walkers_mesh
    from repro.systems.bench import build_bench_wavefunction, \
        make_bench_system
    s = make_bench_system('micro-peptide', n_elec=30, seed=5)
    cfg, params = build_bench_wavefunction(s, method='kernel', k_max=160,
                                           screen_eps=0.0)
    rng = np.random.default_rng(1)
    W = 8
    at = rng.integers(0, s.mol.coords.shape[0], (W, s.mol.n_elec))
    R = jnp.asarray(s.mol.coords[at]
                    + rng.normal(scale=1.2, size=(W, s.mol.n_elec, 3)),
                    jnp.float32)
    base = wf.psi_state_batched(cfg, params, R)
    mesh = walkers_mesh(8)
    Rs = jax.device_put(R, NamedSharding(mesh, P('walkers')))
    sharded = wf.psi_state_batched(cfg, params, Rs)
    np.testing.assert_array_equal(np.asarray(base.log_psi),
                                  np.asarray(sharded.log_psi))
    np.testing.assert_array_equal(np.asarray(base.e_loc),
                                  np.asarray(sharded.e_loc))
    return True


needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason='needs XLA_FLAGS=--xla_force_host_platform_device_count=8')


@needs_8_devices
def test_sharded_screened_kernel_bitwise_inprocess():
    assert _sharded_consistency_check()


@pytest.mark.slow
def test_sharded_screened_kernel_bitwise_subprocess():
    """Same check under 8 virtual CPU devices when this session is
    single-device (mirrors test_sem's subprocess pattern)."""
    if len(jax.devices()) >= 8:
        pytest.skip('in-process variant already covers this')
    env = dict(os.environ,
               XLA_FLAGS='--xla_force_host_platform_device_count=8',
               PYTHONPATH=str(ROOT / 'src'))
    code = ('import sys; sys.path.insert(0, %r); '
            'import test_screened_mo_kernel as t; '
            'assert t._sharded_consistency_check(); print("CONSISTENT")'
            % str(ROOT / 'tests'))
    out = subprocess.run([sys.executable, '-c', code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert 'CONSISTENT' in out.stdout
