"""Ensemble-flattened evaluation (`psi_state_batched`) vs per-walker vmap.

The ensemble path must be a pure performance transform: identical PsiState
(atol 1e-5; in practice bitwise on CPU) for every MO-product method, and the
VMC/DMC drivers that default to it must keep their physics contracts.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from functools import partial

from repro.core import aos, mos
from repro.core.wavefunction import (make_batched, psi_state,
                                     psi_state_batched)
from repro.kernels.sparse_mo.ops import ensemble_tile_e, ensemble_tiles
from repro.systems.molecule import build_wavefunction, h2, water


def _cfgs():
    # 'sparse' packs only where k_max < n_ao (7 for water): k_max 6 runs the
    # packed product.  Most electrons of water have all 7 AOs active, so it
    # truncates them, alike on both entry points that are compared.
    cfg_d, params = build_wavefunction(*water(), method='dense')
    assert cfg_d.basis.n_ao == 7
    return params, [
        ('dense', cfg_d),
        ('sparse', dataclasses.replace(cfg_d, method='sparse', k_max=6)),
        ('kernel', dataclasses.replace(cfg_d, method='kernel',
                                       kernel_tiles=(8, 8, 8))),
    ]


@pytest.mark.parametrize('method_i', [0, 1, 2], ids=['dense', 'sparse',
                                                     'kernel'])
def test_batched_matches_vmap_all_methods(method_i):
    params, cfgs = _cfgs()
    name, cfg = cfgs[method_i]
    rng = np.random.default_rng(42)
    R = jnp.asarray(rng.normal(scale=1.2, size=(5, cfg.n_elec, 3)),
                    jnp.float32)
    ref = jax.vmap(partial(psi_state, cfg, params))(R)
    bat = psi_state_batched(cfg, params, R)
    for field in ref._fields:
        a = np.asarray(getattr(ref, field), np.float32)
        b = np.asarray(getattr(bat, field), np.float32)
        assert a.shape == b.shape, (name, field)
        np.testing.assert_allclose(b, a, atol=1e-5, rtol=1e-5,
                                   err_msg=f'{name}.{field}')


def test_batched_matches_vmap_open_shell():
    """n_dn == 0 branch (single spin channel)."""
    from repro.systems.molecule import hydrogen
    cfg, params = build_wavefunction(*hydrogen(), method='dense')
    rng = np.random.default_rng(0)
    R = jnp.asarray(rng.normal(scale=1.0, size=(7, cfg.n_elec, 3)),
                    jnp.float32)
    ref = jax.vmap(partial(psi_state, cfg, params))(R)
    bat = psi_state_batched(cfg, params, R)
    np.testing.assert_allclose(np.asarray(bat.log_psi),
                               np.asarray(ref.log_psi), atol=1e-5)
    np.testing.assert_allclose(np.asarray(bat.e_loc),
                               np.asarray(ref.e_loc), atol=1e-5)


def test_make_batched_dispatch():
    params, cfgs = _cfgs()
    _, cfg = cfgs[0]
    rng = np.random.default_rng(1)
    R = jnp.asarray(rng.normal(size=(3, cfg.n_elec, 3)), jnp.float32)
    ens = make_batched(cfg)(params, R)
    legacy = make_batched(dataclasses.replace(cfg, ensemble_eval=False))(
        params, R)
    np.testing.assert_allclose(np.asarray(ens.log_psi),
                               np.asarray(legacy.log_psi), atol=1e-5)


def test_vmc_block_same_physics_both_paths():
    """One VMC block, same key: ensemble and vmap paths agree closely."""
    from repro.core.driver import EnsembleDriver
    from repro.core.vmc import VMCPropagator, init_walkers
    cfg_e, params = build_wavefunction(*h2())
    cfg_v = dataclasses.replace(cfg_e, ensemble_eval=False)
    stats = {}
    for tag, cfg in [('ens', cfg_e), ('vmap', cfg_v)]:
        ens = init_walkers(cfg, params, jax.random.PRNGKey(0), 32)
        drv = EnsembleDriver(VMCPropagator(cfg, tau=0.3), steps=15,
                             donate=False)
        _, s = drv.run_block(params, ens, jax.random.PRNGKey(5))
        stats[tag] = float(s.e_mean)
    assert abs(stats['ens'] - stats['vmap']) < 1e-4, stats


def test_eval_ao_block_flat_and_walker_shapes_agree():
    cfg, params = build_wavefunction(*water())
    rng = np.random.default_rng(3)
    R = jnp.asarray(rng.normal(scale=1.5, size=(4, cfg.n_elec, 3)),
                    jnp.float32)
    Bw, aaw = aos.eval_ao_block(cfg.basis, params.coords, R)      # batched
    Bf, aaf = aos.eval_ao_block(cfg.basis, params.coords,
                                R.reshape(-1, 3))                 # flattened
    n_ao = Bf.shape[0]
    merged = jnp.moveaxis(Bw, 0, 1).reshape(n_ao, -1, 5)
    np.testing.assert_array_equal(np.asarray(merged), np.asarray(Bf))
    np.testing.assert_array_equal(
        np.asarray(aaw.reshape(-1, aaw.shape[-1])), np.asarray(aaf))


def test_ensemble_tile_helpers():
    assert ensemble_tile_e(8, 8) == 8              # nothing to grow into
    assert ensemble_tile_e(4096, 8, cap=128) == 128
    assert ensemble_tile_e(96, 8, cap=128) == 64   # bounded by batch
    to, tk, te = ensemble_tiles((16, 32, 8), n_orb=30, n_e_total=3840,
                                cap_e=2048)
    assert to == 32          # grows to cover n_orb
    assert tk == 32          # never changes
    assert te == 2048        # interpret-mode cap (pinned explicitly —
    #                          cap_e=0 would pick it per backend)
    to_t, _, te_t = ensemble_tiles((16, 32, 8), n_orb=30, n_e_total=3840,
                                   cap_e=128)
    assert te_t == 128       # the TPU cap
    # tiles never shrink below the caller's choice
    to2, _, _ = ensemble_tiles((64, 32, 8), n_orb=30, n_e_total=64)
    assert to2 == 64


def test_default_chunk_regimes():
    assert mos.default_chunk(60) == 64
    assert mos.default_chunk(1731) == 64       # large single walker: still 64
    assert mos.default_chunk(512, ensemble=True) == 64
    assert mos.default_chunk(3840, ensemble=True) == 256
