"""Unified Propagator/Driver API: one block loop for every method.

Covers the driver contract (DESIGN.md §5): deprecated wrappers delegate to
the same implementation, restart tiling, E_T feedback routing, per-walker
RNG, and — the scaling contract — single-device vs mesh-sharded blocks
producing the same BlockStats to fp32 reduction tolerance on an 8-virtual-
device CPU mesh (subprocess with XLA_FLAGS, or in-process when the session
already has the devices, e.g. the CI sharded job).
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.dmc import DMCPropagator, init_dmc
from repro.core.driver import EnsembleDriver, Population, restart_ensemble
from repro.core.vmc import VMCPropagator, evaluate_ensemble, init_walkers
from repro.systems.molecule import build_wavefunction, h2

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope='module')
def h2_wf():
    return build_wavefunction(*h2())


# ---------------------------------------------------------------------------
# driver basics + the method registry
# ---------------------------------------------------------------------------
def test_driver_vmc_block_stats_contract(h2_wf):
    """One VMC block: typed BlockStats with walker-step weight + aux."""
    cfg, params = h2_wf
    drv = EnsembleDriver(VMCPropagator(cfg, tau=0.3), steps=8, donate=False)
    ens = drv.init(params, jax.random.PRNGKey(0), 16)
    _, stats = drv.run_block(params, ens, jax.random.PRNGKey(1))
    assert float(stats.weight) == 8 * 16
    assert np.isfinite(float(stats.e_mean))
    assert set(stats.aux) == {'accept', 'ao_fill', 'e_kin', 'e_pot'}


def test_driver_dmc_block_stats_contract(h2_wf):
    cfg, params = h2_wf
    ens = init_walkers(cfg, params, jax.random.PRNGKey(0), 16)
    state = init_dmc(ens, e_trial=-1.1)
    drv = EnsembleDriver(DMCPropagator(cfg, e_trial=-1.1, tau=0.02),
                         steps=8, donate=False)
    _, stats = drv.run_block(params, state, jax.random.PRNGKey(1))
    assert np.isfinite(float(stats.e_mean))
    assert set(stats.aux) == {'accept', 'pop_weight', 'sign_flips',
                              'ao_fill'}


def test_make_propagator_registry(h2_wf):
    """The one place method strings resolve — used by RunSpec and the CLI."""
    from repro.core.dmc import DMCPropagator as DMC
    from repro.core.driver import make_propagator
    from repro.core.sem import SEMVMCPropagator
    cfg, _ = h2_wf
    assert isinstance(make_propagator('vmc', cfg), VMCPropagator)
    dmc = make_propagator('dmc', cfg, e_trial=-1.2)
    assert isinstance(dmc, DMC) and dmc.e_trial0 == -1.2
    assert make_propagator('dmc', cfg).e_trial0 == -0.5 * cfg.n_elec
    sem = make_propagator('sem-vmc', cfg, tau=0.45)
    assert isinstance(sem, SEMVMCPropagator)
    assert sem.step_size == pytest.approx(0.45)
    assert make_propagator('vmc', cfg).tau == pytest.approx(0.3)  # default
    with pytest.raises(ValueError, match='unknown method'):
        make_propagator('gfmc', cfg)


def test_driver_pickles_without_jit_cache_and_rejects_mesh(h2_wf):
    """ProcessBackend contract: pickling drops the compiled cache; a
    device-mesh driver refuses to travel to another process."""
    import pickle
    from jax.sharding import Mesh
    cfg, params = h2_wf
    drv = EnsembleDriver(VMCPropagator(cfg, tau=0.3), steps=4, donate=False)
    ens = drv.init(params, jax.random.PRNGKey(0), 8)
    drv.run_block(params, ens, jax.random.PRNGKey(1))   # populate cache
    assert drv._compiled
    clone = pickle.loads(pickle.dumps(drv))
    assert not clone._compiled
    _, stats = clone.run_block(params, ens, jax.random.PRNGKey(1))
    assert np.isfinite(float(stats.e_mean))
    meshed = EnsembleDriver(VMCPropagator(cfg, tau=0.3), steps=4,
                            mesh=Mesh(np.array(jax.devices()[:1]),
                                      ('walkers',)))
    with pytest.raises(TypeError, match='mesh'):
        pickle.dumps(meshed)


def test_feedback_routes_through_update_e_trial(h2_wf):
    """One damping knob: driver feedback == dmc.update_e_trial."""
    cfg, params = h2_wf
    prop = DMCPropagator(cfg, e_trial=-1.0, tau=0.02, damping=0.25)
    drv = EnsembleDriver(prop, steps=1)
    st = drv.init(params, jax.random.PRNGKey(0), 4)
    st2 = drv.feedback(st, -2.0)
    assert float(st2.e_trial) == pytest.approx(0.75 * -1.0 + 0.25 * -2.0)
    # VMC has no feedback hook: driver passes the state through untouched
    vdrv = EnsembleDriver(VMCPropagator(cfg), steps=1)
    ens = vdrv.init(params, jax.random.PRNGKey(0), 4)
    assert vdrv.feedback(ens, -5.0) is ens


def test_restart_ensemble_tiles_up_and_truncates(h2_wf):
    """n_kept < n_walkers tiles the reservoir; n_kept > truncates."""
    cfg, params = h2_wf
    kept = np.random.default_rng(0).normal(
        scale=1.0, size=(3, cfg.n_elec, 3)).astype(np.float32)
    ev = lambda r: evaluate_ensemble(cfg, params, r)[0]
    ens = restart_ensemble(kept, 8, ev)
    assert ens.r.shape == (8, cfg.n_elec, 3)
    np.testing.assert_array_equal(np.asarray(ens.r[:3]), kept)
    np.testing.assert_array_equal(np.asarray(ens.r[3:6]), kept)
    np.testing.assert_array_equal(np.asarray(ens.r[6:]), kept[:2])
    assert np.all(np.isfinite(np.asarray(ens.log_psi)))
    small = restart_ensemble(kept, 2, ev)
    assert small.r.shape == (2, cfg.n_elec, 3)
    np.testing.assert_array_equal(np.asarray(small.r), kept[:2])


def test_sampler_restart_uses_reservoir(h2_wf):
    """BlockSampler restart path goes through restart_ensemble."""
    from repro.runtime.samplers import BlockSampler
    cfg, params = h2_wf
    kept = np.random.default_rng(1).normal(
        scale=1.0, size=(5, cfg.n_elec, 3)).astype(np.float32)
    sampler = BlockSampler(VMCPropagator(cfg, tau=0.3), params,
                           n_walkers=12, steps=4)
    _, ens = sampler.init_state(0, seed=0, walkers=kept)
    assert ens.r.shape == (12, cfg.n_elec, 3)
    np.testing.assert_array_equal(np.asarray(ens.r[:5]), kept)


# ---------------------------------------------------------------------------
# RNG layout
# ---------------------------------------------------------------------------
def test_walker_keys_are_distinct_and_layout_invariant():
    pop = Population()
    keys = np.asarray(pop.walker_keys(jax.random.PRNGKey(7), 16))
    assert len({tuple(k) for k in keys}) == 16


def test_worker_streams_do_not_alias(h2_wf):
    """fold_in(worker_key, step) streams: different workers and steps give
    different sub-block keys (the old seed*2+1 / seed+step scheme aliased
    after 1000 sub-blocks)."""
    import jax.random as jr
    seen = set()
    for worker_id in range(4):
        wkey = jr.fold_in(jr.PRNGKey(0), worker_id)
        _, k_blocks = jr.split(wkey)
        for step in range(1500):
            seen.add(tuple(np.asarray(jr.fold_in(k_blocks, step))))
    assert len(seen) == 4 * 1500


# ---------------------------------------------------------------------------
# sharding: single-device vs walker-mesh consistency
# ---------------------------------------------------------------------------
def _consistency_check(n_shards=8, steps=20, n_walkers=64):
    """Run one VMC and one DMC block single-device and mesh-sharded;
    assert identical trajectories and reduction-tolerance-equal stats."""
    from jax.sharding import Mesh
    devices = jax.devices()
    assert len(devices) >= n_shards, f'need {n_shards} devices'
    mesh = Mesh(np.array(devices[:n_shards]), ('walkers',))
    cfg, params = build_wavefunction(*h2())
    props = [('vmc', VMCPropagator(cfg, tau=0.3)),
             ('dmc', DMCPropagator(cfg, e_trial=-1.17, tau=0.02))]
    for name, prop in props:
        d1 = EnsembleDriver(prop, steps, donate=False)
        dn = EnsembleDriver(prop, steps, mesh=mesh, donate=False)
        s1 = d1.init(params, jax.random.PRNGKey(0), n_walkers)
        sn = dn.init(params, jax.random.PRNGKey(0), n_walkers)
        s1, st1 = d1.run_block(params, s1, jax.random.PRNGKey(1))
        sn, stn = dn.run_block(params, sn, jax.random.PRNGKey(1))
        e1 = s1.ens if hasattr(s1, 'ens') else s1
        en = sn.ens if hasattr(sn, 'ens') else sn
        # per-walker RNG keyed on global indices: identical trajectories
        np.testing.assert_array_equal(np.asarray(e1.r), np.asarray(en.r),
                                      err_msg=f'{name}: walker paths')
        for field in ('weight', 'e_mean', 'e2_mean'):
            a, b = float(getattr(st1, field)), float(getattr(stn, field))
            assert a == pytest.approx(b, rel=1e-5, abs=1e-5), \
                (name, field, a, b)
        for k in st1.aux:
            a, b = float(st1.aux[k]), float(stn.aux[k])
            assert a == pytest.approx(b, rel=1e-5, abs=1e-5), (name, k, a, b)
    return True


needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason='needs XLA_FLAGS=--xla_force_host_platform_device_count=8')


@needs_8_devices
def test_sharded_block_matches_single_device_inprocess():
    assert _consistency_check()


@pytest.mark.slow
def test_sharded_block_matches_single_device_subprocess():
    """Same check in a subprocess with 8 virtual CPU devices, so the quick
    single-device environment still exercises the mesh path."""
    if len(jax.devices()) >= 8:
        pytest.skip('in-process variant already covers this')
    env = dict(os.environ,
               XLA_FLAGS='--xla_force_host_platform_device_count=8',
               PYTHONPATH=str(ROOT / 'src'))
    code = ('import sys; sys.path.insert(0, %r); '
            'import test_driver; '
            'assert test_driver._consistency_check(); print("CONSISTENT")'
            % str(ROOT / 'tests'))
    out = subprocess.run([sys.executable, '-c', code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert 'CONSISTENT' in out.stdout


# ---------------------------------------------------------------------------
# end-to-end CLI through the new API
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_qmc_run_cli_smoke(tmp_path):
    from repro.launch.qmc_run import main
    avg = main(['--system', 'h2', '--method', 'vmc', '--workers', '1',
                '--walkers', '8', '--steps', '10', '--blocks', '2',
                '--db', str(tmp_path / 'smoke.sqlite')])
    assert avg.n_blocks >= 2
    assert np.isfinite(avg.energy)


@pytest.mark.slow
def test_qmc_run_cli_sharded_smoke():
    """qmc_run --shards 2 in a subprocess with 2 virtual CPU devices."""
    env = dict(os.environ,
               XLA_FLAGS='--xla_force_host_platform_device_count=2',
               PYTHONPATH=str(ROOT / 'src'))
    out = subprocess.run(
        [sys.executable, '-m', 'repro.launch.qmc_run', '--system', 'h2',
         '--method', 'dmc', '--workers', '1', '--walkers', '8',
         '--steps', '5', '--blocks', '2', '--shards', '2'],
        env=env, capture_output=True, text=True, timeout=900, cwd=str(ROOT))
    assert out.returncode == 0, out.stderr[-4000:]
    assert 'E =' in out.stdout
