"""Single-electron-move propagator: SM-updated state vs fresh recompute.

The contract under test (ISSUE acceptance / DESIGN.md §6): after k <
cfg.sem_refresh sweeps of Sherman–Morrison updates + Newton–Schulz
correction, the running ``minv`` blocks and log-determinant agree with a
fresh ``slater_state``-style recompute to fp32 tolerance (Minv relative to
its own scale, logdet absolute), for BOTH spin blocks, including the
spin-block boundary electron j = n_up, and identically under a walker-mesh
sharded driver.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sem
from repro.core.driver import EnsembleDriver, Population
from repro.core.sem import SEMVMCPropagator, evaluate_sem
from repro.core.vmc import sample_positions
from repro.systems.molecule import build_wavefunction, h2, water

jax.config.update('jax_enable_x64', False)

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope='module')
def water_wf():
    return build_wavefunction(*water())


def _assert_tracks_fresh(ens, fresh, tol=1e-4):
    """Running minv/logdet vs fresh recompute: minv relative to the block's
    own magnitude (entries reach ~1e5 where 1e-4 absolute is below fp32
    resolution), logdet absolute."""
    for f in ('minv_up', 'minv_dn'):
        a = np.asarray(getattr(ens, f), np.float64)
        b = np.asarray(getattr(fresh, f), np.float64)
        if a.size == 0:
            continue
        scale = max(np.max(np.abs(b)), 1.0)
        assert np.max(np.abs(a - b)) / scale <= tol, f
    np.testing.assert_allclose(np.asarray(ens.logdet),
                               np.asarray(fresh.logdet), atol=tol)
    np.testing.assert_array_equal(np.asarray(ens.sign),
                                  np.asarray(fresh.sign))


@pytest.mark.parametrize('wf', [h2, water], ids=['h2', 'water'])
def test_sweeps_track_fresh_recompute(wf):
    """k=3 < sem_refresh=8 sweeps: both spin blocks' minv + logdet agree
    with a from-scratch evaluation of the final configuration."""
    cfg, params = build_wavefunction(*wf())
    prop = SEMVMCPropagator(cfg, step_size=0.4)
    drv = EnsembleDriver(prop, steps=3, donate=False)
    st = drv.init(params, jax.random.PRNGKey(0), 8)
    st, stats = drv.run_block(params, st, jax.random.PRNGKey(1))
    assert 0.0 < float(stats.aux['accept']) < 1.0
    assert np.isfinite(float(stats.e_mean))
    _assert_tracks_fresh(st.ens, evaluate_sem(cfg, params, st.ens.r))


def test_sweeps_track_fresh_recompute_kernel_method(water_wf):
    """Same contract through cfg.method='kernel': the Pallas SM-update
    branch of _apply_update (padding + traced electron index inside the
    sweep scan, under the driver) and the Pallas MO-product path."""
    import dataclasses
    cfg, params = water_wf
    cfg = dataclasses.replace(cfg, method='kernel', kernel_tiles=(8, 8, 8))
    prop = SEMVMCPropagator(cfg, step_size=0.4)
    drv = EnsembleDriver(prop, steps=2, donate=False)
    st = drv.init(params, jax.random.PRNGKey(0), 4)
    st, stats = drv.run_block(params, st, jax.random.PRNGKey(1))
    assert np.isfinite(float(stats.e_mean))
    _assert_tracks_fresh(st.ens, evaluate_sem(cfg, params, st.ens.r))


def test_kernel_and_ref_sweeps_walk_identically(water_wf):
    """Inside ``_sweep_spin_block`` the MO method only selects the
    ``_apply_update`` branch (per-move values come from
    ``eval_ao_values`` either way), so a Pallas-update sweep must
    reproduce the jnp-ref sweep bitwise: positions and inverse."""
    import dataclasses
    cfg, params = water_wf
    r = sample_positions(params, jax.random.PRNGKey(7), 4, cfg.n_elec)
    ens = evaluate_sem(cfg, params, r)
    wkeys = Population().walker_keys(jax.random.PRNGKey(9), 4)
    outs = {}
    for method in ('dense', 'kernel'):
        c = dataclasses.replace(cfg, method=method)
        A_up, _ = sem._mo_blocks(c, params)
        outs[method], _ = sem._sweep_spin_block(
            c, params, A_up, 0, c.n_up, wkeys, 0.4, (ens.r, ens.minv_up))
    for a, b in zip(outs['dense'], outs['kernel']):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_spin_boundary_electron_update(water_wf):
    """One trial of exactly electron j = n_up (the first spin-down
    electron): the dn-block inverse tracks a fresh recompute."""
    cfg, params = water_wf
    r = sample_positions(params, jax.random.PRNGKey(3), 4, cfg.n_elec)
    ens = evaluate_sem(cfg, params, r)
    pop = Population()
    wkeys = pop.walker_keys(jax.random.PRNGKey(5), 4)
    _, A_dn = sem._mo_blocks(cfg, params)
    (r2, minv_dn), acc = sem._sweep_spin_block(
        cfg, params, A_dn, cfg.n_up, 1, wkeys, 0.5, (ens.r, ens.minv_dn))
    assert np.any(np.asarray(r2) != np.asarray(r)), 'no move accepted'
    # only electron n_up may have moved
    moved = np.any(np.asarray(r2) != np.asarray(r), axis=-1)  # (W, n_e)
    assert not np.any(np.delete(moved, cfg.n_up, axis=1))
    fresh = evaluate_sem(cfg, params, r2)
    scale = max(np.max(np.abs(np.asarray(fresh.minv_dn))), 1.0)
    assert np.max(np.abs(np.asarray(minv_dn, np.float64)
                         - np.asarray(fresh.minv_dn, np.float64))) / scale \
        <= 1e-4


def test_refresh_resets_fp32_drift(water_wf):
    """Drift regression: at step = sem_refresh the full recompute kicks in
    (sweep counter wraps to 0) and the state matches a fresh evaluation to
    tighter-than-drift tolerance; one step before, the corrector alone
    keeps it within the 1e-4 contract."""
    cfg, params = water_wf
    import dataclasses
    cfg = dataclasses.replace(cfg, sem_refresh=4)
    prop = SEMVMCPropagator(cfg, step_size=0.4)

    def run(steps):
        drv = EnsembleDriver(prop, steps=steps, donate=False)
        st = drv.init(params, jax.random.PRNGKey(0), 8)
        st, _ = drv.run_block(params, st, jax.random.PRNGKey(1))
        return st

    st3 = run(3)                       # corrector only
    assert int(st3.sweeps) == 3
    _assert_tracks_fresh(st3.ens, evaluate_sem(cfg, params, st3.ens.r))
    st4 = run(4)                       # step 4 ran the full refresh
    assert int(st4.sweeps) == 0
    fresh4 = evaluate_sem(cfg, params, st4.ens.r)
    _assert_tracks_fresh(st4.ens, fresh4, tol=1e-5)


def test_log_psi_and_e_loc_match_all_electron_evaluation(water_wf):
    """The SEM state's log|Psi|/E_L equal the all-electron pipeline's on
    the same configurations (same wavefunction, different kinetics)."""
    from repro.core.vmc import evaluate_ensemble
    cfg, params = water_wf
    r = sample_positions(params, jax.random.PRNGKey(11), 6, cfg.n_elec)
    ens = evaluate_sem(cfg, params, r)
    ref, _ = evaluate_ensemble(cfg, params, r)
    np.testing.assert_allclose(np.asarray(ens.log_psi),
                               np.asarray(ref.log_psi), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(ens.e_loc),
                               np.asarray(ref.e_loc), rtol=1e-4, atol=1e-3)


def test_sem_blocksampler_roundtrip(water_wf):
    """SEMVMCPropagator behind the generic runtime BlockSampler: sub-block
    stats and reservoir payloads come out well-formed, restart works."""
    from repro.runtime.samplers import BlockSampler
    cfg, params = water_wf
    sampler = BlockSampler(SEMVMCPropagator(cfg, step_size=0.4), params,
                           n_walkers=6, steps=3)
    state = sampler.init_state(0, seed=0)
    state, acc, r, e_loc = sampler.run_subblock(state, 0)
    assert acc.is_valid() and acc.weight == 3 * 6
    assert r.shape == (6, cfg.n_elec, 3) and e_loc.shape == (6,)
    restart = sampler.init_state(1, seed=0, walkers=r[:2])
    np.testing.assert_array_equal(np.asarray(restart[1].ens.r[:2]), r[:2])


# ---------------------------------------------------------------------------
# sharding: single-device vs walker-mesh consistency
# ---------------------------------------------------------------------------
def _sem_consistency_check(n_shards=8, steps=5, n_walkers=32):
    """Sharded SEM block == single-device block (bitwise trajectories,
    reduction-tolerance stats), and the sharded running inverses still
    track a fresh recompute to the 1e-4 contract."""
    from jax.sharding import Mesh
    devices = jax.devices()
    assert len(devices) >= n_shards, f'need {n_shards} devices'
    mesh = Mesh(np.array(devices[:n_shards]), ('walkers',))
    cfg, params = build_wavefunction(*water())
    prop = SEMVMCPropagator(cfg, step_size=0.4)
    d1 = EnsembleDriver(prop, steps, donate=False)
    dn = EnsembleDriver(prop, steps, mesh=mesh, donate=False)
    s1 = d1.init(params, jax.random.PRNGKey(0), n_walkers)
    sn = dn.init(params, jax.random.PRNGKey(0), n_walkers)
    s1, st1 = d1.run_block(params, s1, jax.random.PRNGKey(1))
    sn, stn = dn.run_block(params, sn, jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(s1.ens.r),
                                  np.asarray(sn.ens.r))
    for field in ('weight', 'e_mean', 'e2_mean'):
        a, b = float(getattr(st1, field)), float(getattr(stn, field))
        assert a == pytest.approx(b, rel=1e-5, abs=1e-5), (field, a, b)
    for k in st1.aux:
        a, b = float(st1.aux[k]), float(stn.aux[k])
        assert a == pytest.approx(b, rel=1e-5, abs=1e-5), (k, a, b)
    _assert_tracks_fresh(jax.device_get(sn.ens),
                         evaluate_sem(cfg, params, sn.ens.r))
    return True


needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason='needs XLA_FLAGS=--xla_force_host_platform_device_count=8')


@needs_8_devices
def test_sem_sharded_matches_single_device_inprocess():
    assert _sem_consistency_check()


@pytest.mark.slow
def test_sem_sharded_matches_single_device_subprocess():
    """Same check under 8 virtual CPU devices when the current session is
    single-device (mirrors test_driver's subprocess pattern)."""
    if len(jax.devices()) >= 8:
        pytest.skip('in-process variant already covers this')
    env = dict(os.environ,
               XLA_FLAGS='--xla_force_host_platform_device_count=8',
               PYTHONPATH=str(ROOT / 'src'))
    code = ('import sys; sys.path.insert(0, %r); '
            'import test_sem; '
            'assert test_sem._sem_consistency_check(); print("CONSISTENT")'
            % str(ROOT / 'tests'))
    out = subprocess.run([sys.executable, '-c', code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert 'CONSISTENT' in out.stdout


@pytest.mark.slow
def test_qmc_run_cli_sem_smoke(tmp_path):
    """qmc_run --method sem-vmc end to end through manager/db/workers."""
    from repro.launch.qmc_run import main
    avg = main(['--system', 'h2', '--method', 'sem-vmc', '--workers', '1',
                '--walkers', '8', '--steps', '5', '--blocks', '2',
                '--db', str(tmp_path / 'sem.sqlite')])
    assert avg.n_blocks >= 2
    assert np.isfinite(avg.energy)
