"""End-to-end QMC physics: VMC/DMC on exactly-solvable small systems."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dmc import DMCPropagator, init_dmc, update_e_trial
from repro.core.driver import EnsembleDriver
from repro.core.vmc import VMCPropagator, init_walkers
from repro.systems.molecule import build_wavefunction, h2, hydrogen


def vmc_driver(cfg, steps, tau):
    return EnsembleDriver(VMCPropagator(cfg, tau=tau), steps, donate=False)


def dmc_driver(cfg, steps, tau):
    # the running E_T lives in DMCState, so e_trial=0.0 here is inert
    return EnsembleDriver(DMCPropagator(cfg, e_trial=0.0, tau=tau), steps,
                          donate=False)


@pytest.fixture(scope='module')
def h_wf():
    # no Jastrow for a 1-electron system (e-n term only biases VMC here)
    from repro.core.jastrow import JastrowParams
    jz = JastrowParams(b_ee=jnp.float32(1.0), b_en=jnp.float32(1.0),
                       a_en=jnp.float32(0.0))
    return build_wavefunction(*hydrogen(), jastrow=jz)


def test_vmc_hydrogen_energy(h_wf):
    """VMC with a 6-31G-quality orbital: E within ~0.01 Ha of -0.5."""
    cfg, params = h_wf
    key = jax.random.PRNGKey(0)
    ens = init_walkers(cfg, params, key, 256, spread=1.0)
    drv = vmc_driver(cfg, steps=120, tau=0.35)
    ens, _ = drv.run_block(params, ens, jax.random.PRNGKey(1))  # equilibrate
    ens, stats = drv.run_block(params, ens, jax.random.PRNGKey(2))
    assert abs(float(stats.e_mean) - (-0.5)) < 0.015
    assert 0.3 < float(stats.aux['accept']) < 1.0


def test_dmc_hydrogen_exact(h_wf):
    """DMC is exact for a nodeless state: E -> -0.5 within stat error."""
    cfg, params = h_wf
    key = jax.random.PRNGKey(3)
    ens = init_walkers(cfg, params, key, 256, spread=1.0)
    vdrv = vmc_driver(cfg, steps=80, tau=0.35)
    ens, vstats = vdrv.run_block(params, ens, jax.random.PRNGKey(4))

    st = init_dmc(ens, e_trial=float(vstats.e_mean), window=10)
    ddrv = dmc_driver(cfg, steps=150, tau=0.02)
    st, _ = ddrv.run_block(params, st, jax.random.PRNGKey(5))  # equilibrate
    es = []
    for i in range(4):
        st, ds = ddrv.run_block(params, st, jax.random.PRNGKey(6 + i))
        st = update_e_trial(st, ds.e_mean)
        es.append(float(ds.e_mean))
    assert abs(np.mean(es) - (-0.5)) < 0.01, es


def test_dmc_h2_below_vmc(h_wf):
    """DMC energy must be <= VMC energy (variational) for H2, and near
    the exact -1.174 Ha (nodeless ground state => exact up to tau bias)."""
    cfg, params = build_wavefunction(*h2())
    key = jax.random.PRNGKey(7)
    ens = init_walkers(cfg, params, key, 192)
    vdrv = vmc_driver(cfg, steps=120, tau=0.25)
    ens, _ = vdrv.run_block(params, ens, jax.random.PRNGKey(18))  # equil
    ens, vstats = vdrv.run_block(params, ens, jax.random.PRNGKey(8))
    e_vmc = float(vstats.e_mean)

    st = init_dmc(ens, e_trial=e_vmc, window=10)
    ddrv = dmc_driver(cfg, steps=120, tau=0.02)
    for i in range(3):                                    # equilibrate
        st, ds = ddrv.run_block(params, st, jax.random.PRNGKey(9 + i))
        st = update_e_trial(st, ds.e_mean)
    es = []
    for i in range(4):
        st, ds = ddrv.run_block(params, st, jax.random.PRNGKey(30 + i))
        st = update_e_trial(st, ds.e_mean)
        es.append(float(ds.e_mean))
    e_dmc = float(np.mean(es))
    assert e_dmc < e_vmc + 0.005
    # tau=0.02 time-step bias + mixed estimator: 0.06 Ha band around exact
    assert abs(e_dmc - (-1.174)) < 0.06, (e_vmc, e_dmc)


def test_population_is_constant_through_dmc():
    cfg, params = build_wavefunction(*h2())
    ens = init_walkers(cfg, params, jax.random.PRNGKey(0), 64)
    st = init_dmc(ens, e_trial=-1.1)
    ddrv = dmc_driver(cfg, steps=25, tau=0.02)
    st2, _ = ddrv.run_block(params, st, jax.random.PRNGKey(1))
    assert st2.ens.r.shape == ens.r.shape                   # constant M


def test_dmc_block_reports_ao_fill():
    """DMC's aux carries the active AOs per electron, as VMC's does."""
    cfg, params = build_wavefunction(*h2())
    st = init_dmc(init_walkers(cfg, params, jax.random.PRNGKey(0), 16),
                  e_trial=-1.1)
    _, stats = dmc_driver(cfg, steps=4, tau=0.02).run_block(
        params, st, jax.random.PRNGKey(1))
    assert 0.0 < float(stats.aux['ao_fill']) <= cfg.basis.n_ao


def test_blocks_are_reproducible():
    """Same key => bitwise-identical block stats (determinism contract)."""
    cfg, params = build_wavefunction(*h2())
    ens = init_walkers(cfg, params, jax.random.PRNGKey(0), 32)
    drv = vmc_driver(cfg, steps=20, tau=0.3)
    _, s1 = drv.run_block(params, ens, jax.random.PRNGKey(5))
    _, s2 = drv.run_block(params, ens, jax.random.PRNGKey(5))
    assert float(s1.e_mean) == float(s2.e_mean)
