"""Multideterminant wavefunctions: shared-inverse SMW vs naive slogdet.

The contracts under test (ISSUE acceptance / DESIGN.md §8):

* an n_det = 1 (reference-only) expansion reproduces the single-
  determinant pipeline BITWISE — evaluation, a VMC driver block, and a
  single-electron-move sweep;
* every determinant ratio, and the CI-weighted grad/Laplacian
  contractions, match a naive per-determinant slogdet/inverse oracle;
* the SEM-maintained tables/ratios track a fresh recompute to the 1e-4
  fp32 contract over a sweep of Sherman–Morrison + rank-1 table updates;
* the local energy agrees with the autodiff oracle and the rank-k column
  replacement of ``slater.det_ratio_rank_k`` matches refactorization.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import multidet, slater
from repro.core.driver import EnsembleDriver, Population
from repro.core.vmc import VMCPropagator, sample_positions
from repro.core.wavefunction import local_energy_autodiff, psi_state
from repro.systems import build_system
from repro.systems.bench import synthetic_ci
from repro.systems.molecule import build_wavefunction, water

jax.config.update('jax_enable_x64', False)


@pytest.fixture(scope='module')
def water_ci():
    """Water with a 6-determinant synthetic CISD-style expansion."""
    return build_system('water', n_det=6, ci_seed=3)


@pytest.fixture(scope='module')
def water_pair():
    """Same params (7 MO rows): single-det config + reference-only CI."""
    mol, shells = water()
    cfg, params = build_wavefunction(mol, shells, n_orb=7)
    ci = multidet.from_excitations([1.0], [], mol.n_up, mol.n_dn, 7)
    return cfg, dataclasses.replace(cfg, ci=ci), params


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------
def test_from_excitations_validates():
    with pytest.raises(ValueError, match='not occupied'):
        multidet.from_excitations([1., .1], [(([7], [8]), ([], []))],
                                  5, 5, 9)
    with pytest.raises(ValueError, match='not virtual'):
        multidet.from_excitations([1., .1], [(([0], [2]), ([], []))],
                                  5, 5, 9)
    with pytest.raises(ValueError, match='duplicate'):
        multidet.from_excitations([1., .1], [(([0, 0], [5, 6]), ([], []))],
                                  5, 5, 9)


def test_det_file_roundtrip():
    text = """
    # CISD-style toy file: coeff  up-occ | dn-occ
     1.00  0 1 | 0 1
    -0.20  0 3 | 0 1    # single: up 1 -> 3
     0.10  2 3 | 0 1    # double: up 0,1 -> 2,3
     0.05  0 1 | 1 2    # single: dn 0 -> 2
    """
    mdw = multidet.from_det_file(text, n_up=2, n_dn=2, n_orb=4)
    assert mdw.n_det == 4 and mdw.k == 2
    # file coefficients are in the sorted-occupation convention; internal
    # storage is hole-row-replacement, so det 3 (dn rows [2, 1]: one
    # inversion) picks up a -1 parity
    np.testing.assert_array_equal(mdw.coeffs,
                                  np.float32([1.0, -0.2, 0.1, -0.05]))
    # det 1: up hole {1} -> particle {3}
    assert mdw.holes_up[1, 0] == 1 and mdw.parts_up[1, 0] == 3
    # det 2: up holes {0,1} -> particles {2,3}
    np.testing.assert_array_equal(mdw.holes_up[2], [0, 1])
    np.testing.assert_array_equal(mdw.parts_up[2], [2, 3])
    # det 3: dn hole {0} -> {2}; its up side is all padding (sentinels)
    assert mdw.holes_dn[3, 0] == 0 and mdw.parts_dn[3, 0] == 2
    assert mdw.holes_up[3, 0] == 2 and mdw.parts_up[3, 0] == 4

    with pytest.raises(ValueError, match='reference determinant'):
        multidet.from_det_file(' 1.0  1 2 | 0 1', 2, 2, 4)
    # a duplicated orbital index must raise, not collapse in the set
    with pytest.raises(ValueError, match='occupation counts'):
        multidet.from_det_file(' 1.0  0 1 | 0 1\n 0.5  0 1 1 | 0 1',
                               2, 2, 4)


def test_row_parity_matches_sorted_determinant_convention():
    """_row_parity: the hole-row determinant equals parity x the
    sorted-occupation determinant, checked against numpy slogdet."""
    rng = np.random.default_rng(11)
    V = rng.standard_normal((8, 4))          # orbital values, 4 electrons
    for holes, parts in ([(0,), (6,)], [(3,), (7,)], [(0, 2), (5, 7)],
                         [(1, 3), (4, 6)]):
        rows_pos = list(range(4))
        for h, p in zip(holes, parts):
            rows_pos[h] = p
        d_pos = np.linalg.det(V[rows_pos])
        d_sorted = np.linalg.det(V[sorted(rows_pos)])
        parity = multidet._row_parity(holes, parts, 4)
        assert d_pos == pytest.approx(parity * d_sorted, rel=1e-10)


# ---------------------------------------------------------------------------
# ratios + grad/lap vs naive per-determinant oracle
# ---------------------------------------------------------------------------
def _naive_spin(C_blk, holes, parts, n_occ):
    """Oracle: build every excited matrix, factorize it, contract."""
    C = np.asarray(C_blk, np.float64)
    s0, l0 = np.linalg.slogdet(C[:n_occ, :, 0])
    ratios, grads, laps = [], [], []
    for d in range(holes.shape[0]):
        rows = list(range(n_occ))
        for a in range(holes.shape[1]):
            if holes[d, a] < n_occ:
                rows[holes[d, a]] = parts[d, a]
        D = C[rows, :, 0]
        sI, lI = np.linalg.slogdet(D)
        ratios.append(sI * s0 * np.exp(lI - l0))
        MI = np.linalg.inv(D)
        grads.append(np.einsum('iej,ei->ej', C[rows][..., 1:4], MI))
        laps.append(np.einsum('ie,ei->e', C[rows][..., 4], MI))
    return np.array(ratios), np.array(grads), np.array(laps)


def test_ratios_and_gradients_match_naive_oracle(water_ci):
    """Shared-inverse ratios AND the CI-weighted Woodbury grad/lap
    contractions vs explicit per-determinant factorizations."""
    cfg, params = water_ci
    ci = cfg.ci
    r = sample_positions(params, jax.random.PRNGKey(1), 2, cfg.n_elec)[0]
    from repro.core.wavefunction import _ci_blocks, _mo_tensor
    C, _ = _mo_tensor(cfg, params, r)
    up_all, dn_all = _ci_blocks(cfg, C)

    sign, logdet, grad, lap = multidet.ci_assemble(ci, up_all, dn_all,
                                                   cfg.ns_steps)
    ru, gu, qu = _naive_spin(up_all, ci.holes_up, ci.parts_up, cfg.n_up)
    rd, gd, qd = _naive_spin(dn_all, ci.holes_dn, ci.parts_dn, cfg.n_dn)

    up_blk = multidet.spin_block_ci(up_all, ci.holes_up, ci.parts_up)
    dn_blk = multidet.spin_block_ci(dn_all, ci.holes_dn, ci.parts_dn)
    np.testing.assert_allclose(np.asarray(up_blk.ratios), ru,
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dn_blk.ratios), rd,
                               rtol=1e-4, atol=1e-5)

    c = np.asarray(ci.coeffs, np.float64)
    S = np.sum(c * ru * rd)
    w = c * ru * rd / S
    g_ref = np.concatenate([np.einsum('d,dej->ej', w, gu),
                            np.einsum('d,dej->ej', w, gd)], axis=0)
    q_ref = np.concatenate([np.einsum('d,de->e', w, qu),
                            np.einsum('d,de->e', w, qd)], axis=0)
    np.testing.assert_allclose(np.asarray(grad), g_ref, rtol=1e-3,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(lap), q_ref, rtol=1e-3,
                               atol=2e-3)
    s0u, _ = np.linalg.slogdet(np.asarray(up_all, np.float64)[:cfg.n_up, :, 0])
    s0d, _ = np.linalg.slogdet(np.asarray(dn_all, np.float64)[:cfg.n_dn, :, 0])
    assert float(sign) == pytest.approx(s0u * s0d * np.sign(S))


def test_rank_k_column_replacement_matches_refactorization():
    """slater.det_ratio_rank_k: ratio + Woodbury inverse vs slogdet/inv."""
    rng = np.random.default_rng(4)
    n, k = 7, 3
    D = rng.standard_normal((n, n)) + 2.0 * np.eye(n)
    M = jnp.asarray(np.linalg.inv(D), jnp.float32)
    js = jnp.asarray([1, 4, 6])
    Phi = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    ratio, M2 = slater.det_ratio_rank_k(M, Phi, js)
    Dn = D.copy()
    for a, j in enumerate([1, 4, 6]):
        Dn[:, j] = np.asarray(Phi)[a]
    assert float(ratio) == pytest.approx(
        np.linalg.det(Dn) / np.linalg.det(D), rel=1e-4)
    np.testing.assert_allclose(np.asarray(M2), np.linalg.inv(Dn),
                               rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# n_det = 1 bitwise equivalence with the single-determinant path
# ---------------------------------------------------------------------------
def test_ndet1_psi_state_bitwise(water_pair):
    cfg1, cfgm, params = water_pair
    r = sample_positions(params, jax.random.PRNGKey(0), 2, cfg1.n_elec)[0]
    s1 = psi_state(cfg1, params, r)
    sm = psi_state(cfgm, params, r)
    for f in s1._fields:
        np.testing.assert_array_equal(np.asarray(getattr(s1, f)),
                                      np.asarray(getattr(sm, f)), err_msg=f)


def test_ndet1_vmc_block_bitwise(water_pair):
    cfg1, cfgm, params = water_pair
    trajs = []
    for cfg in (cfg1, cfgm):
        drv = EnsembleDriver(VMCPropagator(cfg, tau=0.3), steps=5,
                             donate=False)
        ens = drv.init(params, jax.random.PRNGKey(0), 4)
        ens, stats = drv.run_block(params, ens, jax.random.PRNGKey(1))
        trajs.append((np.asarray(ens.r), float(stats.e_mean)))
    np.testing.assert_array_equal(trajs[0][0], trajs[1][0])
    assert trajs[0][1] == trajs[1][1]


def test_ndet1_sem_sweep_bitwise(water_pair):
    from repro.core.sem import SEMVMCPropagator
    cfg1, cfgm, params = water_pair
    outs = []
    for cfg in (cfg1, cfgm):
        drv = EnsembleDriver(SEMVMCPropagator(cfg, step_size=0.4), steps=3,
                             donate=False)
        st = drv.init(params, jax.random.PRNGKey(0), 4)
        st, stats = drv.run_block(params, st, jax.random.PRNGKey(1))
        outs.append((np.asarray(st.ens.r), np.asarray(st.ens.logdet),
                     float(stats.e_mean)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
    assert outs[0][2] == outs[1][2]


# ---------------------------------------------------------------------------
# local energy: autodiff oracle + all-electron/SEM consistency
# ---------------------------------------------------------------------------
def test_multidet_local_energy_vs_autodiff(water_ci):
    cfg, params = water_ci
    r = sample_positions(params, jax.random.PRNGKey(2), 2, cfg.n_elec)[0]
    st = psi_state(cfg, params, r)
    e_ad = local_energy_autodiff(cfg, params, r)
    assert float(st.e_loc) == pytest.approx(float(e_ad), rel=2e-3,
                                            abs=5e-3)


def test_sem_multidet_matches_all_electron_evaluation(water_ci):
    """The SEM ensemble's log|Psi|/E_L equal the all-electron multidet
    pipeline's on the same configurations."""
    from repro.core.sem import evaluate_sem
    from repro.core.vmc import evaluate_ensemble
    cfg, params = water_ci
    r = sample_positions(params, jax.random.PRNGKey(5), 6, cfg.n_elec)
    ens = evaluate_sem(cfg, params, r)
    ref, _ = evaluate_ensemble(cfg, params, r)
    np.testing.assert_allclose(np.asarray(ens.log_psi),
                               np.asarray(ref.log_psi), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(ens.e_loc),
                               np.asarray(ref.e_loc), rtol=1e-4, atol=2e-3)


# ---------------------------------------------------------------------------
# SEM sweep: maintained tables/ratios vs fresh per-determinant slogdet
# ---------------------------------------------------------------------------
def test_sem_sweep_smw_ratios_track_fresh_slogdet(water_ci):
    """A full up-block sweep of SM inverse + rank-1 table updates: the
    carried P table and determinant ratios match a from-scratch
    slogdet-based recompute of the final configuration to <= 1e-4
    (relative to each block's own scale)."""
    from repro.core import sem
    cfg, params = water_ci
    ci = cfg.ci
    r = sample_positions(params, jax.random.PRNGKey(7), 6, cfg.n_elec)
    ens = sem.evaluate_sem(cfg, params, r)
    wkeys = Population().walker_keys(jax.random.PRNGKey(9), 6)
    A_up, _ = sem._mo_blocks(cfg, params)
    carry = (ens.r, ens.minv_up, ens.p_up, ens.rdet_up)
    (r2, minv_up, P, rdet), acc = sem._sweep_spin_block(
        cfg, params, A_up, 0, cfg.n_up, wkeys, 0.4, carry,
        ci_args=(ci.holes_up, ci.parts_up, ens.rdet_dn))
    assert np.any(np.asarray(r2) != np.asarray(ens.r)), 'no move accepted'

    from repro.core.wavefunction import _ci_blocks, _mo_tensor_ensemble
    Cw, _ = _mo_tensor_ensemble(cfg, params, r2)
    up_all, _ = _ci_blocks(cfg, Cw)
    fresh = np.stack([_naive_spin(np.asarray(up_all)[w], ci.holes_up,
                                  ci.parts_up, cfg.n_up)[0]
                      for w in range(6)])
    rdet = np.asarray(rdet, np.float64)
    scale = max(np.max(np.abs(fresh)), 1.0)
    assert np.max(np.abs(rdet - fresh)) / scale <= 1e-4

    # the maintained table itself tracks V @ Minv_fresh
    Vu = np.asarray(up_all[..., 0], np.float64)
    M_fresh = np.linalg.inv(Vu[:, :cfg.n_up, :])
    P_fresh = np.einsum('wvh,whe->wve', Vu, M_fresh)
    P_fresh[:, :cfg.n_up] = np.eye(cfg.n_up)[None]
    P_scale = max(np.max(np.abs(P_fresh)), 1.0)
    assert np.max(np.abs(np.asarray(P, np.float64) - P_fresh)) / P_scale \
        <= 1e-4


def test_sem_multidet_driver_block_consistent(water_ci):
    """Full propagate blocks: finite stats, and the rebuilt ensemble
    tables/ratios equal a fresh evaluate_sem of the final positions."""
    from repro.core.sem import SEMVMCPropagator, evaluate_sem
    cfg, params = water_ci
    drv = EnsembleDriver(SEMVMCPropagator(cfg, step_size=0.4), steps=3,
                         donate=False)
    st = drv.init(params, jax.random.PRNGKey(0), 6)
    st, stats = drv.run_block(params, st, jax.random.PRNGKey(1))
    assert 0.0 < float(stats.aux['accept']) < 1.0
    assert np.isfinite(float(stats.e_mean))
    fresh = evaluate_sem(cfg, params, st.ens.r)
    for f in ('rdet_up', 'rdet_dn', 'log_psi', 'e_loc'):
        a = np.asarray(getattr(st.ens, f), np.float64)
        b = np.asarray(getattr(fresh, f), np.float64)
        scale = max(np.max(np.abs(b)), 1.0)
        assert np.max(np.abs(a - b)) / scale <= 2e-4, f


# ---------------------------------------------------------------------------
# spec / CLI
# ---------------------------------------------------------------------------
def test_runspec_n_det_validation_and_key():
    from repro.launch.spec import RunSpec
    with pytest.raises(ValueError, match='n_det'):
        RunSpec(n_det=0)
    spec1 = RunSpec(system='h2', method='vmc')
    spec2 = RunSpec(system='h2', method='vmc', n_det=4)
    from repro.launch.spec import build_run
    run1 = build_run(spec1)
    run2 = build_run(spec2)
    assert run1.run_key != run2.run_key
    assert run2.cfg.ci is not None and run2.cfg.ci.n_det == 4
    # the expansion CONTENT is critical data: a different synthetic draw
    # (same n_det, different seed) must land in different database rows
    run2b = build_run(spec2.replace(seed=1))
    assert run2b.run_key != run2.run_key


def test_synthetic_ci_exhaustion_raises():
    with pytest.raises(ValueError, match='distinct excitations'):
        synthetic_ci(1, 0, 2, 50, seed=0)   # only 1 virtual: 1 single
    with pytest.raises(ValueError, match='no virtual orbitals'):
        synthetic_ci(2, 0, 2, 3, seed=0)    # no virtuals at all


@pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason='needs XLA_FLAGS=--xla_force_host_platform_device_count=8')
def test_multidet_sem_sharded_matches_single_device(water_ci):
    """Walker-mesh sharding of the multidet SEM state (inverse + tables +
    per-det ratios are all walker-major leaves): sharded block == single
    device, bitwise trajectories and equal tables."""
    from jax.sharding import Mesh
    from repro.core.sem import SEMVMCPropagator
    cfg, params = water_ci
    mesh = Mesh(np.array(jax.devices()[:8]), ('walkers',))
    prop = SEMVMCPropagator(cfg, step_size=0.4)
    d1 = EnsembleDriver(prop, steps=3, donate=False)
    dn = EnsembleDriver(prop, steps=3, mesh=mesh, donate=False)
    s1 = d1.init(params, jax.random.PRNGKey(0), 16)
    sn = dn.init(params, jax.random.PRNGKey(0), 16)
    s1, st1 = d1.run_block(params, s1, jax.random.PRNGKey(1))
    sn, stn = dn.run_block(params, sn, jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(s1.ens.r),
                                  np.asarray(sn.ens.r))
    for f in ('rdet_up', 'rdet_dn', 'p_up', 'p_dn'):
        np.testing.assert_allclose(np.asarray(getattr(s1.ens, f)),
                                   np.asarray(getattr(sn.ens, f)),
                                   rtol=1e-5, atol=1e-5, err_msg=f)
    assert float(st1.e_mean) == pytest.approx(float(stn.e_mean), rel=1e-5,
                                              abs=1e-5)


@pytest.mark.slow
def test_qmc_run_cli_n_det_smoke(tmp_path):
    """qmc_run --n-det end to end through manager/db/workers (sem-vmc)."""
    from repro.launch.qmc_run import main
    avg = main(['--system', 'h2', '--method', 'sem-vmc', '--n-det', '4',
                '--workers', '1', '--walkers', '8', '--steps', '5',
                '--blocks', '2', '--db', str(tmp_path / 'md.sqlite')])
    assert avg.n_blocks >= 2
    assert np.isfinite(avg.energy)
