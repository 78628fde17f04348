"""Fused-sweep kernel vs scan oracle: bitwise parity + autotuner.

``kernels.fused_sweep`` runs one spin block's whole Metropolis sweep in a
single dispatch — ``ref.fused_sweep_ref`` as one ``lax.scan``, ``kernel.
fused_sweep_call`` as one walker-tiled Pallas call.  Both paths execute
the SAME ``ref._move_step`` per electron, so the kernel must reproduce
the oracle MOVE-FOR-MOVE BITWISE at fp32: positions, inverse and the
full accept matrix — including ragged walker tiles (W not
a multiple of tile_w: padded walkers carry logu=+1e30 and never accept),
degenerate all-reject / all-accept sweeps, multidet (n_det > 1) P-table
updates, and under an 8-virtual-device walker mesh.

The measured tile autotuner's contract rides along: a cache hit returns
the stored tile with NO re-measurement (pinned via ``build_count`` and an
injectable measure hook), the key spans (n_e, W, dtype, backend), and a
corrupt or stale-schema cache re-measures instead of crashing.
"""
import dataclasses
import json
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
from repro.kernels.fused_sweep import autotune
from repro.kernels.fused_sweep.ops import fused_sweep_block
from repro.systems import build_system
from repro.systems.molecule import build_wavefunction, water

jax.config.update('jax_enable_x64', False)

ROOT = Path(__file__).resolve().parents[1]


def _state(cfg, params, W, seed=2):
    r = sample_positions(params, jax.random.PRNGKey(seed), W, cfg.n_elec)
    return evaluate_sem(cfg, params, r)


@pytest.fixture(scope='module')
def water_wf():
    return build_wavefunction(*water())


def _block_operands(cfg, params, ens, seed=4, step=0.4):
    """Real up-block sweep operands: proposals off the current positions,
    proposal MO values through the wavefunction's own panel."""
    W, n_up = ens.r.shape[0], cfg.n_up
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    r_prop = ens.r[:, :n_up] + step * jax.random.normal(
        k1, (W, n_up, 3), jnp.float32)
    A_up, _ = sem._mo_blocks(cfg, params)
    phi = sem._fused_phi_block(cfg, params, A_up,
                               r_prop.reshape(W * n_up, 3)
                               ).reshape(W, n_up, -1)
    en_delta = 0.05 * jax.random.normal(k2, (W, n_up), jnp.float32)
    logu = jnp.log(jax.random.uniform(k3, (W, n_up),
                                      minval=1e-6, maxval=1.0))
    return phi, r_prop, en_delta, logu


def _run_both(cfg, params, ens, tile_w, logu_override=None, ci_ops=None,
              seed=4):
    """The same sweep through the scan oracle and the Pallas kernel."""
    phi, r_prop, en_delta, logu = _block_operands(cfg, params, ens, seed)
    if logu_override is not None:
        logu = jnp.full_like(logu, logu_override)
    outs = {}
    for use_kernel in (False, True):
        outs[use_kernel] = fused_sweep_block(
            ens.minv_up, phi, ens.r, r_prop, en_delta, logu,
            params.jastrow.b_ee, ci_ops, offset=0,
            n_up=cfg.n_up, use_kernel=use_kernel, tile_w=tile_w)
    return outs[False], outs[True], r_prop


def _assert_bitwise(ref_out, ker_out):
    names = ('r', 'minv', 'P', 'rdet', 'accept')
    for name, a, b in zip(names, ref_out, ker_out):
        assert a.shape == b.shape, name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


# ---------------------------------------------------------------------------
# kernel vs ref: bitwise, move for move
# ---------------------------------------------------------------------------
def test_kernel_matches_ref_bitwise(water_wf):
    """Exact tiling (W=8, tile_w=4): every output — including the
    (W, n_blk) accept matrix — bitwise-equal between kernel and oracle."""
    cfg, params = water_wf
    ens = _state(cfg, params, W=8)
    ref_out, ker_out, _ = _run_both(cfg, params, ens, tile_w=4)
    assert bool(np.any(np.asarray(ref_out[4]))), 'sweep accepted nothing'
    _assert_bitwise(ref_out, ker_out)


@pytest.mark.parametrize('tile_w', [4, 8], ids=['ragged', 'oversize'])
def test_ragged_walker_tiles(water_wf, tile_w):
    """W=5 with tile_w=4 (ragged: 3 padded walkers) and tile_w=8 (a single
    tile wider than the batch): padding never leaks into real walkers."""
    cfg, params = water_wf
    ens = _state(cfg, params, W=5)
    ref_out, ker_out, _ = _run_both(cfg, params, ens, tile_w=tile_w)
    assert ker_out[0].shape == (5, cfg.n_elec, 3)
    assert ker_out[4].shape == (5, cfg.n_up)
    _assert_bitwise(ref_out, ker_out)


def test_all_reject_sweep(water_wf):
    """logu=+1e30 beats any finite log-ratio: nothing accepted, the state
    passes through bitwise-untouched on both paths."""
    cfg, params = water_wf
    ens = _state(cfg, params, W=5)
    ref_out, ker_out, _ = _run_both(cfg, params, ens, tile_w=4,
                                    logu_override=1e30)
    for out in (ref_out, ker_out):
        assert not np.any(np.asarray(out[4]))
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.asarray(ens.r))
        np.testing.assert_array_equal(np.asarray(out[1]),
                                      np.asarray(ens.minv_up))
    _assert_bitwise(ref_out, ker_out)


def test_all_accept_sweep(water_wf):
    """logu=-1e30 accepts every move: the block's electrons land exactly
    on their proposals and the paths still agree bitwise."""
    cfg, params = water_wf
    ens = _state(cfg, params, W=5)
    ref_out, ker_out, r_prop = _run_both(cfg, params, ens, tile_w=4,
                                         logu_override=-1e30)
    for out in (ref_out, ker_out):
        assert np.all(np.asarray(out[4]))
        np.testing.assert_array_equal(np.asarray(out[0][:, :cfg.n_up]),
                                      np.asarray(r_prop))
    _assert_bitwise(ref_out, ker_out)


def test_multidet_kernel_parity():
    """n_det=4 CI wavefunction: the in-kernel P-table rank-1 updates and
    determinant-ratio state match the oracle bitwise."""
    cfg, params = build_system('water', n_det=4, ci_seed=3)
    ens = _state(cfg, params, W=6)
    ci = cfg.ci
    ci_ops = (ens.p_up, ens.rdet_up, ens.rdet_dn, ci.holes_up,
              ci.parts_up, ci.coeffs)
    ref_out, ker_out, _ = _run_both(cfg, params, ens, tile_w=4,
                                    ci_ops=ci_ops)
    assert ref_out[2].shape[1] > 0 and ref_out[3].shape[1] == 4
    _assert_bitwise(ref_out, ker_out)


def test_fused_kernel_propagator_matches_scan(water_wf, tmp_path,
                                              monkeypatch):
    """cfg.method='fused-kernel' through the full propagator walks bitwise
    like 'fused' (pre-seeded tile cache: no in-test measurement)."""
    cfg, params = water_wf
    W = 6
    cache = tmp_path / 'tiles.json'
    key = f'{cfg.n_elec}|{W}|fp32|{jax.default_backend()}'
    cache.write_text(json.dumps({'schema': 1, 'tiles': {key: 4}}))
    monkeypatch.setenv('REPRO_FUSED_TILE_CACHE', str(cache))
    before = autotune.build_count()
    states = {}
    for method in ('fused', 'fused-kernel'):
        prop = SEMVMCPropagator(dataclasses.replace(cfg, method=method),
                                step_size=0.4)
        drv = EnsembleDriver(prop, steps=2, donate=False)
        st = drv.init(params, jax.random.PRNGKey(0), W)
        st, _ = drv.run_block(params, st, jax.random.PRNGKey(1))
        states[method] = st.ens
    assert autotune.build_count() == before, 'cache hit should not measure'
    for a, b in zip(states['fused'], states['fused-kernel']):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# autotuner: measured once, cached forever, corruption-tolerant
# ---------------------------------------------------------------------------
def test_autotuner_cache_hit_skips_measurement(tmp_path):
    calls = []

    def fake_measure(n_e, W, candidates):
        calls.append((n_e, W, tuple(candidates)))
        return candidates[-1]

    path = tmp_path / 'tiles.json'
    before = autotune.build_count()
    t1 = autotune.best_tile_w(10, 32, 'fp32', backend='cpu', path=path,
                              measure=fake_measure)
    assert len(calls) == 1 and autotune.build_count() == before + 1
    assert t1 == 32 and calls[0] == (10, 32, (4, 8, 16, 32))
    t2 = autotune.best_tile_w(10, 32, 'fp32', backend='cpu', path=path,
                              measure=fake_measure)
    assert t2 == t1
    assert len(calls) == 1, 'cache hit re-measured'
    assert autotune.build_count() == before + 1
    doc = json.loads(path.read_text())
    assert doc == {'schema': 1, 'tiles': {'10|32|fp32|cpu': 32}}


def test_autotuner_key_spans_all_fields(tmp_path):
    """Changing any of (n_e, W, dtype, backend) is a distinct cache entry
    — each triggers exactly one fresh measurement."""
    calls = []

    def fake_measure(n_e, W, candidates):
        calls.append(None)
        return candidates[0]

    path = tmp_path / 'tiles.json'
    base = dict(n_e=10, W=32, dtype='fp32', backend='cpu')
    variants = [dict(base), dict(base, n_e=12), dict(base, W=64),
                dict(base, dtype='bf16'), dict(base, backend='tpu')]
    for kw in variants + variants:          # second pass: all cache hits
        autotune.best_tile_w(kw['n_e'], kw['W'], kw['dtype'],
                             backend=kw['backend'], path=path,
                             measure=fake_measure)
    assert len(calls) == len(variants)
    assert len(json.loads(path.read_text())['tiles']) == len(variants)


@pytest.mark.parametrize('garbage', ['{not json', '[]',
                                     '{"schema": 0, "tiles": {"a": 4}}',
                                     '{"schema": 1, "tiles": 7}'],
                         ids=['corrupt', 'nondict', 'stale', 'badtiles'])
def test_autotuner_corrupt_cache_remeasures(tmp_path, garbage):
    path = tmp_path / 'tiles.json'
    path.write_text(garbage)
    tile = autotune.best_tile_w(6, 8, 'fp32', backend='cpu', path=path,
                                measure=lambda n_e, W, cands: cands[0])
    assert tile == 4
    doc = json.loads(path.read_text())      # rewritten healthy
    assert doc['schema'] == 1 and doc['tiles'] == {'6|8|fp32|cpu': 4}


def test_autotuner_env_override(tmp_path, monkeypatch):
    monkeypatch.setenv('REPRO_FUSED_TILE_CACHE', str(tmp_path / 'c.json'))
    assert autotune.cache_path() == tmp_path / 'c.json'
    monkeypatch.delenv('REPRO_FUSED_TILE_CACHE')
    assert autotune.cache_path().name == 'fused_sweep_tiles.json'


@pytest.mark.slow
def test_autotuner_real_measurement(tmp_path):
    """The default measurement hook actually times the kernel and returns
    one of the offered candidates."""
    tile = autotune.best_tile_w(4, 8, 'fp32', backend='cpu',
                                path=tmp_path / 'tiles.json')
    assert tile in (4, 8)


# ---------------------------------------------------------------------------
# sharding: fused sweep under a walker mesh stays bitwise
# ---------------------------------------------------------------------------
def _fused_consistency_check(n_shards=8, steps=4, n_walkers=32):
    """Sharded fused-sweep block == single-device block: bitwise walker
    trajectories, reduction-tolerance stats."""
    from jax.sharding import Mesh
    devices = jax.devices()
    assert len(devices) >= n_shards, f'need {n_shards} devices'
    mesh = Mesh(np.array(devices[:n_shards]), ('walkers',))
    cfg, params = build_wavefunction(*water())
    cfg = dataclasses.replace(cfg, method='fused')
    prop = SEMVMCPropagator(cfg, step_size=0.4)
    d1 = EnsembleDriver(prop, steps, donate=False)
    dn = EnsembleDriver(prop, steps, mesh=mesh, donate=False)
    s1 = d1.init(params, jax.random.PRNGKey(0), n_walkers)
    sn = dn.init(params, jax.random.PRNGKey(0), n_walkers)
    s1, st1 = d1.run_block(params, s1, jax.random.PRNGKey(1))
    sn, stn = dn.run_block(params, sn, jax.random.PRNGKey(1))
    np.testing.assert_array_equal(np.asarray(s1.ens.r),
                                  np.asarray(sn.ens.r))
    np.testing.assert_array_equal(np.asarray(s1.ens.minv_up),
                                  np.asarray(jax.device_get(sn.ens.minv_up)))
    for field in ('weight', 'e_mean', 'e2_mean'):
        a, b = float(getattr(st1, field)), float(getattr(stn, field))
        assert a == pytest.approx(b, rel=1e-5, abs=1e-5), (field, a, b)
    return True


needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason='needs XLA_FLAGS=--xla_force_host_platform_device_count=8')


@needs_8_devices
def test_fused_sharded_matches_single_device_inprocess():
    assert _fused_consistency_check()


@pytest.mark.slow
def test_fused_sharded_matches_single_device_subprocess():
    """Same check under 8 virtual CPU devices when the current session is
    single-device (mirrors test_sem's subprocess pattern)."""
    if len(jax.devices()) >= 8:
        pytest.skip('in-process variant already covers this')
    env = dict(os.environ,
               XLA_FLAGS='--xla_force_host_platform_device_count=8',
               PYTHONPATH=str(ROOT / 'src'))
    code = ('import sys; sys.path.insert(0, %r); '
            'import test_fused_sweep_kernel as t; '
            'assert t._fused_consistency_check(); print("CONSISTENT")'
            % str(ROOT / 'tests'))
    out = subprocess.run([sys.executable, '-c', code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    assert 'CONSISTENT' in out.stdout


@pytest.mark.slow
def test_qmc_run_cli_fused_smoke(tmp_path):
    """qmc_run --method fused-vmc --precision bf16 end to end."""
    from repro.launch.qmc_run import main
    avg = main(['--system', 'h2', '--method', 'fused-vmc',
                '--precision', 'bf16', '--workers', '1', '--walkers', '8',
                '--steps', '5', '--blocks', '2',
                '--db', str(tmp_path / 'fused.sqlite')])
    assert avg.n_blocks >= 2
    assert np.isfinite(avg.energy)
