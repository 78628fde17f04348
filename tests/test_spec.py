"""RunSpec front-end: validation, compilation, CLI parsing, run keys."""
import numpy as np
import pytest

from repro.launch.qmc_run import parse_spec
from repro.launch.spec import RunSpec, build_run
from repro.runtime import (ProcessBackend, SimGridBackend, SimGridConfig,
                           ThreadBackend)


def test_runspec_validation():
    with pytest.raises(ValueError, match='unknown method'):
        RunSpec(method='gfmc')
    with pytest.raises(ValueError, match='unknown backend'):
        RunSpec(backend='mpi')
    with pytest.raises(ValueError, match='thread or sim'):
        RunSpec(backend='process', shards=2)
    # sharded thread/sim specs are legal (validated at build time against
    # the visible devices)
    RunSpec(backend='thread', shards=2)


def test_runspec_tau_defaults():
    assert RunSpec(method='vmc').resolved_tau() == pytest.approx(0.3)
    assert RunSpec(method='dmc').resolved_tau() == pytest.approx(0.02)
    assert RunSpec(method='sem-vmc').resolved_tau() == pytest.approx(0.3)
    assert RunSpec(method='dmc', tau=0.05).resolved_tau() == \
        pytest.approx(0.05)


def test_runspec_replace_is_functional_update():
    spec = RunSpec(system='h2', max_blocks=10)
    spec2 = spec.replace(backend='sim', max_blocks=99)
    assert spec.max_blocks == 10 and spec.backend == 'thread'
    assert spec2.max_blocks == 99 and spec2.backend == 'sim'


def test_build_run_assembles_stack():
    """build_run wires spec fields into sampler/control/backend/manager."""
    spec = RunSpec(system='h2', method='vmc', n_workers=3, n_walkers=16,
                   steps=7, max_blocks=5, target_error=0.01,
                   subblocks_per_block=2, backend='thread', seed=11)
    run = build_run(spec)
    assert isinstance(run.backend, ThreadBackend)
    assert run.backend.n_workers == 3
    assert run.manager.control.max_blocks == 5
    assert run.manager.control.target_error == 0.01
    assert run.manager.control.subblocks_per_block == 2
    assert run.manager.control.e_trial_feedback is False   # vmc
    assert run.sampler.n_walkers == 16
    assert run.sampler.driver.steps == 7
    assert run.manager._seed == 11
    assert build_run(spec.replace(method='dmc')) \
        .manager.control.e_trial_feedback is True


def test_build_run_backend_selection():
    assert isinstance(build_run(RunSpec(backend='process')).backend,
                      ProcessBackend)
    sim = build_run(RunSpec(
        backend='sim', grid=SimGridConfig(drop_rate=0.2))).backend
    assert isinstance(sim, SimGridBackend)
    assert sim.grid.drop_rate == 0.2


def test_run_key_is_critical_data_only():
    """Platform axis (backend, workers, blocks, walkers) never changes the
    run key; estimator fields (method, tau) do — paper §V.C."""
    spec = RunSpec(system='h2', method='vmc')
    base = build_run(spec).run_key
    same = build_run(spec.replace(backend='sim', n_workers=7, max_blocks=3,
                                  n_walkers=8, steps=5)).run_key
    assert same == base
    assert build_run(spec.replace(tau=0.17)).run_key != base
    assert build_run(spec.replace(method='sem-vmc')).run_key != base


def test_parse_spec_maps_cli_flags():
    spec = parse_spec(['--system', 'h2', '--method', 'dmc', '--backend',
                       'sim', '--workers', '5', '--walkers', '16',
                       '--steps', '9', '--blocks', '33', '--tau', '0.04',
                       '--sim-latency', '0.01', '--sim-drop', '0.2',
                       '--seed', '4'])
    assert spec.system == 'h2' and spec.method == 'dmc'
    assert spec.backend == 'sim' and spec.n_workers == 5
    assert spec.n_walkers == 16 and spec.steps == 9
    assert spec.max_blocks == 33 and spec.tau == pytest.approx(0.04)
    assert spec.grid.latency == pytest.approx(0.01)
    assert spec.grid.drop_rate == pytest.approx(0.2)
    assert spec.grid.seed == 4 and spec.seed == 4


def test_build_system_catalog():
    from repro.systems import build_system
    cfg, params = build_system('h2')
    assert cfg.n_elec == 2
    assert np.asarray(params.coords).shape[0] == 2
    with pytest.raises(KeyError):
        from repro.systems.bench import paper_system
        paper_system('not-a-system')


@pytest.mark.parametrize('kw', [
    dict(backend='process'),
    dict(backend='grid'),                      # spawns localhost workers
], ids=['process', 'grid-local'])
def test_tpu_refuses_local_worker_processes(kw):
    """On a TPU the parent holds the chip, so layouts whose workers are
    local processes are refused, saying why."""
    spec = RunSpec(**kw)
    with pytest.raises(ValueError, match='holds the chip'):
        spec.check_platform('tpu')
    spec.check_platform('cpu')                 # off the TPU: unchanged


@pytest.mark.parametrize('kw', [
    dict(backend='thread'),
    dict(backend='sim'),
], ids=['thread', 'sim'])
def test_tpu_keeps_in_process_and_remote_workers(kw):
    from repro.runtime import GridConfig
    RunSpec(**kw).check_platform('tpu')
    RunSpec(backend='grid',
            net=GridConfig(local_workers=False)).check_platform('tpu')


class _FailingSampler:
    """Worker-protocol sampler whose every sub-block raises."""

    def init_state(self, worker_id, seed, walkers=None):
        return None

    def run_subblock(self, state, step):
        raise RuntimeError('injected sampler fault')


def test_qmc_run_exits_nonzero_on_worker_error(monkeypatch, capsys):
    """A worker that dies must fail the CLI run, not print and exit 0."""
    from types import SimpleNamespace

    from repro.launch import qmc_run
    from repro.runtime import QMCManager, RunControl

    def fake_build_run(spec):
        mgr = QMCManager(_FailingSampler(), 'deadbeef',
                         RunControl(max_blocks=2, poll_interval=0.01),
                         backend=ThreadBackend(1))
        return SimpleNamespace(run_key='deadbeef', run=mgr.run,
                               worker_errors=mgr.worker_errors,
                               backend=mgr.backend)

    monkeypatch.setattr(qmc_run, 'build_run', fake_build_run)
    with pytest.raises(SystemExit) as exc:
        qmc_run.main(['--backend', 'thread', '--workers', '1'])
    assert exc.value.code not in (0, None)
    out = capsys.readouterr().out
    assert 'WORKER ERROR' in out and 'injected sampler fault' in out


_CACHE_PROBE = '''
import sys
import jax, jax.numpy as jnp
from pathlib import Path
from repro.compile_cache import enable_compile_cache
jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
hits = []
jax.monitoring.register_event_listener(
    lambda name, **kw: hits.append(name)
    if name == '/jax/compilation_cache/cache_hits' else None)
print(enable_compile_cache(Path(sys.argv[1])))
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
print(len(hits))
'''


def test_compile_cache_fixed_dir_and_env_override(tmp_path):
    """Without JAX_COMPILATION_CACHE_DIR the cache goes to the fixed
    directory and a second process reads it back; with it, JAX's own
    directory is used and the fixed one is never created."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = str(Path(__file__).resolve().parents[1] / 'src')
    env = {k: v for k, v in os.environ.items()
           if k != 'JAX_COMPILATION_CACHE_DIR'}
    env['PYTHONPATH'] = src
    fixed = tmp_path / 'fixed'

    def probe(env):
        out = subprocess.run([sys.executable, '-c', _CACHE_PROBE,
                              str(fixed)], env=env, capture_output=True,
                             text=True, timeout=120, check=True)
        path, hits = out.stdout.split()
        return path, int(hits)

    assert probe(env) == (str(fixed), 0)
    assert any(fixed.iterdir())
    path, hits = probe(env)
    assert path == str(fixed) and hits >= 1     # read back

    own = tmp_path / 'own'
    fixed_files = sorted(fixed.iterdir())
    path, hits = probe({**env, 'JAX_COMPILATION_CACHE_DIR': str(own)})
    assert (path, hits) == (str(own), 0)
    assert any(own.iterdir())
    assert sorted(fixed.iterdir()) == fixed_files
