"""The program names its layers: device scopes and host spans.

Device side: every compute layer of the block is a
``jax.named_scope('qmc.<layer>')`` (``repro.core``), which reaches the
compiled HLO as op metadata and a profile as each op's ``tf_op`` path.
Host side: ``repro.tracing.span`` writes ``qmc.`` spans into the same
profiler trace from the jax-free runtime.  The compile cache keys programs
with their metadata, so a cached executable never shows stale names.
"""
import os
import re
import subprocess
import sys
from collections import Counter
from pathlib import Path

import jax
import pytest

from repro.compile_cache import enable_compile_cache
from repro.core.driver import EnsembleDriver, make_propagator
from repro.systems.molecule import build_wavefunction, h2, water

SRC = str(Path(__file__).resolve().parents[1] / 'src')
LAYERS = {'ao_eval', 'ao_index', 'mo_product', 'slater', 'jastrow_energy',
          'sem_move', 'reconfig'}
EXPECTED = {
    'vmc': {'ao_eval', 'ao_index', 'mo_product', 'slater',
            'jastrow_energy', 'block_stats'},
    'sem-vmc': {'ao_eval', 'ao_index', 'mo_product', 'slater',
                'jastrow_energy', 'sem_move', 'block_stats'},
    'dmc': {'ao_eval', 'ao_index', 'mo_product', 'slater',
            'jastrow_energy', 'reconfig', 'block_stats'},
}
_INSTR = re.compile(r'\s*(?:ROOT )?%\S+ = .*? ([\w-]+)\(')


_CACHE_SETTINGS = ('jax_enable_compilation_cache', 'jax_compilation_cache_dir',
                   'jax_compilation_cache_include_metadata_in_key',
                   'jax_hlo_source_file_canonicalization_regex')


@pytest.fixture(scope='module')
def compiled_blocks(tmp_path_factory):
    """The compiled block programs (HLO text) of a small sparse system,
    under the settings every entry point makes (``enable_compile_cache``),
    restored afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    was = {name: getattr(jax.config, name) for name in _CACHE_SETTINGS}
    enable_compile_cache(tmp_path_factory.mktemp('jax_cache'))
    try:
        cfg, params = build_wavefunction(*water(), method='sparse', k_max=8)
        out = {}
        for method in EXPECTED:
            drv = EnsembleDriver(make_propagator(method, cfg, tau=0.1),
                                 steps=2, donate=False)
            state = drv.init(params, jax.random.PRNGKey(0), 4)
            out[method] = drv._build(state).lower(
                params, state, jax.random.PRNGKey(1)).compile().as_text()
    finally:
        for name, value in was.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
    return out


def _instructions(hlo: str):
    """(opcode, [qmc scope names on the op's path]) for every instruction,
    fused computations included."""
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            on = re.search(r'op_name="([^"]*)"', line)
            yield m.group(1), re.findall(r'qmc\.(\w+)',
                                         on.group(1) if on else '')


@pytest.mark.parametrize('method', sorted(EXPECTED))
def test_block_carries_its_layer_scopes(compiled_blocks, method):
    seen = {s for _, scopes in _instructions(compiled_blocks[method])
            for s in scopes}
    assert seen == EXPECTED[method]


def test_vmc_heavy_ops_lie_in_exactly_one_layer(compiled_blocks):
    """Every dot, sort, LU and triangular solve (on the CPU the last two
    are LAPACK custom calls) is under one ``qmc.`` layer; ``block_stats``
    is a phase around layers, not a layer."""
    kinds = Counter()
    for op, scopes in _instructions(compiled_blocks['vmc']):
        if op in ('dot', 'sort', 'triangular-solve', 'custom-call'):
            layers = [s for s in scopes if s in LAYERS]
            assert len(layers) == 1, (op, scopes)
            kinds[op] += 1
    assert kinds['dot'] and kinds['custom-call']


def test_span_is_null_without_jax():
    code = ('import sys; from repro import tracing; '
            'cm = tracing.span("qmc.x"); '
            'import contextlib; '
            'assert isinstance(cm, contextlib.nullcontext), cm; '
            'assert "jax" not in sys.modules; print("ok")')
    out = subprocess.run([sys.executable, '-c', code], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ, PYTHONPATH=SRC))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == 'ok'


def test_span_is_a_trace_annotation_with_jax():
    from repro import tracing
    assert isinstance(tracing.span('qmc.x'), jax.profiler.TraceAnnotation)


@pytest.mark.parametrize('module', ['worker', 'database', 'forwarder',
                                    'manager'])
def test_runtime_imports_no_jax(module):
    code = (f'import sys; import repro.runtime.{module}; '
            'print("jax" in sys.modules)')
    out = subprocess.run([sys.executable, '-c', code], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ, PYTHONPATH=SRC))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == 'False'


def test_runtime_writes_its_host_spans(tmp_path):
    """A few sub-blocks through BlockSampler, Worker, forwarder and
    database under the profiler: each host span is in the trace."""
    from jax.profiler import ProfileData

    from repro.core.vmc import VMCPropagator
    from repro.runtime.backends import ThreadBackend
    from repro.runtime.manager import QMCManager, RunControl
    from repro.runtime.samplers import BlockSampler

    cfg, params = build_wavefunction(*h2())
    sampler = BlockSampler(VMCPropagator(cfg, tau=0.3), params,
                           n_walkers=8, steps=2)
    state = sampler.init_state(0, 1)
    sampler.run_subblock(state, 0)                 # compile outside
    ctl = RunControl(max_blocks=3, poll_interval=0.02,
                     subblocks_per_block=2)
    mgr = QMCManager(sampler, 'trace-key', ctl, backend=ThreadBackend(1))
    with jax.profiler.trace(str(tmp_path)):
        avg = mgr.run()
    assert not mgr.worker_errors(), mgr.worker_errors()
    assert avg.n_blocks >= 3
    found = list(tmp_path.glob('plugins/profile/*/*.xplane.pb'))
    assert found
    names = Counter()
    for plane in ProfileData.from_file(str(found[0])).planes:
        if plane.name.startswith('/host:'):
            for line in plane.lines:
                names.update(e.name for e in line.events
                             if e.name.startswith('qmc.'))
    for span in ('qmc.subblock', 'qmc.dispatch', 'qmc.fetch', 'qmc.merge',
                 'qmc.submit', 'qmc.db_append', 'qmc.forward', 'qmc.poll'):
        assert names[span] >= 1, (span, names)
    assert names['qmc.subblock'] >= 6
    assert names['qmc.dispatch'] == names['qmc.fetch'] == \
        names['qmc.subblock']


_KEY_PROBE = '''
import sys
from pathlib import Path
import jax, jax.numpy as jnp
from repro.compile_cache import enable_compile_cache
jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
hits = []
jax.monitoring.register_event_listener(
    lambda name, **kw: hits.append(name)
    if name == '/jax/compilation_cache/cache_hits' else None)
enable_compile_cache(Path(sys.argv[1]))
print(jax.config.jax_compilation_cache_include_metadata_in_key)


def program(scope):
    def f(x):
        with jax.named_scope(scope):
            return jnp.sin(x) @ x.T
    return jax.jit(f)


x = jnp.ones((8, 8))
counts = []
for scope in ('qmc.a', 'qmc.a', 'qmc.b'):
    n = len(hits)
    program(scope)(x).block_until_ready()
    counts.append(len(hits) - n)
print(*counts)
'''


def test_compile_cache_key_includes_scope_names(tmp_path):
    """After ``enable_compile_cache`` the key includes the metadata: the
    same program is read back from the cache, while one that differs only
    in a scope name is compiled anew; a second process reads all three
    back, so the key holds nothing that changes from run to run."""
    env = {k: v for k, v in os.environ.items()
           if k != 'JAX_COMPILATION_CACHE_DIR'}
    env['PYTHONPATH'] = SRC
    for expected in (['0', '1', '0'], ['1', '1', '1']):
        out = subprocess.run([sys.executable, '-c', _KEY_PROBE,
                              str(tmp_path / 'cache')], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-3000:]
        flag, counts = out.stdout.strip().splitlines()[-2:]
        assert flag == 'True'
        assert counts.split() == expected
