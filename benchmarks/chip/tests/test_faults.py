"""Each cell's check, with the timed path broken underneath.

The harness runs as on the chip, less its look for a chip, at a size a
CPU holds, with each of the faults a cell can have planted in the
program: a block that returns its state unchanged; half of the walkers
left out, the population mean taken over the rest; the answer, each
walker's local energy, altered by 1 % where the block program produces
it.  (One chip: no exchange between chips to leave out.)  Every fault
must make ``correct`` false, and the unbroken program must keep it true.
"""
import json

import pytest

from conftest import cpu_size, run_in

CELLS = ['smallest.vmc', 'smallest.sem']
FAULTS = ['none', 'unchanged', 'half', 'altered']

DRIVER = r'''
import json, sys
sys.path[:0] = ['benchmarks/chip', 'src']
import jax, jax.numpy as jnp
import run
from repro.core import driver

run_block = driver.EnsembleDriver.run_block
pop_mean = driver.Population.mean


def unchanged(self, params, state, key):
    _, stats = run_block(self, params, jax.tree.map(jnp.copy, state), key)
    return state, stats


def _ens(st):
    return st.ens if hasattr(st, 'ens') else st


def _put(st, ens):
    return st._replace(ens=ens) if hasattr(st, 'ens') else ens


def altered(self, params, state, key):
    st, stats = run_block(self, params, state, key)
    ens = _ens(st)
    return _put(st, ens._replace(e_loc=ens.e_loc * 1.01)), stats


def half_block(self, params, state, key):
    st, stats = run_block(self, params, jax.tree.map(jnp.copy, state), key)
    n = _ens(st).r.shape[0] // 2
    ens = jax.tree.map(lambda a, b: jnp.concatenate([a[:n], b[n:]]),
                       _ens(st), _ens(state))
    return _put(st, ens), stats


def half_mean(self, x):
    if x.ndim >= 1 and x.shape[0] > 1:
        x = x[:x.shape[0] // 2]
    return pop_mean(self, x)


PLANT = dict(none={}, unchanged=dict(run_block=unchanged),
             altered=dict(run_block=altered),
             half=dict(run_block=half_block, mean=half_mean))
for cell in sys.argv[1].split(','):
    for fault in sys.argv[2].split(','):
        for k, fn in PLANT[fault].items():
            owner = driver.Population if k == 'mean' \
                else driver.EnsembleDriver
            setattr(owner, k, fn)
        try:
            r = run.run_cell(cell, 2**31 + 11, 16.0, False,
                             require_chip=False)
        finally:
            driver.EnsembleDriver.run_block = run_block
            driver.Population.mean = pop_mean
        print(json.dumps(dict(cell=cell, fault=fault, correct=r['correct'],
                              checks=r['checks'])), flush=True)
'''


@pytest.fixture(scope='module')
def outcomes(tmp_path_factory):
    import shutil
    from conftest import CHIP, ROOT
    root = tmp_path_factory.mktemp('faults')
    (root / 'benchmarks').mkdir()
    shutil.copytree(CHIP, root / 'benchmarks' / 'chip',
                    ignore=shutil.ignore_patterns('__pycache__', 'data'))
    shutil.copy(ROOT / 'BENCHMARK.json', root / 'BENCHMARK.json')
    (root / 'src').symlink_to(ROOT / 'src')
    cpu_size(root)
    (root / 'drive.py').write_text(DRIVER)
    out = run_in(root, f'import runpy, sys; sys.argv = ["drive.py", '
                       f'"{",".join(CELLS)}", "{",".join(FAULTS)}"]; '
                       f'runpy.run_path("drive.py", run_name="__main__")',
                 timeout=2400)
    assert out.returncode == 0, out.stderr[-4000:]
    rows = [json.loads(x) for x in out.stdout.splitlines()
            if x.startswith('{')]
    return {(r['cell'], r['fault']): r for r in rows}


@pytest.mark.parametrize('cell', CELLS)
@pytest.mark.parametrize('fault', FAULTS)
def test_fault_fails_the_check(outcomes, cell, fault):
    r = outcomes[(cell, fault)]
    failed = [k for k, v in r['checks'].items()
              if v['value'] is None or v['value'] > v['limit']]
    if fault == 'none':
        assert r['correct'], failed
    else:
        assert not r['correct'], r['checks']
