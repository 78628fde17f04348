"""The control: the reference at a lower precision in the program's place.

``control.py`` reads it on the chip at each cell's own size with the
reference at three bfloat16 passes (``high``), the nearest precision below
the configurations' float32 at HIGHEST.  Here, at a size a CPU holds, the
same readings are taken through the same code, and the control at one
bfloat16 pass must fail the cell's limits where the program passes them.
(On a CPU every float32 product is exact, so ``high`` differs from
float32 only by its explicit bfloat16 split; its failing is shown on the
chip, PERF.md.)
"""
import json

import pytest

import control
from conftest import cpu_size, run_in

CELLS = ['smallest.vmc', 'smallest.sem']

CODE = r'''
import json, sys
sys.path.insert(0, 'benchmarks/chip')
import run
for cell in %r:
    r = run.run_cell(cell, 2**31 + 21, 16.0, False, require_chip=False,
                     controls=('high', 'bf16'))
    print(json.dumps(dict(cell=cell, correct=r['correct'],
                          checks=r['checks'], control=r['control'])),
          flush=True)
'''


@pytest.fixture(scope='module')
def readings(tmp_path_factory):
    import shutil
    from conftest import CHIP, ROOT
    root = tmp_path_factory.mktemp('control')
    (root / 'benchmarks').mkdir()
    shutil.copytree(CHIP, root / 'benchmarks' / 'chip',
                    ignore=shutil.ignore_patterns('__pycache__', 'data'))
    shutil.copy(ROOT / 'BENCHMARK.json', root / 'BENCHMARK.json')
    (root / 'src').symlink_to(ROOT / 'src')
    cpu_size(root)
    out = run_in(root, CODE % CELLS, timeout=2400)
    assert out.returncode == 0, out.stderr[-4000:]
    return {r['cell']: r for r in (json.loads(x) for x in
                                   out.stdout.splitlines()
                                   if x.startswith('{'))}


@pytest.mark.parametrize('cell', CELLS)
def test_control_fails_where_program_passes(readings, cell):
    r = readings[cell]
    assert r['correct'], r['checks']
    assert not control.judged(r)['bf16'], r['control']['bf16']
