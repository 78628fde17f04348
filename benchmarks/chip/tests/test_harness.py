"""The harness as data, and its guards.

A new configuration, cell and per-layer metric are picked up as new files
plus entries, with no edit to a file that is there; names and units
outside the allowed characters are refused; without a TPU ``run.py``
exits non-zero and prints no result.
"""
import json
import os
import subprocess
import sys

import pytest

import bench
from conftest import ROOT, run_in


def _add(root):
    """A throwaway config, cell and metric, as new files plus entries."""
    chip = root / 'benchmarks' / 'chip'
    conf = json.loads((chip / 'configs' / 'smallest.json').read_text())
    conf['name'] = 'smallest_copy'
    (chip / 'configs' / 'smallest_copy.json').write_text(json.dumps(conf))
    cell = json.loads((chip / 'workloads' / 'smallest.vmc.json').read_text())
    cell.update(config='smallest_copy', traffic='tiny', walkers=4, steps=2,
                subblocks_per_block=2)
    (chip / 'workloads' / 'smallest_copy.tiny.json').write_text(
        json.dumps(cell))
    (chip / 'metrics' / 'blocks_in_window.py').write_text(
        'def read(ctx):\n    return ctx["window"]["blocks"]\n')
    spec = json.loads((root / 'BENCHMARK.json').read_text())
    spec['configs'].append(dict(
        name='smallest_copy', source='https://arxiv.org/abs/1209.6630',
        file='benchmarks/chip/configs/smallest_copy.json', reduced=[],
        why='a throwaway copy'))
    spec['workloads'].append(dict(
        name='smallest_copy.tiny', config='smallest_copy', traffic='tiny',
        chips=1, why='a throwaway cell'))
    spec['per_layer'].append(dict(
        name='blocks_in_window', unit='blocks', better='higher',
        source='host_clock', layer='runtime', moves='moves_per_s',
        workloads=['smallest_copy.tiny']))
    (root / 'BENCHMARK.json').write_text(json.dumps(spec))


def test_new_config_cell_and_metric_need_no_edit(bench_copy):
    before = {p: p.read_bytes() for p in
              (bench_copy / 'benchmarks' / 'chip').rglob('*.py')}
    _add(bench_copy)
    assert all(p.read_bytes() == b for p, b in before.items())
    code = ('import sys, json; sys.path.insert(0, "benchmarks/chip"); '
            'import run; r = run.run_cell("smallest_copy.tiny", 2**31 + 3, '
            '3.0, True, require_chip=False); print(json.dumps(r))')
    out = run_in(bench_copy, code)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r['correct'], r['checks']
    assert r['metrics']['blocks_in_window']['value'] >= 2
    assert r['metrics']['blocks_in_window']['unit'] == 'blocks'
    assert list(r)[-1] == 'checks'


@pytest.mark.parametrize('kind,key,value', [
    ('workloads', 'name', 'smallest vmc'),
    ('workloads', 'traffic', 'a/b'),
    ('configs', 'name', 'x' * 65),
    ('end_to_end', 'name', 'moves,per_s'),
    ('end_to_end', 'unit', 'moves per s'),
    ('per_layer', 'unit', 'µs'),
])
def test_bad_names_and_units_refused(bench_copy, kind, key, value):
    spec = json.loads((bench_copy / 'BENCHMARK.json').read_text())
    spec[kind][0][key] = value
    (bench_copy / 'BENCHMARK.json').write_text(json.dumps(spec))
    with pytest.raises(bench.BenchmarkError):
        bench.load(bench_copy)


def test_shipped_benchmark_is_valid():
    spec = bench.load(ROOT)
    for w in spec['workloads']:
        c = bench.cell(spec, ROOT, w['name'])
        assert c['cell']['limits']
    for m in spec['per_layer']:
        assert callable(bench.reader(m['name']))


def _run_py(cwd):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    return subprocess.run(
        [sys.executable, 'benchmarks/chip/run.py', '--workload',
         'smallest.vmc', '--seed', '1', '--seconds', '1', '--trace', '0'],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    out = _run_py(ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ''
    assert 'no TPU' in out.stderr


def test_benchmark_files_alone_give_no_result(bench_copy):
    (bench_copy / 'src').unlink()
    out = _run_py(bench_copy)
    assert out.returncode != 0
    assert out.stdout.strip() == ''
