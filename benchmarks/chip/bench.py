"""``BENCHMARK.json`` and the files it names: loading and validation.

Everything that belongs to one configuration, one cell or one per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives
it:

    configs/<config>.json       the configuration as it is run
    workloads/<cell>.json       the cell's traffic, work model and limits
    metrics/<metric>.py         a per-layer metric's reader: read(ctx)
    work/<work>.py              operations one generation needs

so a new configuration, cell or metric is new files plus entries, with no
edit to a file that is here.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
NAME = re.compile(r'[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}')
UNIT = re.compile(r'[A-Za-z0-9_/%.-]{1,16}')


class BenchmarkError(ValueError):
    """BENCHMARK.json or a file it names is malformed."""


def _name(value, what):
    if not isinstance(value, str) or not NAME.fullmatch(value):
        raise BenchmarkError(f'{what}: {value!r} is not a valid name')
    return value


def _unit(value, what):
    if not isinstance(value, str) or not UNIT.fullmatch(value):
        raise BenchmarkError(f'{what}: {value!r} is not a valid unit')
    return value


def load(root: Path) -> dict:
    """Read and validate ``root/BENCHMARK.json``."""
    path = Path(root) / 'BENCHMARK.json'
    try:
        spec = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        raise BenchmarkError(f'cannot read {path}: {e}') from e
    for c in spec.get('configs', []):
        _name(c['name'], 'config')
        for k in c.get('reduced', []):
            _name(k, f"config {c['name']} reduced key")
    for w in spec.get('workloads', []):
        _name(w['name'], 'workload')
        _name(w['config'], f"workload {w['name']} config")
        _name(w['traffic'], f"workload {w['name']} traffic")
    for m in spec.get('end_to_end', []) + spec.get('per_layer', []):
        _name(m['name'], 'metric')
        _unit(m['unit'], f"metric {m['name']} unit")
    names = [m['name'] for m in spec.get('end_to_end', [])
             + spec.get('per_layer', [])]
    for kind in ('configs', 'workloads'):
        names_k = [x['name'] for x in spec.get(kind, [])]
        if len(set(names_k)) != len(names_k):
            raise BenchmarkError(f'duplicate name in {kind}')
    if len(set(names)) != len(names):
        raise BenchmarkError('duplicate metric name')
    return spec


def cell(spec: dict, root: Path, name: str) -> dict:
    """The workload entry, its cell file and its configuration file."""
    entry = next((w for w in spec['workloads'] if w['name'] == name), None)
    if entry is None:
        raise BenchmarkError(f'no workload {name!r} in BENCHMARK.json')
    conf = next((c for c in spec['configs'] if c['name'] == entry['config']),
                None)
    if conf is None:
        raise BenchmarkError(f"no config {entry['config']!r}")
    cfile = json.loads((Path(root) / conf['file']).read_text())
    wfile = json.loads((HERE / 'workloads' / f'{name}.json').read_text())
    if wfile.get('config') != entry['config'] or \
            wfile.get('traffic') != entry['traffic']:
        raise BenchmarkError(f'workloads/{name}.json does not match its '
                             f'BENCHMARK.json entry')
    return dict(entry=entry, config=cfile, cell=wfile)


def metrics_for(spec: dict, kind: str, name: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
    return [m for m in spec[kind]
            if 'workloads' not in m or name in m['workloads']]


def load_module(path: Path):
    """Import a benchmark file by path (names may hold dots)."""
    mod_spec = importlib.util.spec_from_file_location(
        'bench_' + re.sub(r'\W', '_', str(path.relative_to(HERE))), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The per-layer reader ``metrics/<metric>.py``."""
    return load_module(HERE / 'metrics' / f'{metric}.py').read


def work(name: str):
    """The work model ``work/<name>.py``."""
    return load_module(HERE / 'work' / f'{name}.py')


def peak(device_kind: str) -> dict:
    """The chip's published peaks; a device not in the table is an error."""
    table = json.loads((HERE / 'peaks.json').read_text())
    if device_kind not in table['devices']:
        raise BenchmarkError(f'no peaks for device {device_kind!r} in '
                             f'peaks.json')
    return table['devices'][device_kind]
