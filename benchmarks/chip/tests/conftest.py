"""Shared fixtures: the benchmark's directory on ``sys.path`` and a
throwaway copy of the benchmark tree for tests that add or break files."""
import shutil
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))
sys.path.insert(1, str(ROOT / "src"))


@pytest.fixture
def bench_copy(tmp_path):
    """BENCHMARK.json, benchmarks/chip and a link to src in a temp root."""
    (tmp_path / 'benchmarks').mkdir()
    shutil.copytree(CHIP, tmp_path / 'benchmarks' / 'chip',
                    ignore=shutil.ignore_patterns('__pycache__', 'data'))
    shutil.copy(ROOT / 'BENCHMARK.json', tmp_path / 'BENCHMARK.json')
    (tmp_path / 'src').symlink_to(ROOT / 'src')
    return tmp_path


def run_in(root, code: str, timeout: float = 1500):
    """Run python ``code`` in ``root`` on the CPU; -> CompletedProcess."""
    import os
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    env.pop('JAX_COMPILATION_CACHE_DIR', None)
    return subprocess.run([sys.executable, '-c', code], cwd=root, env=env,
                          capture_output=True, text=True, timeout=timeout)


def shrink(root, cells, walkers=4, steps=2, per_block=2, config=None):
    """Cut the named cells of a benchmark copy to a size a CPU holds."""
    import json
    spec = json.loads((root / 'BENCHMARK.json').read_text())
    for name in cells:
        f = root / 'benchmarks' / 'chip' / 'workloads' / f'{name}.json'
        c = json.loads(f.read_text())
        c.update(walkers=walkers, steps=steps, subblocks_per_block=per_block)
        if config:
            c['config'] = config
            for w in spec['workloads']:
                if w['name'] == name:
                    w['config'] = config
        f.write_text(json.dumps(c))
    (root / 'BENCHMARK.json').write_text(json.dumps(spec))


def cpu_size(root):
    """The cells at a size a CPU test holds: 64 walkers, so that one
    walker is 1/64 of ``moved_off``, under its limits."""
    shrink(root, ['smallest.vmc', 'smallest.sem'], walkers=64, steps=4,
           per_block=1)
