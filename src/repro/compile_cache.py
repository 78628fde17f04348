"""Where compiled programs and measured kernel tiles persist between runs.

Every entry point (``qmc_run``, ``qmc_worker``, ``qmc_serve``,
``chip_smoke.py`` and ``benchmarks/run.py``) calls ``enable_compile_cache``
before it compiles anything.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing else is set.  Otherwise the cache goes to
``.jax_cache`` in the checkout: a fixed path, because the path is part of
each entry's key, so a directory named after a temporary file, a process id
or the time would never be hit again.  The fused-sweep autotuner keeps its
measured tiles by the same rule (``REPRO_FUSED_TILE_CACHE`` or
``.tile_cache`` in the checkout).  Both directories are listed in
``.gitignore``.
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]
JAX_CACHE_DIR = REPO_ROOT / '.jax_cache'
TILE_CACHE_DIR = REPO_ROOT / '.tile_cache'


def enable_compile_cache(default_dir: Path = JAX_CACHE_DIR) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it on import);
    otherwise the cache is pointed at ``default_dir``.
    """
    env = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if env:
        return env
    import jax
    jax.config.update('jax_compilation_cache_dir', str(default_dir))
    return str(default_dir)


__all__ = ['JAX_CACHE_DIR', 'REPO_ROOT', 'TILE_CACHE_DIR',
           'enable_compile_cache']
