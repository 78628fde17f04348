"""Where compiled programs and measured kernel tiles persist between runs.

Every entry point (``qmc_run``, ``qmc_worker``, ``qmc_serve``,
``chip_smoke.py`` and ``benchmarks/run.py``) calls ``enable_compile_cache``
before it compiles anything.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads the directory from it itself.  Otherwise the cache goes to
``.jax_cache`` in the checkout: a fixed path, because the path is part of
each entry's key, so a directory named after a temporary file, a process id
or the time would never be hit again.  The fused-sweep autotuner keeps its
measured tiles by the same rule (``REPRO_FUSED_TILE_CACHE`` or
``.tile_cache`` in the checkout).  Both directories are listed in
``.gitignore``.

The key of a cached program includes its debug metadata: by default JAX
strips it, so an executable compiled before a ``jax.named_scope`` was
added or renamed would be loaded for the new code and a profile would show
the old op names.  The checkout's own path is removed from each op's
source frames, so the key does not depend on where the checkout lies.  The
frames themselves stay whole: with ``jax_include_full_tracebacks_in_locations``
off, JAX 0.9 drops the scope path from the op names of the block's products
(``tests/test_tracing.py`` compiles under these settings).
"""
from __future__ import annotations

import os
import re
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]
JAX_CACHE_DIR = REPO_ROOT / '.jax_cache'
TILE_CACHE_DIR = REPO_ROOT / '.tile_cache'


def enable_compile_cache(default_dir: Path = JAX_CACHE_DIR) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it on import);
    otherwise the cache is pointed at ``default_dir``.  Either way the key
    includes the programs' metadata (module docstring).
    """
    import jax
    jax.config.update('jax_compilation_cache_include_metadata_in_key', True)
    jax.config.update('jax_hlo_source_file_canonicalization_regex',
                      '^' + re.escape(f'{REPO_ROOT}{os.sep}'))
    env = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if env:
        return env
    jax.config.update('jax_compilation_cache_dir', str(default_dir))
    return str(default_dir)


__all__ = ['JAX_CACHE_DIR', 'REPO_ROOT', 'TILE_CACHE_DIR',
           'enable_compile_cache']
