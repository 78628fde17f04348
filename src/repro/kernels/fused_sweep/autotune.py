"""Measured walker-tile autotuner for the fused-sweep kernel.

The fused kernel's only free launch parameter is the walker tile ``tile_w``
— how many walkers one grid step owns.  The best value depends on the
problem geometry and machine (VMEM footprint per tile grows with n^2,
per-tile fixed cost amortizes with tile_w), so instead of a heuristic the
tuner MEASURES each candidate on synthetic operands of the real shape and
persists the winner in a small JSON cache keyed on
``(n_e, W, dtype, backend)``:

    {"schema": 1, "tiles": {"60|256|fp32|cpu": 16, ...}}

Cache location: ``$REPRO_FUSED_TILE_CACHE`` or
``.tile_cache/fused_sweep_tiles.json`` in the checkout
(``repro.compile_cache``).  A cache hit returns the stored
tile without re-measuring (``build_count()`` exposes the number of
measurement runs so tests can pin determinism); a corrupt, stale-schema or
otherwise unreadable cache falls back to re-measuring and rewrites the
file rather than crashing.  Writes are atomic (tmp + replace) so
concurrent runs at worst lose an entry, never corrupt the file.
"""
from __future__ import annotations

import json
import os
import time
from pathlib import Path

_SCHEMA = 1
_CANDIDATES = (4, 8, 16, 32)
_build_count = 0


def build_count() -> int:
    """Number of measurement runs (cache misses) this process performed."""
    return _build_count


def cache_path() -> Path:
    """Resolved tile-cache location (env override for tests/CI)."""
    env = os.environ.get('REPRO_FUSED_TILE_CACHE')
    if env:
        return Path(env)
    from repro.compile_cache import TILE_CACHE_DIR
    return TILE_CACHE_DIR / 'fused_sweep_tiles.json'


def _cache_key(n_e: int, W: int, dtype: str, backend: str) -> str:
    return f'{n_e}|{W}|{dtype}|{backend}'


def _load_tiles(path: Path) -> dict:
    """Stored tile table, or {} on any corruption/staleness (no crash)."""
    try:
        doc = json.loads(path.read_text())
    except (OSError, ValueError):
        return {}
    if not isinstance(doc, dict) or doc.get('schema') != _SCHEMA:
        return {}                      # stale schema: re-measure everything
    tiles = doc.get('tiles')
    return tiles if isinstance(tiles, dict) else {}


def _store_tiles(path: Path, tiles: dict) -> None:
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(f'.tmp{os.getpid()}')
        tmp.write_text(json.dumps({'schema': _SCHEMA, 'tiles': tiles},
                                  indent=2) + '\n')
        os.replace(tmp, path)
    except OSError:
        pass                           # read-only cache dir: stay in-memory


def _measure(n_e: int, W: int, candidates, repeats: int = 2) -> int:
    """Time the fused kernel at each candidate tile on synthetic operands.

    Single-determinant, n_up = ceil(n_e/2), random fp32 state — the shapes
    are what matters; min-of-N wall time per candidate, smallest wins.
    Each candidate runs the way ``kernels.platform.kernel_plan`` picks for
    this platform: compiled on a TPU, interpreted elsewhere.
    """
    import jax
    import jax.numpy as jnp
    from .ops import fused_sweep_block

    n_up = (n_e + 1) // 2
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 6)
    minv = jax.random.normal(ks[0], (W, n_up, n_up), jnp.float32)
    phi = jax.random.normal(ks[1], (W, n_up, n_up), jnp.float32)
    r = jax.random.normal(ks[2], (W, n_e, 3), jnp.float32)
    r_prop = r[:, :n_up] + 0.1 * jax.random.normal(
        ks[3], (W, n_up, 3), jnp.float32)
    en = 0.01 * jax.random.normal(ks[4], (W, n_up), jnp.float32)
    logu = jnp.log(jax.random.uniform(ks[5], (W, n_up),
                                      minval=1e-6, maxval=1.0))

    best, best_t = None, float('inf')
    for tile_w in candidates:
        def _run():
            out = fused_sweep_block(
                minv, phi, r, r_prop, en, logu, jnp.float32(1.0), offset=0, n_up=n_up, use_kernel=True,
                tile_w=tile_w)
            jax.block_until_ready(out)
        _run()                                       # compile/warmup
        t = min(_timed(_run) for _ in range(repeats))
        if t < best_t:
            best, best_t = tile_w, t
    return int(best)


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def best_tile_w(n_e: int, W: int, dtype: str = 'fp32',
                backend: str | None = None, path: Path | None = None,
                measure=None) -> int:
    """Autotuned walker tile for a (n_e, W, dtype, backend) geometry.

    Cache hit: returns the stored tile with NO measurement.  Miss (or
    corrupt/stale cache): measures the candidates that divide into the
    padded walker count, persists, returns the winner.  ``measure`` is an
    injectable measurement hook for tests (signature
    ``(n_e, W, candidates) -> int``).
    """
    global _build_count
    if backend is None:
        import jax
        backend = jax.default_backend()
    path = Path(path) if path is not None else cache_path()
    key = _cache_key(n_e, W, dtype, backend)
    tiles = _load_tiles(path)
    stored = tiles.get(key)
    if isinstance(stored, int) and stored > 0:
        return stored
    _build_count += 1
    candidates = tuple(c for c in _CANDIDATES if c <= max(W, 4))
    best = int((measure or _measure)(n_e, W, candidates))
    tiles[key] = best
    _store_tiles(path, tiles)
    return best


__all__ = ['best_tile_w', 'build_count', 'cache_path']
