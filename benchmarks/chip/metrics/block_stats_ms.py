"""Device milliseconds per sub-block of every op with ``qmc.block_stats``
on its path: the block's statistics, with the re-evaluation of the last
configuration; a phase, so its ops also count in their layers
(``scopes.py``)."""
from pathlib import Path

from bench import load_module

scopes = load_module(Path(__file__).resolve().parents[1] / 'scopes.py')


def read(ctx):
    return scopes.of(ctx).get('block_stats_ms')
