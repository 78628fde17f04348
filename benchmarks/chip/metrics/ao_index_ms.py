"""Device milliseconds per generation of the ops whose innermost scope is
``qmc.ao_index``: the active-AO index build and the packing of the AO
values by it (``scopes.py``)."""
from pathlib import Path

from bench import load_module

scopes = load_module(Path(__file__).resolve().parents[1] / 'scopes.py')


def read(ctx):
    return scopes.of(ctx).get('layer_ms', {}).get('ao_index')
