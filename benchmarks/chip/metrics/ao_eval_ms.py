"""Device milliseconds per generation of the ops whose innermost scope is
``qmc.ao_eval``: the AO values, gradients and Laplacians of every electron
and the active-AO mask and count (``scopes.py``)."""
from pathlib import Path

from bench import load_module

scopes = load_module(Path(__file__).resolve().parents[1] / 'scopes.py')


def read(ctx):
    return scopes.of(ctx).get('layer_ms', {}).get('ao_eval')
