"""Device milliseconds per sweep of the ops whose innermost scope is
``qmc.sem_move``: the single-electron proposals, their AO and MO values,
determinant ratios and Sherman-Morrison updates (``scopes.py``)."""
from pathlib import Path

from bench import load_module

scopes = load_module(Path(__file__).resolve().parents[1] / 'scopes.py')


def read(ctx):
    return scopes.of(ctx).get('layer_ms', {}).get('sem_move')
