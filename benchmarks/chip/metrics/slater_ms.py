"""Device milliseconds per generation of the ops whose innermost scope is
``qmc.slater``: the batched LU, inverse and Newton-Schulz steps of the
Slater matrices, and their drift and Laplacian ratios (``scopes.py``)."""
from pathlib import Path

from bench import load_module

scopes = load_module(Path(__file__).resolve().parents[1] / 'scopes.py')


def read(ctx):
    return scopes.of(ctx).get('layer_ms', {}).get('slater')
