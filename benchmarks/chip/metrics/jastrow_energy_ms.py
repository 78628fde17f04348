"""Device milliseconds per generation of the ops whose innermost scope is
``qmc.jastrow_energy``: the Jastrow factor, drift and Laplacian assembly,
potential and local energy (``scopes.py``)."""
from pathlib import Path

from bench import load_module

scopes = load_module(Path(__file__).resolve().parents[1] / 'scopes.py')


def read(ctx):
    return scopes.of(ctx).get('layer_ms', {}).get('jastrow_energy')
