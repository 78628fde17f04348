"""Device milliseconds per generation of the ops whose innermost scope is
``qmc.mo_product``: the product of the MO coefficients with the AO values,
in whichever form the configuration runs (``scopes.py``)."""
from pathlib import Path

from bench import load_module

scopes = load_module(Path(__file__).resolve().parents[1] / 'scopes.py')


def read(ctx):
    return scopes.of(ctx).get('layer_ms', {}).get('mo_product')
