"""Per cent of the device busy time in the traced sub-blocks spent in ops
with no ``qmc.`` layer on their path: proposal draws, acceptance, merges
and means, and whatever a layer scope misses (``scopes.py``)."""
from pathlib import Path

from bench import load_module

scopes = load_module(Path(__file__).resolve().parents[1] / 'scopes.py')


def read(ctx):
    return scopes.of(ctx).get('unscoped_share')
