"""Per cent of the device idle time in the traced window that no ``qmc.``
host span of the program covers, on any thread (``scopes.py``)."""
from pathlib import Path

from bench import load_module

scopes = load_module(Path(__file__).resolve().parents[1] / 'scopes.py')


def read(ctx):
    return scopes.of(ctx).get('idle_unattributed_share')
