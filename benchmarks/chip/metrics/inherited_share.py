"""Per cent of the device busy time in the traced sub-blocks spent in ops
that carry no scope path of their own (made by a compiler pass) and took
their layer from the ops around them: how much of the layer times rests
on that rule (``scopes.py``)."""
from pathlib import Path

from bench import load_module

scopes = load_module(Path(__file__).resolve().parents[1] / 'scopes.py')


def read(ctx):
    return scopes.of(ctx).get('inherited_share')
