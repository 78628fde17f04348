"""Host spans on the profiler's clock, for code that never imports jax.

``span(name)`` is a ``jax.profiler.TraceAnnotation`` when jax has already
been imported by someone else, and a null context otherwise, so the
jax-free runtime (worker, forwarder, database, manager) can name what the
host is doing without importing jax itself.  Under ``jax.profiler.trace``
the spans land in the same trace as the device ops, on the same clock;
with no profiler running a span costs one context-manager entry.

The program's device scopes are ``jax.named_scope('qmc.<layer>')`` in
``repro.core``.  Both kinds share the ``qmc.`` prefix; PERF.md §3 names
each span and scope with the benchmark metric that reads it.
"""
from __future__ import annotations

import contextlib
import sys


def span(name: str):
    """A named host span: ``TraceAnnotation(name)`` if jax is loaded."""
    jax = sys.modules.get('jax')
    profiler = getattr(jax, 'profiler', None)
    if profiler is None:
        return contextlib.nullcontext()
    return profiler.TraceAnnotation(name)


__all__ = ['span']
