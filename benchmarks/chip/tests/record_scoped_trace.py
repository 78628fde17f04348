"""Record a small scoped device trace, for checking ``scopes.load`` on a
real chip trace.

    python3 benchmarks/chip/tests/record_scoped_trace.py OUT_DIR

On the chip: a jitted program whose two products lie in a
``qmc.ao_eval`` and a ``qmc.mo_product`` scope, called three times, each
call in a ``qmc.subblock`` span that starts with 5 ms of host work and ends
in a host copy, then a 20 ms host pause in a ``qmc.db_append`` span, all
inside ``bench.window``.  (The host work leaves room between a span's
start and its device ops.)  The ``.xplane.pb`` under OUT_DIR is meant for
``tests/data/scoped.xplane.pb``.
"""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def program(x):
    with jax.named_scope('qmc.ao_eval'):
        y = jnp.sin(x) @ x
    with jax.named_scope('qmc.mo_product'):
        return jnp.tanh(y) @ y.T


def main(out: str) -> None:
    f = jax.jit(program)
    x = jnp.ones((1024, 1024), jnp.float32) / 1024
    np.asarray(f(x))
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation('bench.window'):
        for _ in range(3):
            with jax.profiler.TraceAnnotation('qmc.subblock'):
                time.sleep(0.005)
                x = f(x)
                np.asarray(x)
        with jax.profiler.TraceAnnotation('qmc.db_append'):
            time.sleep(0.02)
    jax.profiler.stop_trace()


if __name__ == '__main__':
    main(sys.argv[1])
