"""Record the small device trace that ``test_trace.py`` reads.

    python3 benchmarks/chip/tests/record_trace.py OUT_DIR

On the chip: three calls of a small jitted program inside a
``bench.run_subblock`` span, then a 20 ms host pause inside a
``bench.db_append`` span, all inside ``bench.window``.  The
``.xplane.pb`` under OUT_DIR is copied to ``tests/data/small.xplane.pb``.
"""
import sys
import time

import jax
import jax.numpy as jnp


def main(out: str) -> None:
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(out)
    with jax.profiler.TraceAnnotation('bench.window'):
        with jax.profiler.TraceAnnotation('bench.run_subblock'):
            for _ in range(3):
                x = f(x)
            x.block_until_ready()
        with jax.profiler.TraceAnnotation('bench.db_append'):
            time.sleep(0.02)
        with jax.profiler.TraceAnnotation('bench.run_subblock'):
            f(x).block_until_ready()
    jax.profiler.stop_trace()


if __name__ == '__main__':
    main(sys.argv[1])
