"""Device memory of a cell's block program, compiled for a described v5e.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/aot_probe.py \
        --config smallest --method vmc --tau 0.01 --steps 10 \
        --walkers 256 512 1024

Compiles the block program that ``BlockSampler.run_subblock`` runs, at
each walker count, for one chip of a TPU v5e that is described and not
attached, and prints one JSON line per count with the bytes
``memory_analysis()`` reports.  This is how the cells' walker counts were
bounded before the chip measured moves per second at the candidates.
Nothing runs, so it gives no time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ.setdefault('TPU_LOG_DIR', 'disabled')
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / 'src')]


def probe(config: str, method: str, tau: float, steps: int, walkers: int,
          one_chip) -> dict:
    import jax
    from repro.launch.spec import RunSpec, build_run
    run = build_run(RunSpec(system=config, method=method, n_walkers=walkers,
                            steps=steps, tau=tau, equil_steps=0,
                            backend='thread', n_workers=1))
    drv = run.sampler.driver
    prop = run.sampler.propagator
    key = jax.random.PRNGKey(0)
    state = jax.eval_shape(lambda k: prop.init(run.params, k, walkers), key)

    def shaped(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    fn = drv._build(state)
    compiled = fn.lower(shaped(run.params), shaped(state),
                        shaped(key)).compile()
    m = compiled.memory_analysis()
    return dict(config=config, method=method, walkers=walkers,
                temp_bytes=m.temp_size_in_bytes,
                argument_bytes=m.argument_size_in_bytes,
                output_bytes=m.output_size_in_bytes,
                total_bytes=(m.temp_size_in_bytes + m.argument_size_in_bytes
                             + m.output_size_in_bytes
                             - m.alias_size_in_bytes))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--config', required=True)
    ap.add_argument('--method', required=True)
    ap.add_argument('--tau', type=float, required=True)
    ap.add_argument('--steps', type=int, required=True)
    ap.add_argument('--walkers', type=int, nargs='+', required=True)
    args = ap.parse_args()
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    import program
    jax.config.update('jax_enable_compilation_cache', False)
    program.install(json.loads(
        (HERE / 'configs' / f'{args.config}.json').read_text()))
    topo = topologies.get_topology_desc(platform='tpu',
                                        topology_name='v5e:2x2')
    one_chip = SingleDeviceSharding(topo.devices[0])
    for w in args.walkers:
        print(json.dumps(probe(args.config, args.method, args.tau,
                               args.steps, w, one_chip)), flush=True)


if __name__ == '__main__':
    main()
