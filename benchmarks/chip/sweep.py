"""Moves per second of a cell's block program at several walker counts.

    python3 benchmarks/chip/sweep.py --config smallest --method vmc \
        --tau 0.01 --steps 10 --subblocks 4 --walkers 256 512 1024

In one process that holds the chip: for each walker count, build the run
as the cell does, compile with one sub-block, then time ``--subblocks``
sub-blocks through ``BlockSampler.run_subblock`` and print one JSON line
(moves per second, seconds per generation, acceptance).  This is how the
cells' walker counts were chosen: the count at which moves per second
stops rising, or the largest that fits, whichever is smaller
(``aot_probe.py`` bounds what fits).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / 'src')]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--config', required=True)
    ap.add_argument('--method', required=True)
    ap.add_argument('--tau', type=float, required=True)
    ap.add_argument('--steps', type=int, required=True)
    ap.add_argument('--subblocks', type=int, default=4)
    ap.add_argument('--walkers', type=int, nargs='+', required=True)
    args = ap.parse_args()
    import gc
    import jax
    if jax.devices()[0].platform != 'tpu':
        sys.exit('sweep: JAX found no TPU')
    from repro.compile_cache import enable_compile_cache
    from repro.launch.spec import RunSpec, build_run
    import program
    import system
    enable_compile_cache()
    conf = json.loads((HERE / 'configs' / f'{args.config}.json').read_text())
    program.install(conf)
    sysd = system.build(conf)
    for w in args.walkers:
        run = build_run(RunSpec(system=args.config, method=args.method,
                                n_walkers=w, steps=args.steps, tau=args.tau,
                                equil_steps=0, backend='thread',
                                n_workers=1))
        t = time.time()
        state = run.sampler.init_state(
            0, 1, walkers=system.initial_walkers(sysd, w, 1))
        state, acc, _, _ = run.sampler.run_subblock(state, 0)
        first = time.time() - t
        t = time.time()
        accs = []
        for i in range(args.subblocks):
            state, acc, _, _ = run.sampler.run_subblock(state, 1 + i)
            accs.append(acc.aux.get('accept'))
        dt = time.time() - t
        gens = args.subblocks * args.steps
        n_e = run.cfg.n_elec
        stats = jax.devices()[0].memory_stats() or {}
        print(json.dumps(dict(
            config=args.config, method=args.method, walkers=w,
            steps=args.steps, first_subblock_s=first, seconds=dt,
            gen_ms=1e3 * dt / gens, moves_per_s=gens * w * n_e / dt,
            accept=accs, peak_bytes=stats.get('peak_bytes_in_use'))),
            flush=True)
        del state, run
        gc.collect()


if __name__ == '__main__':
    main()
