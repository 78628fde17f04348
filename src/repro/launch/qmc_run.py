"""QMC production launcher — the paper's deployment (fig. 3) end to end.

    manager -> data server (sqlite DB) -> forwarder tree -> workers

A thin argparse front over the declarative ``launch.spec.RunSpec``: flags
map one-to-one onto spec fields and ``build_run`` assembles the whole
sampler / driver / manager stack.  ``--backend`` picks the execution
substrate (paper §V: "all kinds of computational platforms"):

* ``thread``  (default) — in-process worker threads (XLA releases the GIL);
* ``process`` — one OS process per worker, pickled block packets pumped
  into the forwarder tree (real isolation, true multi-core);
* ``sim``     — deterministic simulated grid (``--sim-latency``,
  ``--sim-drop``) for fault-tolerance drills;
* ``grid``    — real multi-host TCP grid: the manager listens on
  ``--listen HOST:PORT`` and workers (localhost subprocesses by default,
  or remote ``python -m repro.launch.qmc_worker --connect HOST:PORT``)
  attach with heartbeats, reconnect backoff, and work stealing.

``--method vmc|dmc|sem-vmc|opt-vmc|fused-vmc`` selects the propagator
plug-in (``opt-vmc`` runs the outer wavefunction-optimization loop of
DESIGN.md §10 instead of a single sampling run; ``fused-vmc`` is the
single-electron-move sampler with the whole sweep fused into one batched
dispatch per spin block — DESIGN.md §13 — and honors ``--precision
fp32|bf16|fp16`` reduced-precision state storage); ``--shards N``
shards each worker's walker axis over N local devices (DESIGN.md §5).  The
database IS the checkpoint: re-running with the same --db resumes from the
stored walker reservoir and keeps appending blocks under the same CRC-32
run key — which hashes only critical data, so any backend/worker layout
extends the same averages.

  PYTHONPATH=src python -m repro.launch.qmc_run --system h2 --method dmc \
      --workers 4 --blocks 40 --backend process --db /tmp/h2.sqlite
"""
from __future__ import annotations

import argparse

from repro.compile_cache import enable_compile_cache
from repro.launch.spec import GridConfig, RunSpec, SimGridConfig, build_run


def parse_spec(argv=None) -> RunSpec:
    """CLI flags -> RunSpec (exposed separately for tests/tooling)."""
    ap = argparse.ArgumentParser()
    ap.add_argument('--system', default='h2',
                    help='h|h2|heh+|water|smallest|b-strand|...')
    ap.add_argument('--method',
                    choices=('vmc', 'dmc', 'sem-vmc', 'opt-vmc',
                             'fused-vmc'),
                    default='vmc')
    ap.add_argument('--n-det', type=int, default=1,
                    help='CI expansion size (1: single determinant; >1: '
                         'synthetic multideterminant wavefunction, all '
                         'ratios off the shared reference inverse)')
    ap.add_argument('--backend',
                    choices=('thread', 'process', 'sim', 'grid'),
                    default='thread',
                    help='execution substrate for the workers')
    ap.add_argument('--workers', type=int, default=2)
    ap.add_argument('--walkers', type=int, default=32,
                    help='walkers per worker (paper: 10-100/core)')
    ap.add_argument('--steps', type=int, default=50,
                    help='MC generations per sub-block')
    ap.add_argument('--blocks', type=int, default=20)
    ap.add_argument('--shards', type=int, default=1,
                    help='device shards for each walker ensemble '
                         '(1: single-device; N: walkers mesh over N '
                         'local devices)')
    ap.add_argument('--target-error', type=float, default=0.0)
    ap.add_argument('--wall-clock', type=float, default=0.0)
    ap.add_argument('--tau', type=float, default=0.0)
    ap.add_argument('--screen-eps', type=float, default=-1.0,
                    help='AO cutoff tolerance for cell-list distance '
                         'screening (DESIGN.md §11).  Negative (default): '
                         'screening off; 0: drop only exact zeros (bitwise-'
                         'identical estimator, linear-scaling cost); > 0: '
                         'tolerance cutoffs (enters the run key)')
    ap.add_argument('--precision', choices=('fp32', 'bf16', 'fp16'),
                    default='fp32',
                    help='storage policy for the maintained SEM inverses / '
                         'CI P-tables (DESIGN.md §13).  bf16/fp16 halve '
                         'the resting ensemble footprint; all ratios and '
                         'updates still accumulate in fp32 and the drift '
                         'contract is enforced per dtype.  Non-default '
                         'values enter the run key')
    ap.add_argument('--db', default=':memory:')
    ap.add_argument('--e-trial', type=float, default=None)
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--opt-steps', type=int, default=5,
                    help='[opt-vmc] outer parameter-update iterations')
    ap.add_argument('--opt-solver', choices=('sr', 'lm'), default='sr',
                    help='[opt-vmc] stochastic reconfiguration or linear '
                         'method update')
    ap.add_argument('--opt-lr', type=float, default=0.1,
                    help='[opt-vmc] SR step scale')
    ap.add_argument('--sr-damping', type=float, default=1e-2,
                    help='[opt-vmc] diagonal regularization of the overlap '
                         'matrix')
    ap.add_argument('--opt-blocks', type=int, default=4,
                    help='[opt-vmc] blocks sampled per parameter version')
    ap.add_argument('--ckpt-dir', default='',
                    help='[opt-vmc] per-step checkpoint directory '
                         '(empty: no checkpoints; an existing directory '
                         'resumes from its latest step)')
    ap.add_argument('--sim-latency', type=float, default=0.0,
                    help='[sim backend] seconds per worker->tree send')
    ap.add_argument('--sim-drop', type=float, default=0.0,
                    help='[sim backend] per-packet loss probability')
    ap.add_argument('--listen', default='127.0.0.1:0', metavar='HOST:PORT',
                    help='[grid backend] TCP listen address for workers '
                         '(port 0: ephemeral, printed at startup; use '
                         '0.0.0.0:PORT to accept remote hosts)')
    ap.add_argument('--no-local-workers', action='store_true',
                    help='[grid backend] do not spawn localhost workers; '
                         'wait for remote qmc_worker processes to attach '
                         '(elastic join)')
    ap.add_argument('--heartbeat-timeout', type=float, default=2.0,
                    help='[grid backend] silence after which a worker is '
                         'declared dead (its lease is re-queued)')
    args = ap.parse_args(argv)
    from repro.launch.qmc_worker import parse_address
    host, port = parse_address(args.listen)
    return RunSpec(
        system=args.system, method=args.method, n_det=args.n_det,
        tau=args.tau, screen_eps=args.screen_eps,
        precision=args.precision,
        e_trial=args.e_trial, n_walkers=args.walkers, steps=args.steps,
        shards=args.shards, backend=args.backend, n_workers=args.workers,
        grid=SimGridConfig(latency=args.sim_latency, drop_rate=args.sim_drop,
                           seed=args.seed),
        net=GridConfig(host=host, port=port,
                       heartbeat_timeout=args.heartbeat_timeout,
                       local_workers=not args.no_local_workers),
        opt_steps=args.opt_steps, opt_solver=args.opt_solver,
        opt_lr=args.opt_lr, sr_damping=args.sr_damping,
        opt_blocks_per_step=args.opt_blocks, ckpt_dir=args.ckpt_dir,
        max_blocks=args.blocks, target_error=args.target_error,
        wall_clock_limit=args.wall_clock, db=args.db, seed=args.seed)


def main(argv=None):
    """Parse flags, build the run, execute to completion, print stats.

    Exits with status 1 when any worker died with an error, after printing
    each traceback and the statistics the surviving workers gathered.
    """
    spec = parse_spec(argv)
    enable_compile_cache()
    run = build_run(spec)
    print(f'run_key={run.run_key} system={spec.system} '
          f'method={spec.method} backend={spec.backend}: '
          f'{spec.n_workers} workers x {spec.n_walkers} walkers'
          + (f' x {spec.shards} shards' if spec.shards > 1 else ''))
    if spec.backend == 'grid':
        host, port = run.backend.address
        print(f'grid listening on {host}:{port} — attach workers with: '
              f'python -m repro.launch.qmc_worker --connect {host}:{port}')
    avg = run.run()
    errors = run.worker_errors()
    for err in errors:
        print('WORKER ERROR:\n', err)
    print(avg)
    if errors:
        raise SystemExit(f'qmc_run: {len(errors)} worker(s) failed')
    return avg


if __name__ == '__main__':
    main()
