"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload smallest.vmc --seed 7 \
        --seconds 20 --trace 0

The cell drives ``qmc_run``'s served path as ``python -m
repro.launch.qmc_run`` does: ``build_run(RunSpec(...))`` on the thread
backend with one worker and a database in the run's own temporary
directory, then ``manager.run()`` until a wall-clock stop.  The molecule
comes from the configuration file as data (``program.py``).

* Set-up (``setup_s``) is everything from process start to the start of
  the window: JAX import, system build, the compile or cache load of the
  block program, the initial evaluation of the walkers, one warm-up
  sub-block through the same ``BlockSampler``, and the worker's first
  block.  The walkers of the warm-up reach
  the worker through the manager's restart path
  (``add_worker(init_walkers=...)``).
* The window starts at the first block the database stores and ends at
  the last block it stores before ``--seconds`` have passed.
  ``moves_per_s`` is all the electron-move proposals of the blocks stored
  after the first, over the time between the first and the last.  A
  compile inside the window fails the run.
* After the window the walkers it produced are compared with the plain
  reference (``compare.py``, ``reference.py``); the numbers and their
  limits are the last lines on standard error and the ``checks`` key of
  the result.

With ``--trace 1`` a few blocks of the window are traced with the JAX
profiler, and the result carries the per-layer metrics and a breakdown.
The last line on standard output is one JSON object.  The run fails,
printing no result, when JAX finds no TPU or fewer chips than the cell
asks for.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import bench  # noqa: E402
import compare  # noqa: E402

COMPILE_EVENT = '/jax/core/compile/backend_compile_duration'
FIRST_BLOCK_TIMEOUT_S = 240.0
COMPILES: list[float] = []       # wall time of every compile or cache load
_LISTENING: list[bool] = []


def _count_compiles(event, duration, **kw):
    if event == COMPILE_EVENT:
        COMPILES.append(time.time())


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class TimedSampler:
    """The worker's ``Sampler``: delegates to the program's BlockSampler.

    Records each sub-block's wall time, statistics and walkers, keeps the
    last state the program returned, and with ``annotate`` wraps each call
    in a ``bench.run_subblock`` profiler span.
    """

    def __init__(self, inner, per_block: int, annotate: bool = False):
        self.inner = inner
        self.per_block = per_block
        self.annotate = annotate
        self.records = []          # (t_start, t_end, step, stats dict)
        self.cond = threading.Condition()
        self.n_sub = 0
        self.walkers = None        # host positions of the current state
        self.last_in = None        # (step, walkers) of the last call
        self.last_state = None

    def init_state(self, worker_id, seed, walkers=None):
        state = self.inner.init_state(worker_id, seed, walkers)
        ens = state[1].ens if hasattr(state[1], 'ens') else state[1]
        self.walkers = np.asarray(ens.r)
        return state

    def run_subblock(self, state, step):
        import jax
        self.last_in = (step, self.walkers)
        t0 = time.time()
        if self.annotate:
            with jax.profiler.TraceAnnotation('bench.run_subblock'):
                out = self.inner.run_subblock(state, step)
        else:
            out = self.inner.run_subblock(state, step)
        t1 = time.time()
        state, acc, walkers, _ = out
        self.last_state = state
        self.walkers = walkers
        self.records.append((t0, t1, step, dict(
            weight=acc.weight, e_mean=acc.e_mean, e2_mean=acc.e2_mean,
            accept=acc.aux.get('accept', float('nan')),
            ao_fill=acc.aux.get('ao_fill'))))
        with self.cond:
            self.n_sub += 1
            self.cond.notify_all()
        return out

    def wait_blocks(self, n: int, timeout: float) -> bool:
        with self.cond:
            return self.cond.wait_for(
                lambda: self.n_sub >= n * self.per_block, timeout)


def _annotated(fn, name):
    import jax

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)
    return wrapped


def sub_key(seed: int, step: int):
    """The sub-block key worker 0 uses at ``step`` (BlockSampler's rule)."""
    import jax
    wkey = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    _, k_blocks = jax.random.split(wkey)
    return jax.random.fold_in(k_blocks, step)


def _program_state(state) -> dict:
    """Host copies of the fields the final-state comparison reads."""
    ens = state[1].ens if hasattr(state[1], 'ens') else state[1]
    out = {}
    for k in ('r', 'sign', 'log_psi', 'e_loc', 'drift', 'minv_up',
              'minv_dn'):
        if hasattr(ens, k):
            out[k] = np.asarray(getattr(ens, k), np.float32)
    return out


def _device(jax) -> dict:
    devs = jax.devices()
    out = dict(platform=devs[0].platform, kind=devs[0].device_kind,
               count=len(devs))
    stats = devs[0].memory_stats() or {}
    out['memory_peak_bytes'] = int(stats.get('peak_bytes_in_use', 0))
    return out


def check_chip(jax, chips: int) -> None:
    devs = jax.devices()
    if devs[0].platform != 'tpu':
        raise NoChip(f'JAX found no TPU (platform {devs[0].platform!r})')
    if len(devs) < chips:
        raise NoChip(f'the cell asks for {chips} chips, JAX found '
                     f'{len(devs)}')


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, require_chip: bool = True,
             controls: tuple = ()) -> dict:
    """One run of one cell -> the result object (``checks`` last).

    ``controls`` names reference precisions (``reference.PRECISIONS``) to
    put in the program's place as well; their numbers come back under
    ``control`` (``control.py`` reads them, a benchmark run asks none).
    """
    spec = bench.load(root)
    c = bench.cell(spec, root, name)
    cell, conf, entry = c['cell'], c['config'], c['entry']

    import jax
    if require_chip:
        check_chip(jax, entry['chips'])
    sys.path.insert(0, str(root / 'src'))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program, however quick to compile, is kept in the cache
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(_count_compiles)
        _LISTENING.append(True)
    from repro.launch.spec import RunSpec, build_run
    import program
    import reference
    import system
    sysd = system.build(conf)
    program.install(conf)

    tmp = tempfile.TemporaryDirectory(prefix='chipbench-')
    W, steps, spb = cell['walkers'], cell['steps'], cell['subblocks_per_block']
    rs = RunSpec(system=conf['name'], method=cell['method'], n_walkers=W,
                 steps=steps, tau=cell['tau'], equil_steps=cell['equil_steps'],
                 subblocks_per_block=spb, backend='thread', n_workers=1,
                 max_blocks=0, seed=seed, db=str(Path(tmp.name) / 'qmc.db'))
    t = time.time()
    run = build_run(rs)
    build_s = time.time() - t
    sampler = run.sampler
    # the walkers enter on the restart path, from positions drawn here:
    # the program's own cold start puts some electrons outside every
    # atom's radius, and leaves most Slater matrices near singular
    # (PERF.md, Open questions)
    state = jax.block_until_ready(sampler.init_state(
        0, seed, walkers=system.initial_walkers(sysd, W, seed)))
    t = time.time()
    state, acc, walkers, _ = sampler.run_subblock(state, 0)
    jax.block_until_ready(state)
    first_subblock_s = time.time() - t
    del state

    ts = TimedSampler(sampler, spb, annotate=trace)
    run.manager.sampler = ts
    trace_dir = Path(tmp.name) / 'trace'
    if trace:
        run.db.append = _annotated(run.db.append, 'bench.db_append')
        run.manager.poll = _annotated(run.manager.poll, 'bench.manager_poll')

    def monitor():
        if not ts.wait_blocks(1, FIRST_BLOCK_TIMEOUT_S):
            run.manager.request_stop()
            return
        t_first = time.time()
        deadline = t_first + seconds + 0.25
        if trace:
            jax.profiler.start_trace(str(trace_dir))
            win = jax.profiler.TraceAnnotation('bench.window')
            win.__enter__()
            ts.wait_blocks(1 + cell['trace_blocks'],
                           max(deadline - time.time(), 0.0))
            win.__exit__(None, None, None)
            jax.profiler.stop_trace()
        time.sleep(max(deadline - time.time(), 0.0))
        run.manager.request_stop()

    mon = threading.Thread(target=monitor, daemon=True)
    run.manager.add_worker(init_walkers=walkers)
    mon.start()
    run.manager.run()
    t_stopped = time.time()
    for w in run.manager.workers:
        w.join(timeout=None)
    mon.join()
    device = _device(jax)
    errors = run.worker_errors()
    db_rejects = sum(run.db.rejects.values())

    rows = sorted(run.db.blocks(run.run_key), key=lambda b: b.block_id)
    n_e = conf['n_elec']
    t_first = rows[0].timestamp if rows else math.inf
    win_rows = [b for b in rows if b.timestamp <= t_first + seconds]
    t0 = win_rows[0].timestamp if win_rows else math.inf
    t1 = win_rows[-1].timestamp if win_rows else math.inf
    n_win = max(len(win_rows) - 1, 0)
    gens = n_win * spb * steps
    win_s = t1 - t0 if n_win else 0.0
    in_win = [r for r in ts.records if t0 < r[1] <= t1]
    window = dict(t0=t0, t1=t1, seconds=win_s, generations=gens,
                  moves=gens * W * n_e, blocks=n_win,
                  inside_s=sum(r[1] - r[0] for r in in_win))
    n_compiles = sum(1 for tc in COMPILES if t0 < tc <= t1)

    # -- correctness: the walkers the window produced --------------------
    step, r_in = ts.last_in
    prog = _program_state(ts.last_state)
    sub_stats = [(r[1], r[3]['weight'], r[3]['e_mean'], r[3]['e2_mean'])
                 for r in ts.records]
    db_rows = [(b.block_id, b.weight, b.e_mean, b.e2_mean) for b in rows]
    ao_fill = [r[3]['ao_fill'] for r in in_win if r[3]['ao_fill']]
    accept = float(np.mean([r[3]['accept'] for r in in_win])) \
        if in_win else float('nan')
    del ts, run, sampler
    gc.collect()

    ref = reference.Reference(sysd, chunk=conf['reference_chunk'])
    ev = {k: np.asarray(v) for k, v in ref.evaluate(prog['r']).items()}
    numbers = compare.final_state(prog, ev)
    key = sub_key(seed, step)

    def replay(r):
        if cell['method'] == 'vmc':
            return r.replay_vmc(r_in, key, steps, cell['tau'])
        return r.replay_sem(r_in, key, steps, cell['tau'])

    rens = replay(ref)
    numbers.update(compare.replay(prog['r'], rens['r']))
    # the manager stops its forwarders at most ~1 s before run() returns
    numbers.update(compare.stored(db_rows, sub_stats, spb, t_stopped - 1.0))
    control = {}
    for prec in controls:
        cref = reference.Reference(sysd, precision=prec,
                                   chunk=conf['reference_chunk'])
        cev = {k: np.asarray(v) for k, v in cref.evaluate(prog['r']).items()
               if k in prog}
        cnum = compare.final_state(cev, ev)
        cnum.update(compare.replay(replay(cref)['r'], rens['r']))
        control[prec] = cnum
    numbers['db_rejects'] = float(db_rejects)
    numbers['compiles'] = float(n_compiles)
    numbers['worker_errors'] = float(len(errors))
    limits = dict(cell['limits'], db_rejects=0, compiles=0, worker_errors=0)
    correct, checks = compare.judge(numbers, limits)
    if n_win < 2:
        correct = False
    failed = sum(1 for r in in_win if not np.isfinite(r[3]['e_mean']))

    result = dict(correct=bool(correct), attempted=len(in_win),
                  failed=int(failed) + len(errors), device=device)
    if trace:
        trace_mod = bench.load_module(HERE / 'trace.py')
        found = glob.glob(str(trace_dir / 'plugins' / 'profile' / '*' /
                              '*.xplane.pb'))
        red = trace_mod.reduce(*trace_mod.load(found[0])) if found else None
        work = bench.work(cell['work'])
        prims = float(np.mean(np.sum(sysd.prim_coeff != 0, axis=1)))
        flops = work.flops(sysd.n_up, sysd.n_dn, float(np.mean(ao_fill)), W,
                           accept=accept, prims=prims) if ao_fill else None
        ctx = dict(build_s=build_s, first_subblock_s=first_subblock_s,
                   window=window, trace=red, flops_per_gen=flops,
                   peak=bench.peak(device['kind']) if require_chip
                   else None)
        metrics = {}
        for m in bench.metrics_for(spec, 'per_layer', name):
            value = bench.reader(m['name'])(ctx)
            if value is not None:
                metrics[m['name']] = dict(value=float(value), unit=m['unit'])
        result['metrics'] = metrics
        if red:
            device.update(busy_s=red['busy_s'], window_s=red['window_s'])
            result['breakdown'] = dict(device_ops=red['device_ops'],
                                       idle_gaps=red['idle_gaps'])
    else:
        e2e = dict(moves_per_s=window['moves'] / win_s if win_s else None,
                   setup_s=t0 - T_START)
        result['metrics'] = {
            m['name']: dict(value=e2e[m['name']], unit=m['unit'])
            for m in bench.metrics_for(spec, 'end_to_end', name)
            if e2e.get(m['name']) is not None}
    for err in errors:
        print(err, file=sys.stderr)
    if controls:
        result['control'] = control
    result['checks'] = checks
    tmp.cleanup()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except (NoChip, bench.BenchmarkError) as e:
        print(f'chip benchmark: {e}', file=sys.stderr)
        return 2
    for k, v in result['checks'].items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
