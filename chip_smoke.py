"""Bring-up check on a TPU: qmc_run's main path and the five Pallas kernels.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # walker mesh over four chips

One process, phases in order, no fallback.  On one chip it runs
``build_run(RunSpec(...)).run()`` — what ``python -m repro.launch.qmc_run``
does — on the thread backend for the paper's ``smallest`` system (158
electrons, 346 AOs, nothing reduced) with the methods ``vmc``, ``dmc`` and
``sem-vmc``; checks each method's local energies against the host CPU
on the same walkers, one DMC generation against the CPU from the same
walkers and keys, and that the single-electron-move state still tracks a fresh
``slogdet``/``inv`` of the same walkers to the DESIGN.md §6 contract; then
compiles each Pallas kernel for the chip at ``smallest`` widths and
compares it with its ``ref.py``.  With ``--four-chips`` it runs only the
walker mesh: ``vmc`` and ``dmc`` with ``shards=4`` against ``shards=1``
under the same keys.

Each phase prints one JSON line (device, compile and run seconds, energy,
drift, ...).  The last line is ``{"ok": true, "device": {...}}``.  The
script exits non-zero, printing no result, when JAX finds no TPU, a phase
fails, or a phase runs past ``PHASE_LIMIT_S`` (every thread's stack is
then dumped to stderr).
"""
from __future__ import annotations

import argparse
import faulthandler
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / 'src'))

SYSTEM = 'smallest'
WALKERS = 64
DRIFT_TOL = 1e-4          # DESIGN.md §6, tests/test_sem.py
# the chip's per-walker local energies against the host CPU's on the same
# walkers (both fp32), median over walkers, relative to the energy scale.
# Two fp32 evaluation orders on one CPU differ by 1.3e-6 at the median and
# up to 9e-3 on the worst-conditioned of 16 walkers at `smallest`, so the
# median is what is held; a bf16-pass contraction moves every walker.
ELOC_TOL = 1e-4
# one DMC generation from the same walkers and keys, chip against the host
# CPU: energy within this fraction of the CPU's, acceptance within this
# many walkers.  (Over a whole sub-block the two walks part: a rounding
# flips one accept, and branching then follows different walkers.)
DMC_STEP_RTOL = 1e-2
DMC_STEP_FLIPS = 2
# a phase that runs longer than this is a failure: the script dumps every
# thread's stack and exits 1 instead of hanging
PHASE_LIMIT_S = 420


def _emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f'chip_smoke: check failed: {what}')


def _device(jax) -> dict:
    d = jax.devices()[0]
    return dict(platform=d.platform, kind=d.device_kind,
                count=len(jax.devices()))


def _spec(method: str, walkers: int, **kw):
    from repro.launch.spec import RunSpec
    # sem-vmc: 5 sweeps per sub-block, fewer than the 8 between full
    # refreshes, so the drift check sees Sherman–Morrison state.  The
    # all-electron methods move 158 electrons at once: at their default
    # steps (0.3 / 0.02, sized for small molecules) almost nothing is
    # accepted, so they take tau = 0.01.
    sem = method == 'sem-vmc'
    return RunSpec(system=SYSTEM, method=method, n_walkers=walkers,
                   steps=5 if sem else 10, tau=0.0 if sem else 0.01,
                   backend='thread', n_workers=1,
                   subblocks_per_block=2, max_blocks=3, seed=0,
                   equil_steps=20, **kw)


def _assert_tracks_fresh(ens, fresh) -> float:
    """Maintained minv (relative to its scale) and logdet vs a fresh
    recompute, as tests/test_sem.py checks them; returns the worst drift."""
    import numpy as np
    worst = 0.0
    for f in ('minv_up', 'minv_dn'):
        a = np.asarray(getattr(ens, f), np.float64)
        b = np.asarray(getattr(fresh, f), np.float64)
        drift = np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1.0)
        _check(drift <= DRIFT_TOL, f'{f} drift {drift:.3e} > {DRIFT_TOL}')
        worst = max(worst, float(drift))
    d_logdet = float(np.max(np.abs(np.asarray(ens.logdet, np.float64)
                                   - np.asarray(fresh.logdet, np.float64))))
    _check(d_logdet <= DRIFT_TOL,
           f'logdet drift {d_logdet:.3e} > {DRIFT_TOL}')
    _check(bool(np.array_equal(np.asarray(ens.sign), np.asarray(fresh.sign))),
           'maintained sign differs from a fresh slogdet')
    return max(worst, d_logdet)


def _cpu():
    import jax
    return jax.default_device(jax.devices('cpu')[0])


def _eloc_witness(run, state) -> float:
    """The chip's local energies of the initial walkers against the host
    CPU's evaluation of the same positions (the propagator's own restart
    path); returns the median and the largest difference, relative to the
    energy scale."""
    import jax
    import numpy as np
    ens = run.sampler.driver._ensemble_part(state[1])
    r = np.asarray(ens.r)
    e_chip = np.asarray(ens.e_loc, np.float64)
    with _cpu():
        params = jax.device_put(run.sampler.params, jax.devices('cpu')[0])
        st = run.sampler.propagator.init(params, None, r.shape[0], walkers=r)
        e_cpu = np.asarray(run.sampler.driver._ensemble_part(st).e_loc,
                           np.float64)
    _check(bool(np.all(np.isfinite(e_chip))), 'non-finite local energy')
    scale = max(float(np.median(np.abs(e_cpu))), 1.0)
    diff = np.abs(e_chip - e_cpu) / scale
    med = float(np.median(diff))
    _check(med <= ELOC_TOL,
           f'local energies differ from the CPU by {med:.3e} of the '
           f'energy scale {scale:.1f} at the median walker (> {ELOC_TOL})')
    return dict(eloc_vs_cpu_median=med, eloc_vs_cpu_max=float(diff.max()))


def _dmc_step_witness(spec, walkers) -> dict:
    """One DMC generation from ``walkers`` with the same keys, on the chip
    and on the host CPU: block energy and acceptance must agree."""
    import contextlib
    from repro.launch.spec import build_run
    one = spec.replace(steps=1)
    out = {}
    for where, ctx in (('chip', contextlib.nullcontext()), ('cpu', _cpu())):
        with ctx:
            run = build_run(one)
            state = run.sampler.init_state(0, spec.seed, walkers=walkers)
            _, out[where], _, _ = run.sampler.run_subblock(state, 0)
    chip, cpu = out['chip'], out['cpu']
    d_e = abs(chip.e_mean - cpu.e_mean)
    _check(d_e <= DMC_STEP_RTOL * abs(cpu.e_mean),
           f'one DMC step: energy {chip.e_mean} vs CPU {cpu.e_mean}')
    d_a = abs(chip.aux['accept'] - cpu.aux['accept'])
    _check(d_a * spec.n_walkers <= DMC_STEP_FLIPS,
           f'one DMC step: acceptance {chip.aux["accept"]} vs CPU '
           f'{cpu.aux["accept"]}')
    return dict(step_energy=chip.e_mean, step_energy_cpu=cpu.e_mean,
                step_accept=chip.aux['accept'],
                step_accept_cpu=cpu.aux['accept'])


def qmc_run_phase(method: str, walkers: int = WALKERS) -> None:
    """One method through build_run(...).run() on the thread backend."""
    import jax
    import numpy as np
    from repro.launch.spec import build_run

    spec = _spec(method, walkers)
    run = build_run(spec)
    # the first sub-block compiles the block program (the worker thread
    # reuses it) and, for sem-vmc, feeds the drift check
    t0 = time.perf_counter()
    state = jax.block_until_ready(run.sampler.init_state(0, spec.seed))
    compile_s = time.perf_counter() - t0
    eloc_err = _eloc_witness(run, state)
    witness = {}
    if method == 'dmc':
        r0 = np.asarray(run.sampler.driver._ensemble_part(state[1]).r)
        witness = _dmc_step_witness(spec, r0)
    t0 = time.perf_counter()
    state, first, _, _ = run.sampler.run_subblock(state, 0)
    jax.block_until_ready(state)
    compile_s += time.perf_counter() - t0
    drift = None
    if method == 'sem-vmc':
        from repro.core.sem import evaluate_sem
        ens = state[1].ens
        fresh = evaluate_sem(run.cfg, run.params, ens.r)
        drift = _assert_tracks_fresh(ens, fresh)

    t0 = time.perf_counter()
    avg = run.run()
    run_s = time.perf_counter() - t0
    errors = run.worker_errors()
    _check(not errors, f'{method}: worker errors:\n' + '\n'.join(errors))
    _check(avg.n_blocks >= spec.max_blocks,
           f'{method}: {avg.n_blocks} of {spec.max_blocks} blocks arrived')
    _check(math.isfinite(avg.energy) and math.isfinite(avg.error),
           f'{method}: energy {avg.energy} +/- {avg.error}')
    accept = [b.aux.get('accept', float('nan'))
              for b in run.db.blocks(run.run_key)]
    _check(all(0.0 < a < 1.0 for a in accept),
           f'{method}: acceptance {accept} not inside (0, 1)')
    _emit(phase=f'qmc_run:{SYSTEM}/{method}', device=_device(jax),
          walkers=walkers, steps=spec.steps, blocks=avg.n_blocks,
          compile_s=compile_s, run_s=run_s, energy=avg.energy,
          error=avg.error, accept=float(np.mean(accept)),
          first_subblock_energy=first.e_mean, drift=drift, **eloc_err,
          **witness)


def _close(name, got, want, rtol, atol) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    _check(got.shape == want.shape, f'{name}: shape {got.shape} vs '
                                    f'{want.shape}')
    _check(bool(np.all(np.isfinite(got))), f'{name}: non-finite output')
    bad = np.abs(got - want) > atol + rtol * np.abs(want)
    _check(not bool(np.any(bad)),
           f'{name}: {int(bad.sum())} of {bad.size} outside rtol={rtol} '
           f'atol={atol}')
    return float(np.max(np.abs(got - want)))


def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, first, time.perf_counter() - t0


def kernels_phase(walkers: int = WALKERS) -> None:
    """Each Pallas kernel, compiled for the chip, against its ref.py at
    ``smallest`` widths, with the tolerances of its kernel tests."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import aos, screening
    from repro.core.sem import _fused_phi_block, _mo_blocks, evaluate_sem
    from repro.core.vmc import sample_positions
    from repro.kernels.fused_sweep.ops import fused_sweep_block
    from repro.kernels.multidet_ratio.ops import multidet_ratios
    from repro.kernels.multidet_ratio.ref import multidet_ratios_ref
    from repro.kernels.platform import kernel_plan
    from repro.kernels.screened_mo.ops import screened_mo_products
    from repro.kernels.screened_mo.ref import screened_mo_ref
    from repro.kernels.sem_update.ops import sem_rank1_update
    from repro.kernels.sem_update.ref import sem_update_ref
    from repro.kernels.sparse_mo.ops import mo_products_ref, sparse_mo_products
    from repro.systems import build_system
    from repro.systems.bench import synthetic_ci

    cfg, params = build_system(SYSTEM, screen_eps=1e-8)
    R = sample_positions(params, jax.random.PRNGKey(0), walkers, cfg.n_elec)
    pts = R.reshape(-1, 3)
    rng = np.random.default_rng(0)
    # the references run at full fp32: a TPU's default for fp32 matmul
    # operands is one bf16 pass
    highest = jax.default_matmul_precision('highest')

    def report(name, fn, ref, rtol, atol, **plan_dims):
        got, first_s, run_s = _timed(fn)
        with highest:
            want = jax.block_until_ready(ref())
        errs = [_close(f'{name}[{i}]', g, w, rtol, atol)
                for i, (g, w) in enumerate(zip(
                    got if isinstance(got, tuple) else (got,),
                    want if isinstance(want, tuple) else (want,)))]
        plan = kernel_plan(name, (), **plan_dims)
        _check(not plan.interpret, f'{name}: planned for the interpreter')
        _emit(phase=f'kernel:{name}', device=_device(jax), tiles=plan.tiles,
              compile_s=first_s, run_s=run_s, max_abs_err=max(errs))

    # sparse_mo: the ensemble MO product of qmc_run's kernel method
    B, atom_active = aos.eval_ao_block(cfg.basis, params.coords, pts)
    ao_mask = atom_active[:, jnp.asarray(cfg.basis.ao_atom)]
    report('sparse_mo', lambda: sparse_mo_products(params.mo, B, ao_mask),
           lambda: mo_products_ref(params.mo, B), 1e-4, 1e-4)

    # screened_mo: packed candidate lists of the cell-list screening
    idx, active, _ = screening.active_ao_lists(cfg.screening, pts)
    Bp = aos.eval_ao_block_screened(cfg.basis, params.coords, pts, idx,
                                    active)
    report('screened_mo',
           lambda: screened_mo_products(params.mo, Bp, idx, active),
           lambda: screened_mo_ref(params.mo, Bp, idx, active), 1e-4, 1e-4,
           n_ao=cfg.basis.n_ao)

    # sem_update: one Sherman–Morrison move over the ensemble
    n = cfg.n_up
    minv = jnp.asarray(rng.standard_normal((walkers, n, n)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((walkers, n)), jnp.float32)
    row = jnp.asarray(rng.standard_normal((walkers, n)), jnp.float32)
    accept = jnp.asarray(rng.random(walkers) < 0.5)
    report('sem_update',
           lambda: sem_rank1_update(minv, u, row, accept, n // 2),
           lambda: sem_update_ref(minv, u, row, accept, n // 2), 0.0, 1e-6,
           n=n)

    # multidet_ratio: 1000 singles/doubles over the occupied + virtual set
    n_orb, n_det = n + max(8, n // 2), 1000
    ci = synthetic_ci(n, cfg.n_dn, n_orb, n_det, seed=0)
    P = jnp.asarray(rng.standard_normal((walkers, n_orb, n)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((walkers, n_orb)), jnp.float32)
    row_t = jnp.asarray(rng.standard_normal((walkers, n)), jnp.float32)
    ro = jnp.asarray(rng.standard_normal((walkers, n_det)), jnp.float32)
    args = (P, g, row_t, ci.holes_up, ci.parts_up, ci.coeffs, ro)
    report('multidet_ratio', lambda: multidet_ratios(*args),
           lambda: multidet_ratios_ref(*args), 1e-5, 1e-5)

    # fused_sweep: the up block's whole sweep against the scan oracle
    ens = evaluate_sem(cfg, params, R)
    A_up, _ = _mo_blocks(cfg, params)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    r_prop = R[:, :n] + 0.3 * jax.random.normal(ks[0], (walkers, n, 3))
    phi = _fused_phi_block(cfg, params, A_up,
                           r_prop.reshape(-1, 3)).reshape(walkers, n, -1)
    en = 0.05 * jax.random.normal(ks[1], (walkers, n))
    logu = jnp.log(jax.random.uniform(ks[2], (walkers, n), minval=1e-6))

    def sweep(use_kernel):
        return fused_sweep_block(
            ens.minv_up, phi, ens.r, r_prop, en, logu, params.jastrow.b_ee,
            offset=0, n_up=n, use_kernel=use_kernel, tile_w=8)

    got, first_s, run_s = _timed(lambda: sweep(True))
    with highest:
        want = jax.block_until_ready(sweep(False))
    names = ('r', 'minv', 'P', 'rdet', 'accept')
    for name, a, b in zip(names, got, want):
        if name in ('r', 'accept'):
            _check(bool(np.array_equal(np.asarray(a), np.asarray(b))),
                   f'fused_sweep: {name} differs from the scan oracle')
    _check(bool(np.any(np.asarray(got[4]))), 'fused_sweep accepted nothing')
    a, b = np.asarray(got[1], np.float64), np.asarray(want[1], np.float64)
    d_minv = float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1.0))
    _check(d_minv <= DRIFT_TOL,
           f'fused_sweep: minv {d_minv:.3e} vs the scan oracle exceeds '
           f'{DRIFT_TOL}')
    plan = kernel_plan('fused_sweep', (8,), n=n, n_e=cfg.n_elec)
    _check(not plan.interpret, 'fused_sweep: planned for the interpreter')
    _emit(phase='kernel:fused_sweep', device=_device(jax), tiles=plan.tiles,
          compile_s=first_s, run_s=run_s, minv_drift=d_minv)


def mesh_phase(method: str, shards: int = 4, walkers: int = WALKERS,
               subblocks: int = 2) -> None:
    """``shards`` walker-mesh shards against one device, same keys."""
    import jax
    import numpy as np
    from repro.launch.spec import build_run

    runs = {s: build_run(_spec(method, walkers, shards=s))
            for s in (1, shards)}
    out, times = {}, {}
    for s, run in runs.items():
        t0 = time.perf_counter()
        state = run.sampler.init_state(0, 0)
        subs = []
        for step in range(subblocks):
            state, acc, walk, energies = run.sampler.run_subblock(state,
                                                                  step)
            subs.append((acc, walk, energies))
        times[s] = time.perf_counter() - t0
        out[s] = (state, subs)

    ens = runs[shards].sampler.driver._ensemble_part(out[shards][0][1])
    placed = {d for leaf in jax.tree.leaves(ens)
              for d in leaf.sharding.device_set}
    _check(len(placed) == shards,
           f'{method}: ensemble on {len(placed)} devices, not {shards}')
    # README/DESIGN.md §5: walker paths identical (bitwise) for
    # power-of-two walkers per shard, block statistics equal to fp32
    # reduction tolerance (tests/test_driver.py uses rel = abs = 1e-5)
    per_shard = walkers // shards
    bitwise = per_shard & (per_shard - 1) == 0
    worst_path = worst_stat = 0.0
    for (a1, w1, _), (a4, w4, _) in zip(out[1][1], out[shards][1]):
        w1, w4 = np.asarray(w1), np.asarray(w4)
        if bitwise:
            _check(bool(np.array_equal(w1, w4)),
                   f'{method}: shards={shards} walker paths differ from '
                   f'shards=1 at {per_shard} walkers per shard')
        worst_path = max(worst_path, float(np.max(np.abs(w1 - w4))))
        pairs = [(a1.weight, a4.weight), (a1.e_mean, a4.e_mean),
                 (a1.e2_mean, a4.e2_mean)]
        pairs += [(a1.aux[k], a4.aux[k]) for k in a1.aux]
        for x, y in pairs:
            _check(abs(x - y) <= 1e-5 + 1e-5 * abs(y),
                   f'{method}: shards={shards} statistic {x} vs {y}')
            worst_stat = max(worst_stat, abs(x - y))

    # the same mesh through the entry point a user calls
    run = runs[shards]
    t0 = time.perf_counter()
    avg = run.run()
    run_s = time.perf_counter() - t0
    _check(not run.worker_errors(), f'{method}: worker errors')
    _check(avg.n_blocks >= run.spec.max_blocks and math.isfinite(avg.energy)
           and math.isfinite(avg.error),
           f'{method}: shards={shards} run gave {avg}')
    _emit(phase=f'mesh:{SYSTEM}/{method}', device=_device(jax),
          shards=shards, devices=sorted(d.id for d in placed),
          walkers=walkers, paths_bitwise=bitwise, max_path_diff=worst_path,
          max_stat_diff=worst_stat,
          subblock_s={str(s): t for s, t in times.items()}, run_s=run_s,
          energy=avg.energy, error=avg.error, blocks=avg.n_blocks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--four-chips', action='store_true',
                    help='run only the walker mesh over four chips')
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    dev = _device(jax)
    if dev['platform'] != 'tpu':
        print(f'chip_smoke: no TPU (JAX platform {dev["platform"]!r})',
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if dev['count'] < need:
        print(f'chip_smoke: {need} chips needed, {dev["count"]} found',
              file=sys.stderr)
        return 1
    _emit(phase='start', device=dev, compile_cache=cache)
    if args.four_chips:
        phases = [lambda m=m: mesh_phase(m) for m in ('vmc', 'dmc')]
    else:
        phases = [lambda m=m: qmc_run_phase(m)
                  for m in ('vmc', 'dmc', 'sem-vmc')] + [kernels_phase]
    for phase in phases:
        faulthandler.dump_traceback_later(PHASE_LIMIT_S, exit=True)
        phase()
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({'ok': True, 'device': dev}), flush=True)
    return 0


if __name__ == '__main__':
    raise SystemExit(main())
