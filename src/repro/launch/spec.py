"""Declarative run description: one ``RunSpec``, any execution substrate.

The paper's §V framework is "universal, dynamic, fault-tolerant, and
load-balanced ... adapted to all kinds of computational platforms".  This
module is the single front door to that framework: a frozen ``RunSpec``
captures *what* to run — system + wavefunction method + propagator choice +
ensemble/shard layout + stopping criteria + resources — and
``build_run(spec)`` compiles it against an interchangeable execution
substrate (``--backend thread | process | sim``), assembling the
sampler / driver / manager stack that used to be hand-wired across
``qmc_run``, ``runtime.samplers`` and ``runtime.manager``:

    spec = RunSpec(system='h2', method='dmc', n_workers=4, max_blocks=40,
                   backend='process')
    result = build_run(spec).run()

Critical data (the CRC-32 run key) is derived from the spec's *estimator*
fields only — method, tau, geometry, MOs — so the same physics on a
different substrate, worker count, or block length lands in the same
database rows and stays combinable (paper §V.C).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.runtime import (GridConfig, QMCManager, ResultDatabase,
                           RunControl, SimGridConfig, critical_data_key,
                           make_backend)
from repro.runtime.samplers import BlockSampler
from repro.systems import build_system

# mirrors the built-in core.driver registrations; kept as a literal so
# spec construction/validation stays jax-import-free (the registry itself
# is consulted lazily for tau defaults and propagator construction)
METHODS = ('vmc', 'dmc', 'sem-vmc', 'opt-vmc', 'fused-vmc')
OPT_SOLVERS = ('sr', 'lm')
BACKEND_NAMES = ('thread', 'process', 'sim', 'grid')
# mirrors core.slater.PRECISIONS (jax-import-free for the same reason)
PRECISIONS = ('fp32', 'bf16', 'fp16')


@dataclasses.dataclass(frozen=True)
class RunSpec:
    """One declarative QMC run: physics + layout + stopping + resources.

    Everything ``build_run`` needs; substrate-independent by construction.
    ``tau=0`` means the method default (0.3 VMC / sem-vmc proposal width,
    0.02 DMC).  ``grid`` only applies to ``backend='sim'``; ``net`` (TCP
    listen address + heartbeat/rebalance policy) only to ``backend='grid'``.
    """

    # physics: system + wavefunction + propagator choice
    system: str = 'h2'
    method: str = 'vmc'              # vmc | dmc | sem-vmc
    n_det: int = 1                   # CI expansion size (1: single det;
    #                                  >1: synthetic multidet wavefunction
    #                                  via systems.build_system, seeded by
    #                                  ``seed`` — critical data, enters
    #                                  the run key)
    tau: float = 0.0                 # 0 -> method default
    e_trial: float | None = None     # DMC reference energy (None: guess)
    equil_steps: int = 100           # DMC cold-start VMC equilibration
    screen_eps: float = -1.0         # AO cutoff tolerance for the cell-list
    #                                  screening pipeline (core.screening).
    #                                  Negative: screening off (dense path,
    #                                  the historical behavior).  >= 0:
    #                                  critical data — enters the run key.
    precision: str = 'fp32'          # storage policy for the maintained
    #                                  SEM inverses / P-tables ('fp32' |
    #                                  'bf16' | 'fp16'; DESIGN.md §13).
    #                                  Reduced dtypes quantize the resting
    #                                  state (fp32 accumulation throughout)
    #                                  and are critical data — they enter
    #                                  the run key; 'fp32' keeps
    #                                  pre-existing keys stable.

    # ensemble / shard layout
    n_walkers: int = 32              # walkers per worker (paper: 10-100)
    steps: int = 50                  # MC generations per sub-block
    shards: int = 1                  # local devices per worker ensemble

    # resources (the platform axis)
    backend: str = 'thread'          # thread | process | sim | grid
    n_workers: int = 2
    subblocks_per_block: int = 4
    grid: SimGridConfig = dataclasses.field(default_factory=SimGridConfig)
    net: GridConfig = dataclasses.field(default_factory=GridConfig)

    # wavefunction optimization (method='opt-vmc'; DESIGN.md §10)
    opt_steps: int = 5               # outer parameter-update iterations
    opt_solver: str = 'sr'           # sr (stochastic reconfig) | lm (linear)
    opt_lr: float = 0.1              # SR step scale
    sr_damping: float = 1e-2         # diagonal shift on the overlap matrix
    opt_blocks_per_step: int = 4     # blocks sampled per parameter version
    ckpt_dir: str = ''               # per-step checkpoints ('' = off)

    # stopping criteria
    max_blocks: int = 20
    target_error: float = 0.0        # Ha, stderr target (0: off)
    wall_clock_limit: float = 0.0    # seconds (0: off)

    # bookkeeping
    db: str = ':memory:'
    seed: int = 0
    n_kept: int = 64                 # walker reservoir (checkpoint) size
    poll_interval: float = 0.05

    def __post_init__(self):
        if self.method not in METHODS:
            raise ValueError(f'unknown method {self.method!r} '
                             f'(choose from {METHODS})')
        if self.backend not in BACKEND_NAMES:
            raise ValueError(f'unknown backend {self.backend!r} '
                             f'(choose from {BACKEND_NAMES})')
        if self.shards > 1 and self.backend in ('process', 'grid'):
            raise ValueError(
                'shards > 1 requires the thread or sim backend: a device '
                'mesh cannot be shipped to worker processes or across '
                'grid hosts')
        if self.n_det < 1:
            raise ValueError(f'n_det must be >= 1, got {self.n_det}')
        if self.opt_solver not in OPT_SOLVERS:
            raise ValueError(f'unknown opt_solver {self.opt_solver!r} '
                             f'(choose from {OPT_SOLVERS})')
        if self.opt_steps < 1:
            raise ValueError(f'opt_steps must be >= 1, got {self.opt_steps}')
        if self.precision not in PRECISIONS:
            raise ValueError(f'unknown precision {self.precision!r} '
                             f'(choose from {PRECISIONS})')

    def check_platform(self, platform: str | None = None) -> None:
        """Refuse layouts that start local worker processes on a TPU.

        The parent builds the wavefunction with ``jnp`` before any worker
        starts, so on a TPU it already holds the chip, and a chip belongs
        to one process: a ``process`` worker or a localhost ``grid`` worker
        would fail or hang trying to reach it.  The thread and sim backends
        and remote grid workers (on their own hosts) are unaffected.
        ``platform`` defaults to ``jax.default_backend()``.
        """
        local = (self.backend == 'process'
                 or (self.backend == 'grid' and self.net.local_workers))
        if not local:
            return
        if platform is None:
            import jax
            platform = jax.default_backend()
        if platform == 'tpu':
            what = ('process workers' if self.backend == 'process'
                    else 'localhost grid workers')
            raise ValueError(
                f'backend={self.backend!r} starts {what}, but on a TPU the '
                f'parent process holds the chip (it builds the wavefunction '
                f'first) and a chip belongs to one process; use the thread '
                f'backend, or grid workers attached from other hosts')

    def replace(self, **kw) -> 'RunSpec':
        """Functional update (dataclasses.replace convenience)."""
        return dataclasses.replace(self, **kw)

    def resolved_tau(self) -> float:
        """The effective step size (the method's registered default when
        tau == 0) — this value, not the raw field, enters the run key."""
        if self.tau:
            return self.tau
        from repro.core.driver import method_default_tau
        return method_default_tau(self.method)


# RunSpec fields that may cross a trust boundary as plain JSON data.
# Everything a client can set is a scalar; the two nested configs are
# rebuilt field-by-field from their own whitelists — nothing is ever
# unpickled or eval'd on the receive path (the packets.py discipline).
_SCALAR_FIELDS = tuple(
    f.name for f in dataclasses.fields(RunSpec)
    if f.name not in ('grid', 'net'))


def spec_to_payload(spec: RunSpec) -> dict:
    """RunSpec -> plain-JSON payload (the wire/database representation).

    The inverse of ``spec_from_payload``; stored under the run key in the
    database's ``runs`` registry, shipped over the service protocol, and
    embedded in grid WELCOME frames.  Pure data: scalars + two nested
    dicts of scalars.
    """
    out = {f: getattr(spec, f) for f in _SCALAR_FIELDS}
    out['grid'] = dataclasses.asdict(spec.grid)
    out['net'] = dataclasses.asdict(spec.net)
    # tuples are not JSON; normalize to lists for a stable round trip
    for cfg in (out['grid'], out['net']):
        for k, v in cfg.items():
            if isinstance(v, tuple):
                cfg[k] = [list(x) if isinstance(x, (tuple, list)) else x
                          for x in v]
    return out


def spec_from_payload(payload: dict) -> RunSpec:
    """Plain-JSON payload -> validated RunSpec (strict whitelist).

    Unknown fields raise ``ValueError`` (a client cannot smuggle state
    into the engine), nested configs are rebuilt from their dataclass
    whitelists, and ``RunSpec.__post_init__`` re-validates the result —
    the one ingest gate for every spec that arrives over the wire or is
    reloaded from the database registry.
    """
    if not isinstance(payload, dict):
        raise ValueError(f'spec payload must be a dict, got '
                         f'{type(payload).__name__}')
    kw = {}
    for name, value in payload.items():
        if name == 'grid':
            allowed = {f.name for f in dataclasses.fields(SimGridConfig)}
            bad = set(value) - allowed
            if bad:
                raise ValueError(f'unknown grid field(s) {sorted(bad)}')
            value = dict(value)
            for k in ('worker_failures', 'forwarder_failures'):
                if k in value:
                    value[k] = tuple(tuple(x) for x in value[k])
            kw['grid'] = SimGridConfig(**value)
        elif name == 'net':
            allowed = {f.name for f in dataclasses.fields(GridConfig)}
            bad = set(value) - allowed
            if bad:
                raise ValueError(f'unknown net field(s) {sorted(bad)}')
            value = dict(value)
            if 'worker_args' in value:
                value['worker_args'] = tuple(value['worker_args'])
            kw['net'] = GridConfig(**value)
        elif name in _SCALAR_FIELDS:
            if value is not None and not isinstance(value, (int, float,
                                                            str, bool)):
                raise ValueError(f'spec field {name!r} must be scalar, '
                                 f'got {type(value).__name__}')
            kw[name] = value
        else:
            raise ValueError(f'unknown spec field {name!r}')
    return RunSpec(**kw)


@dataclasses.dataclass
class QMCRun:
    """A RunSpec compiled against a substrate: ready-to-run stack."""

    spec: RunSpec
    run_key: str
    cfg: object                      # WavefunctionConfig
    params: object                   # WavefunctionParams
    sampler: BlockSampler
    db: ResultDatabase
    manager: QMCManager

    @property
    def backend(self):
        """The ExecutorBackend the manager was compiled against."""
        return self.manager.backend

    def run(self):
        """Blocking run to completion.

        ``method='opt-vmc'`` runs the outer optimization loop and returns
        an ``OptResult``; every other method returns the final
        ``RunningAverage``.
        """
        if self.spec.method == 'opt-vmc':
            from repro.optimize.loop import run_optimization
            return run_optimization(self)
        return self.manager.run()

    def worker_errors(self) -> list[str]:
        """Tracebacks of workers that died during the run."""
        return self.manager.worker_errors()


def build_run(spec: RunSpec, db: ResultDatabase | None = None) -> QMCRun:
    """Compile a RunSpec into a runnable manager/sampler/backend stack.

    The assembly that was hand-wired in ``qmc_run``: resolve the system,
    build the method's Propagator through the ``core.driver`` registry,
    wrap it in the generic ``BlockSampler`` (walker-mesh-sharded when
    ``shards > 1``), key the database by critical data, and stand up a
    ``QMCManager`` on the requested backend.  ``db`` injects a shared
    store (the multi-tenant service passes its own durable database so
    every concurrent run lands in one file); by default each run opens
    ``spec.db`` itself.  Either way the run key is registered with its
    declarative spec payload, which is what ``extend``/``fork`` later
    rebuild the spec from.
    """
    from repro.core.driver import make_propagator

    spec.check_platform()
    screen_eps = spec.screen_eps if spec.screen_eps >= 0 else None
    cfg, params = build_system(spec.system, n_det=spec.n_det,
                               ci_seed=spec.seed, screen_eps=screen_eps)
    if spec.precision != 'fp32':
        cfg = dataclasses.replace(cfg, precision=spec.precision)
    tau = spec.resolved_tau()
    prop = make_propagator(spec.method, cfg, tau=tau, e_trial=spec.e_trial,
                           equil_steps=spec.equil_steps)
    mesh = None
    if spec.shards > 1:
        from repro.sharding import walkers_mesh
        mesh = walkers_mesh(spec.shards)
    sampler = BlockSampler(prop, params, n_walkers=spec.n_walkers,
                           steps=spec.steps, mesh=mesh)

    # the CI expansion is critical data: coefficients AND excitation lists
    # change the estimator, so two different synthetic draws (same n_det,
    # different seed) must never share a key.  Single-det specs add no ci_*
    # entries, keeping pre-existing single-det keys (and database resume)
    # stable.
    ci_key = {}
    if cfg.ci is not None:
        ci_key = dict(
            ci_coeffs=np.asarray(cfg.ci.coeffs),
            ci_exc=np.concatenate([
                np.asarray(cfg.ci.holes_up), np.asarray(cfg.ci.parts_up),
                np.asarray(cfg.ci.holes_dn), np.asarray(cfg.ci.parts_dn)],
                axis=1))
    # screening at eps > 0 perturbs the estimator (AO values below the
    # cutoff are dropped), so the tolerance is critical data.  Off /
    # exhaustive (eps < 0) and exact (eps == 0) runs keep the unscreened
    # key: they produce bitwise-identical estimators (tests/test_screening
    # .py), and adding a key entry would orphan every pre-screening row.
    screen_key = {}
    if screen_eps is not None and screen_eps > 0:
        screen_key = dict(screen_eps=screen_eps)
    # reduced-precision storage quantizes the estimator's resting state, so
    # the policy is critical data; the fp32 default adds no entry, keeping
    # every pre-existing run key (and database resume) stable.
    precision_key = {}
    if spec.precision != 'fp32':
        precision_key = dict(precision=spec.precision)
    run_key = critical_data_key(
        system=spec.system, method=spec.method, tau=tau,
        mo=np.asarray(params.mo), coords=np.asarray(params.coords),
        **ci_key, **screen_key, **precision_key)
    if db is None:
        db = ResultDatabase(spec.db)
    db.register_run(run_key, spec=spec_to_payload(spec))
    control = RunControl(max_blocks=spec.max_blocks,
                         target_error=spec.target_error,
                         wall_clock_limit=spec.wall_clock_limit,
                         poll_interval=spec.poll_interval,
                         subblocks_per_block=spec.subblocks_per_block,
                         e_trial_feedback=(spec.method == 'dmc'))
    backend = make_backend(spec.backend, spec.n_workers, grid=spec.grid,
                           net=spec.net)
    if spec.backend == 'grid':
        # declarative run payload: grid workers rebuild this sampler on
        # their own host from these fields (see qmc_worker
        # .sampler_from_payload) — nothing jit-compiled crosses the wire
        backend.set_run_payload(dict(
            system=spec.system, method=spec.method, n_det=spec.n_det,
            ci_seed=spec.seed, tau=tau, e_trial=spec.e_trial,
            equil_steps=spec.equil_steps, n_walkers=spec.n_walkers,
            steps=spec.steps, screen_eps=spec.screen_eps,
            precision=spec.precision))
    mgr = QMCManager(sampler, run_key, control, db=db, seed=spec.seed,
                     backend=backend, n_kept=spec.n_kept)
    return QMCRun(spec=spec, run_key=run_key, cfg=cfg, params=params,
                  sampler=sampler, db=db, manager=mgr)
