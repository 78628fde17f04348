"""Unified Propagator/Driver API: one block loop for every QMC method.

The paper's §V framework is method-agnostic — "any kind of Monte Carlo
calculation" feeds the same block/forwarder pipeline.  This module is the
method-agnostic half of the *compute* side to match (QMCPACK's unified-driver
design, Kim et al. 2018):

* a ``Propagator`` supplies the physics as three small functions
  (``init`` / ``propagate`` / ``block_stats``, optional ``feedback``);
* ``EnsembleDriver`` owns the walker ensemble (a registered pytree), runs
  the jit'd ``lax.scan`` block loop once for all methods (walker buffers
  donated), and shards the walker axis over a ``walkers`` mesh axis via
  ``shard_map`` so one driver drives W walkers across all local devices;
* ``BlockStats`` is the typed block contract (weight + weighted means),
  merged host-side by ``runtime.blocks.BlockAccumulator``.

RNG layout: the driver folds the step index into the block key, and
propagators draw per-walker streams through ``Population.walker_keys`` —
keys are folded on the *global* walker index, so random streams (and hence
walker trajectories) are identical for every mesh shape; single-device vs
mesh-sharded blocks differ only by floating-point reduction order.

Sharding convention: a propagator's state is either the walker ensemble
pytree itself (every leaf walker-major, e.g. VMC's ``WalkerEnsemble``) or a
NamedTuple with an ``ens`` field holding it (e.g. ``DMCState``); ``ens``
leaves are sharded on their leading axis, every other field is replicated.
Global reductions / gathers inside ``propagate`` must go through the
``Population`` handle so they are collective-correct under ``shard_map``.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

WALKER_AXIS = 'walkers'

# method-name -> propagator factory registry (populated by vmc/dmc/sem at
# import time via register_method) — the single place a method string is
# resolved, shared by launch.spec.RunSpec and the qmc_run CLI.
_METHODS: dict = {}


def register_method(name: str, factory, default_tau: float) -> None:
    """Register a Propagator factory under a CLI/RunSpec method name.

    ``factory(cfg, tau, e_trial, equil_steps) -> Propagator``; arguments a
    method doesn't use are ignored by its factory.  ``default_tau`` is the
    method's step-size default when a spec leaves ``tau`` at 0.
    """
    _METHODS[name] = (factory, float(default_tau))


def _method_entry(method: str):
    if method not in _METHODS:
        from repro.core import dmc, sem, vmc  # noqa: F401  (registration)
        from repro.optimize import propagator  # noqa: F401  (opt-vmc)
    if method not in _METHODS:
        raise ValueError(f'unknown method {method!r} '
                         f'(registered: {sorted(_METHODS)})')
    return _METHODS[method]


def method_default_tau(method: str) -> float:
    """The registered step-size default for a method (tau=0 resolves
    here — the single source, shared with RunSpec's run-key hashing)."""
    return _method_entry(method)[1]


def make_propagator(method: str, cfg, tau: float = 0.0,
                    e_trial: float | None = None, equil_steps: int = 100):
    """Build the Propagator for a registered method name.

    The one place method strings are decided (imports the built-in method
    modules lazily so their ``register_method`` calls have run).
    """
    factory, default_tau = _method_entry(method)
    return factory(cfg, tau or default_tau, e_trial, equil_steps)


class BlockStats(NamedTuple):
    """One block's sufficient statistics (typed — no stringly dicts).

    ``weight`` is the merge weight; every other entry (including ``aux``
    values) is a weighted mean, so two BlockStats combine by weighted
    averaging — the same rule `runtime.blocks.BlockAccumulator` applies
    host-side.  ``aux`` has a static, method-specific key set.
    """
    weight: jnp.ndarray
    e_mean: jnp.ndarray
    e2_mean: jnp.ndarray
    aux: dict


class Population:
    """Global walker-axis reductions, shard-aware.

    Inside the driver's ``shard_map`` each leaf holds one shard of the
    walker axis; ``mean``/``sum`` reduce over the *global* population,
    ``gather`` materializes it (DMC reconfiguration needs the full weight
    vector), and ``walker_keys`` derives one PRNG key per global walker
    index.  Outside a mesh every method degenerates to plain jnp ops, so
    propagators are written once and run identically sharded or not.
    """

    def __init__(self, axis_name: str | None = None, n_shards: int = 1):
        self.axis_name = axis_name
        self.n_shards = n_shards

    def size(self, x) -> int:
        """Global walker count (static)."""
        return x.shape[0] * self.n_shards

    def shard_index(self):
        """This shard's position along the walker mesh axis (0 off-mesh)."""
        return (jax.lax.axis_index(self.axis_name) if self.axis_name
                else jnp.int32(0))

    def mean(self, x):
        """Global population mean of a walker-indexed array (pmean)."""
        if x.dtype == jnp.bool_:
            x = x.astype(jnp.float32)
        m = jnp.mean(x)
        return jax.lax.pmean(m, self.axis_name) if self.axis_name else m

    def mean0(self, x):
        """Global mean over the walker axis only, trailing dims kept.

        ``(W, ...) -> (...)`` — the vector/matrix moment reduction the
        wavefunction optimizer needs for ⟨O⟩, ⟨O Oᵀ⟩ etc.; ``mean``
        collapses every axis, this one pmeans only axis 0.
        """
        m = jnp.mean(x, axis=0)
        return jax.lax.pmean(m, self.axis_name) if self.axis_name else m

    def sum(self, x):
        """Global population sum of a walker-indexed array (psum)."""
        s = jnp.sum(x)
        return jax.lax.psum(s, self.axis_name) if self.axis_name else s

    def gather(self, x):
        """Full population array (W, ...) from a local shard (W/S, ...)."""
        if self.axis_name is None:
            return x
        return jax.lax.all_gather(x, self.axis_name, axis=0, tiled=True)

    def take_local(self, x, n_local: int):
        """This shard's (n_local,) slice of a global walker-indexed array."""
        if self.axis_name is None:
            return x
        start = self.shard_index() * n_local
        return jax.lax.dynamic_slice_in_dim(x, start, n_local, 0)

    def walker_keys(self, key, n_local: int):
        """(n_local,) keys folded on *global* walker indices — the random
        stream per walker is independent of the mesh shape."""
        idx = self.shard_index() * n_local + jnp.arange(n_local)
        return jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)


@runtime_checkable
class Propagator(Protocol):
    """The only method-specific plug-in: one propagation step per method.

    Implementations are pure-jax on the jit'd side (``propagate`` /
    ``block_stats``); ``init`` runs host-side once per worker.  An optional
    ``feedback(state, e_estimate)`` consumes between-block scalar feedback
    (DMC's E_T update); methods without feedback simply omit it.
    """

    def init(self, params, key, n_walkers: int, walkers=None):
        """Build the initial state; ``walkers`` are optional (n_kept, ...)
        restart positions from a checkpoint reservoir."""
        ...

    def propagate(self, params, state, key, pop: Population):
        """One Monte Carlo generation -> (state, per_step_outputs)."""
        ...

    def block_stats(self, params, state, outs, pop: Population) -> BlockStats:
        """Reduce the scanned per-step outputs into one BlockStats."""
        ...


def restart_ensemble(walkers, n_walkers: int, evaluate):
    """Tile checkpointed walker positions up to ``n_walkers`` and re-evaluate.

    ``walkers``: (n_kept, ...) positions (n_kept may be < or > n_walkers);
    ``evaluate``: positions (n_walkers, ...) -> fresh ensemble state.
    The single restart path shared by every propagator (paper §V.D:
    checkpoint/restart = reseed from the energy-stratified reservoir).
    """
    r = jnp.asarray(walkers, jnp.float32)
    reps = -(-n_walkers // r.shape[0])           # ceil division
    r = jnp.tile(r, (reps,) + (1,) * (r.ndim - 1))[:n_walkers]
    return evaluate(r)


def merge_accepted(new, old, accept):
    """Per-walker select between two walker-major pytrees (Metropolis)."""
    pick = lambda a, b: jnp.where(
        accept.reshape((-1,) + (1,) * (a.ndim - 1)), a, b)
    return jax.tree.map(pick, new, old)


class EnsembleDriver:
    """Generic block runner: owns the ensemble, scans ``propagate`` steps.

    One jit'd ``lax.scan`` block loop serves every Propagator; the state
    buffers are donated (in-place update on accelerators).  With ``mesh``
    the walker axis is sharded over its ``walkers`` axis via ``shard_map``
    and the same propagator code runs per shard, with collectives supplied
    by ``Population``.
    """

    def __init__(self, propagator, steps: int, mesh: Mesh | None = None,
                 axis_name: str = WALKER_AXIS, donate: bool = True):
        if mesh is not None and axis_name not in mesh.axis_names:
            raise ValueError(f'mesh has no {axis_name!r} axis: {mesh}')
        self.propagator = propagator
        self.steps = int(steps)
        self.mesh = mesh
        self.axis_name = axis_name
        self.donate = donate
        self._compiled: dict = {}    # state treedef -> jit'd block fn

    def __getstate__(self):
        """Pickle support (ProcessBackend ships samplers to child
        processes): the jit cache is dropped — children recompile — and a
        device mesh refuses to travel (its devices belong to this
        process; shard on the host instead)."""
        if self.mesh is not None:
            raise TypeError(
                'EnsembleDriver with a device mesh cannot be pickled to '
                'another process; use the thread backend for walker-mesh '
                'sharding')
        state = self.__dict__.copy()
        state['_compiled'] = {}
        return state

    # -- state construction / placement ---------------------------------
    def init(self, params, key, n_walkers: int, walkers=None):
        """Build the propagator state and place it on the mesh (if any)."""
        if self.mesh is not None:
            n_sh = self.mesh.shape[self.axis_name]
            if n_walkers % n_sh:
                raise ValueError(
                    f'n_walkers={n_walkers} not divisible by the '
                    f'{self.axis_name!r} mesh axis ({n_sh} shards)')
        state = self.propagator.init(params, key, n_walkers, walkers)
        if self.mesh is not None:
            shardings = jax.tree.map(
                lambda s: NamedSharding(self.mesh, s),
                self._state_specs(state),
                is_leaf=lambda x: isinstance(x, P))
            state = jax.device_put(state, shardings)
        return state

    def feedback(self, state, e_estimate):
        """Between-block scalar feedback; no-op for feedback-free methods."""
        fb = getattr(self.propagator, 'feedback', None)
        return state if fb is None else fb(state, e_estimate)

    # -- block loop ------------------------------------------------------
    def run_block(self, params, state, key):
        """Run one block of ``steps`` generations -> (state, BlockStats)."""
        tdef = jax.tree.structure(state)
        fn = self._compiled.get(tdef)
        if fn is None:
            fn = self._build(state)
            self._compiled[tdef] = fn
        return fn(params, state, key)

    def _scan(self, params, state, key, pop: Population):
        def _body(st, i):
            return self.propagator.propagate(
                params, st, jax.random.fold_in(key, i), pop)

        state, outs = jax.lax.scan(_body, state, jnp.arange(self.steps))
        # a phase, not a layer: its ops also carry the layer they run in
        with jax.named_scope('qmc.block_stats'):
            return state, self.propagator.block_stats(params, state, outs,
                                                      pop)

    def _build(self, state):
        donate = (1,) if self.donate else ()
        if self.mesh is None:
            pop = Population()
            return jax.jit(
                lambda p, st, k: self._scan(p, st, k, pop),
                donate_argnums=donate)

        n_sh = self.mesh.shape[self.axis_name]
        for leaf in jax.tree.leaves(self._ensemble_part(state)):
            if leaf.shape[0] % n_sh:
                raise ValueError(
                    f'walker axis {leaf.shape[0]} not divisible by '
                    f'{n_sh} shards')
        pop = Population(self.axis_name, n_sh)
        specs = self._state_specs(state)
        inner = jax.shard_map(
            lambda p, st, k: self._scan(p, st, k, pop),
            mesh=self.mesh,
            in_specs=(P(), specs, P()),
            out_specs=(specs, P()),     # BlockStats is fully reduced
            check_vma=False)
        return jax.jit(inner, donate_argnums=donate)

    # -- sharding convention --------------------------------------------
    @staticmethod
    def _ensemble_part(state):
        """Walker-major part of the state (see module docstring)."""
        return state.ens if hasattr(state, 'ens') else state

    def _state_specs(self, state):
        ax = self.axis_name
        wspec = lambda leaf: P(ax, *((None,) * (jnp.ndim(leaf) - 1)))
        repl = lambda tree: jax.tree.map(lambda _: P(), tree)
        if hasattr(state, 'ens') and hasattr(state, '_fields'):
            parts = {f: (jax.tree.map(wspec, getattr(state, f))
                         if f == 'ens' else repl(getattr(state, f)))
                     for f in state._fields}
            return type(state)(**parts)
        return jax.tree.map(wspec, state)
