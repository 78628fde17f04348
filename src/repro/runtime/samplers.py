"""The runtime adapter from Propagators to the worker Sampler protocol.

``BlockSampler`` wraps any ``core.driver.Propagator`` behind one generic
adapter: the runtime has zero method-specific branches — VMC vs DMC is
decided once, where the propagator is constructed (launcher / user code).

Each worker owns a *private* walker population (paper §II.B: no
communication between populations) — or, with a ``mesh``, one population
device-sharded over the local ``walkers`` mesh axis.  A sub-block is one
jit'd ``lax.scan`` over ``steps`` generations; the runtime composes
sub-blocks into droppable/truncatable blocks via ``BlockAccumulator``.

RNG: the state threaded through the worker is ``(worker_key, prop_state)``;
sub-block keys are ``fold_in(worker_key, step)`` — no seed arithmetic, so
worker streams can never alias however many sub-blocks a run takes.

A ``BlockSampler`` is picklable until first use (the driver drops its jit
cache on pickling), which is how the ProcessBackend ships one to each
worker process.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax

from repro.core.driver import EnsembleDriver
from repro.runtime.blocks import BlockAccumulator
from repro.tracing import span


class BlockSampler:
    """Generic Sampler: (Propagator, params) -> worker-facing block runner."""

    def __init__(self, propagator, params, n_walkers: int = 32,
                 steps: int = 50, mesh=None):
        self.propagator = propagator
        self.params = params
        self.n_walkers = int(n_walkers)
        self.params_version = 0
        self.driver = EnsembleDriver(propagator, steps, mesh=mesh)

    def init_state(self, worker_id: int, seed: int, walkers=None):
        wkey = jax.random.fold_in(jax.random.PRNGKey(seed), worker_id)
        k_init, _ = jax.random.split(wkey)     # sub-blocks use the other half
        state = self.driver.init(self.params, k_init, self.n_walkers,
                                 walkers)
        return (wkey, state)

    def set_e_trial(self, state, e_trial: float):
        """Between-block scalar feedback (DMC E_T; no-op for VMC) — routed
        through the propagator's one ``feedback``/``update_e_trial`` knob."""
        wkey, st = state
        return (wkey, self.driver.feedback(st, e_trial))

    def apply_params(self, version: int, vec) -> None:
        """Install a broadcast wavefunction-parameter vector (opt-vmc).

        Ordering contract with ``run_subblock`` (which reads the version
        *before* the params): params are written first, version last, so a
        torn concurrent read can only pair new params with the *old*
        version stamp — that block is rejected by the solver's version
        filter (conservative, unbiased), never silently accepted.
        """
        from repro.optimize.estimators import apply_vector
        new = apply_vector(self.propagator.cfg, self.params,
                           np.asarray(vec, np.float64))
        self.params = new
        self.params_version = int(version)

    def run_subblock(self, state, step: int):
        """One jit'd sub-block -> (state, BlockAccumulator, walkers,
        energies), host copies last.  Host spans: ``qmc.subblock`` around
        the call, ``qmc.dispatch`` (jit lookup and enqueue, or compile) and
        ``qmc.fetch`` (the host copies, opened once the device is done, so
        a profile started during the block still names the copies)."""
        with span('qmc.subblock'):
            wkey, st = state
            pv = self.params_version       # read version BEFORE params (see
            params = self.params           # apply_params ordering contract)
            with span('qmc.dispatch'):
                _, k_blocks = jax.random.split(wkey)
                key = jax.random.fold_in(k_blocks, step)
                st, stats = self.driver.run_block(params, st, key)
            ens = st.ens if hasattr(st, 'ens') else st
            jax.block_until_ready((stats, ens.r, ens.e_loc))
            with span('qmc.fetch'):
                acc = BlockAccumulator.from_stats(stats)
                r, e_loc = np.asarray(ens.r), np.asarray(ens.e_loc)
            if getattr(self.propagator, 'n_opt', 0):
                # host-side parameter-version stamp: rides the weighted-mean
                # merge, so sub-blocks merged across a version change average
                # to a fractional stamp and are rejected downstream
                acc = dataclasses.replace(
                    acc, aux={**acc.aux, 'opt_pv': float(pv)})
            return (wkey, st), acc, r, e_loc
