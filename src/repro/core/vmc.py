"""Variational Monte Carlo: all-electron drift-diffusion Metropolis sampling.

One block = ``steps`` Monte Carlo generations over a walker population
(paper §V: a block is the unit of work whose average is an i.i.d. Gaussian
sample; blocks are droppable/truncatable without bias).

The method lives in ``VMCPropagator`` (init / propagate / block_stats);
the block loop, jit, and walker-axis sharding are the generic
``driver.EnsembleDriver`` (DESIGN.md §5).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .driver import (BlockStats as DriverStats, Population, merge_accepted,
                     register_method, restart_ensemble)
from .wavefunction import (WavefunctionConfig, WavefunctionParams, psi_state,
                           psi_state_batched)


class WalkerEnsemble(NamedTuple):
    """Walker-major all-electron ensemble (driver-sharded leading axis)."""

    r: jnp.ndarray          # (W, n_e, 3)
    log_psi: jnp.ndarray    # (W,)
    sign: jnp.ndarray       # (W,)
    drift: jnp.ndarray      # (W, n_e, 3)
    e_loc: jnp.ndarray      # (W,)


def evaluate_ensemble(cfg, params, r):
    """Evaluate a walker batch r: (W, n_e, 3) -> (WalkerEnsemble, PsiState).

    Default path is the ensemble-flattened fused AO->MO->Slater pass
    (``psi_state_batched``); ``cfg.ensemble_eval=False`` falls back to the
    per-walker vmap.  Shared by every propagator (VMC, DMC, ...).
    """
    if cfg.ensemble_eval:
        st = psi_state_batched(cfg, params, r)
    else:
        st = jax.vmap(partial(psi_state, cfg, params))(r)
    return WalkerEnsemble(r=r, log_psi=st.log_psi, sign=st.sign,
                          drift=st.drift, e_loc=st.e_loc), st


def sample_positions(params: WavefunctionParams, key: jax.Array,
                     n_walkers: int, n_e: int,
                     spread: float = 1.5) -> jnp.ndarray:
    """Electrons scattered around (charge-weighted) random nuclei.

    The cold-start position distribution shared by every propagator
    (VMC, DMC, single-electron-move).  Returns (n_walkers, n_e, 3).
    """
    ka, kb = jax.random.split(key)
    n_at = params.coords.shape[0]
    probs = params.charges / jnp.sum(params.charges)
    at = jax.random.choice(ka, n_at, (n_walkers, n_e), p=probs)
    centers = params.coords[at]
    return centers + spread * jax.random.normal(kb, (n_walkers, n_e, 3),
                                                dtype=params.coords.dtype)


def init_walkers(cfg: WavefunctionConfig, params: WavefunctionParams,
                 key: jax.Array, n_walkers: int,
                 spread: float = 1.5) -> WalkerEnsemble:
    """Cold-start ensemble: sampled positions, fully evaluated."""
    r = sample_positions(params, key, n_walkers, cfg.n_elec, spread)
    ens, _ = evaluate_ensemble(cfg, params, r)
    return ens


def _log_green(r_to, r_from, drift_from, tau):
    """log G(r_to <- r_from) for the drift-diffusion proposal."""
    d = r_to - r_from - tau * drift_from
    return -jnp.sum(d * d, axis=(-1, -2)) / (2.0 * tau)


def propose_diffusion(cfg, params, ens: WalkerEnsemble, key, pop: Population,
                      tau):
    """Drift-diffusion proposal shared by VMC and DMC (paper eq. 1).

    Per-walker RNG streams (``pop.walker_keys`` folds the *global* walker
    index) make proposals identical under any walker-axis sharding.
    Returns (proposed ensemble, Metropolis log-ratio, per-walker uniforms,
    the proposal's ``PsiState``).
    """
    def _draw(k):
        k_eta, k_u = jax.random.split(k)
        eta = jax.random.normal(k_eta, ens.r.shape[1:], ens.r.dtype)
        return eta, jax.random.uniform(k_u, ())

    eta, u = jax.vmap(_draw)(pop.walker_keys(key, ens.r.shape[0]))
    r_new = ens.r + tau * ens.drift + jnp.sqrt(tau) * eta
    new, st = evaluate_ensemble(cfg, params, r_new)
    log_ratio = (2.0 * (new.log_psi - ens.log_psi)
                 + _log_green(ens.r, r_new, new.drift, tau)
                 - _log_green(r_new, ens.r, ens.drift, tau))
    return new, log_ratio, u, st


class VMCPropagator:
    """Metropolis sampling of |Psi_T|^2 as a driver plug-in (§II.A)."""

    aux_fields = ('accept', 'ao_fill', 'e_kin', 'e_pot')

    def __init__(self, cfg: WavefunctionConfig, tau: float = 0.3,
                 spread: float = 1.5):
        self.cfg, self.tau, self.spread = cfg, float(tau), float(spread)

    def init(self, params, key, n_walkers: int, walkers=None):
        """Cold start (sampled positions) or reservoir restart."""
        if walkers is not None:
            return restart_ensemble(
                walkers, n_walkers,
                lambda r: evaluate_ensemble(self.cfg, params, r)[0])
        return init_walkers(self.cfg, params, key, n_walkers, self.spread)

    def propagate(self, params, ens: WalkerEnsemble, key, pop: Population):
        """One all-electron drift-diffusion Metropolis generation."""
        new, log_ratio, u, _ = propose_diffusion(self.cfg, params, ens,
                                                 key, pop, self.tau)
        accept = jnp.log(u) < log_ratio
        merged = merge_accepted(new, ens, accept)
        out = (pop.mean(merged.e_loc), pop.mean(merged.e_loc ** 2),
               pop.mean(accept))
        return merged, out

    def block_stats(self, params, ens: WalkerEnsemble, outs,
                    pop: Population) -> DriverStats:
        """Reduce the scanned per-step outputs into one BlockStats."""
        e, e2, acc = outs                       # (steps,) global per-step means
        # sparsity/energy split from the final configuration (cheap,
        # representative)
        _, st = evaluate_ensemble(self.cfg, params, ens.r)
        w = jnp.float32(e.shape[0] * pop.size(ens.r))
        return DriverStats(
            weight=w, e_mean=jnp.mean(e), e2_mean=jnp.mean(e2),
            aux=dict(accept=jnp.mean(acc),
                     ao_fill=pop.mean(st.ao_count.astype(jnp.float32)),
                     e_kin=pop.mean(st.e_kin), e_pot=pop.mean(st.e_pot)))


def vmc_step(cfg, params, ens: WalkerEnsemble, key, tau):
    """One Metropolis generation (single-device, unsharded)."""
    pop = Population()
    new, log_ratio, u, _ = propose_diffusion(cfg, params, ens, key, pop,
                                             tau)
    accept = jnp.log(u) < log_ratio
    return merge_accepted(new, ens, accept), accept


register_method('vmc',
                lambda cfg, tau, e_trial, equil_steps:
                VMCPropagator(cfg, tau=tau),
                default_tau=0.3)
