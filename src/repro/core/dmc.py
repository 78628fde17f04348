"""Fixed-node DMC with constant-walker-count stochastic reconfiguration.

Per generation (paper §II):
  1. drift-diffusion move (eq. 1) with Metropolis accept/reject on |Psi|^2 G
     (Umrigar '93) — removes most time-step error;
  2. fixed-node constraint: moves that flip sign(Psi_T) are rejected
     (nodes act as infinite barriers);
  3. branching weight w = exp(-tau_eff/2 [(E_L(R')-E_T) + (E_L(R)-E_T)])
     (eq. 3), each E_L - E_T capped at +/- sqrt(n_e / tau), the
     size-consistent cutoff of Zen et al., PRB 93, 241118 (2016) at
     alpha = 1 (their 0.2 took 0.11 Ha off the projection of the cusp-less
     H2 trial function).  Without it, walkers near a nucleus, where a
     Gaussian basis has no cusp, take the whole population: at the paper's
     158-electron system the first DMC block read -12492 Ha and the
     acceptance fell to 0;
  4. reconfiguration (reconfig.py) keeps the population size constant;
     the population-mean weight enters the trailing global weight
     Pi_t = prod_{s in window} w_bar_s, which weights the energy estimator
     (removes the finite-population bias, ref. [17]).

The method is ``DMCPropagator`` (init / propagate / block_stats /
feedback); the jit'd ``lax.scan`` block loop and walker-axis sharding are
the generic ``driver.EnsembleDriver``.  Under a sharded driver the
reconfiguration is *global*: weights are all-gathered so the resampling is
identical to the single-device population (walker exchange is the one
collective DMC fundamentally needs) — DESIGN.md §5.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .driver import (BlockStats as DriverStats, EnsembleDriver, Population,
                     merge_accepted, register_method, restart_ensemble)
from .reconfig import reconfigure, global_weight_update
from .vmc import (VMCPropagator, WalkerEnsemble, evaluate_ensemble,
                  init_walkers, propose_diffusion)
from .wavefunction import WavefunctionConfig


class DMCState(NamedTuple):
    """Driver state: walker ensemble + replicated E_T / weight history."""

    ens: WalkerEnsemble
    log_w_hist: jnp.ndarray    # (window,) trailing log population weights
    e_trial: jnp.ndarray       # () E_T reference energy


class DMCPropagator:
    """Fixed-node DMC as a driver plug-in.

    ``feedback`` is the single E_T damping knob: every between-block E_T
    update (runtime feedback included) routes through ``update_e_trial``.
    Cold starts are VMC-equilibrated through a nested (unsharded) driver;
    restarts re-evaluate reservoir walkers via ``restart_ensemble``.
    """

    aux_fields = ('accept', 'pop_weight', 'sign_flips', 'ao_fill')

    def __init__(self, cfg: WavefunctionConfig, e_trial: float,
                 tau: float = 0.02, window: int = 20, damping: float = 0.5,
                 equil_steps: int = 0, vmc_tau: float = 0.3):
        self.cfg, self.tau = cfg, float(tau)
        self.e_trial0 = float(e_trial)
        self.window, self.damping = int(window), float(damping)
        self.equil_steps, self.vmc_tau = int(equil_steps), float(vmc_tau)

    def init(self, params, key, n_walkers: int, walkers=None):
        """Cold start (VMC-equilibrated) or reservoir restart."""
        if walkers is not None:
            ens = restart_ensemble(
                walkers, n_walkers,
                lambda r: evaluate_ensemble(self.cfg, params, r)[0])
        else:
            ens = init_walkers(self.cfg, params, key, n_walkers)
            if self.equil_steps:
                vmc = EnsembleDriver(VMCPropagator(self.cfg, self.vmc_tau),
                                     self.equil_steps, donate=False)
                ens, _ = vmc.run_block(params, ens,
                                       jax.random.fold_in(key, 1))
        return init_dmc(ens, e_trial=self.e_trial0, window=self.window)

    def propagate(self, params, state: DMCState, key, pop: Population):
        """One DMC generation: move, branch weights, reconfigure."""
        ens = state.ens
        kp, kr = jax.random.split(key)
        new, log_ratio, u, st = propose_diffusion(self.cfg, params, ens,
                                                  kp, pop, self.tau)
        crossed = new.sign * ens.sign < 0      # fixed-node: reject crossings
        accept = (jnp.log(u) < log_ratio) & ~crossed
        moved = merge_accepted(new, ens, accept)

        # effective time step compensates rejected moves (Umrigar '93);
        # pop.mean of 0/1 is reduction-order exact for power-of-two shards
        acc_frac = pop.mean(accept.astype(jnp.float32))
        tau_eff = self.tau * jnp.maximum(acc_frac, 1e-3)
        e_cut = (self.cfg.n_elec / self.tau) ** 0.5

        def _dev(e):
            return jnp.clip(e - state.e_trial, -e_cut, e_cut)

        w = jnp.exp(-0.5 * tau_eff * (_dev(moved.e_loc) + _dev(ens.e_loc)))
        w = jnp.clip(w, 0.0, 4.0)              # guard rare E_L spikes

        # global reconfiguration: identical resampling for any mesh shape
        with jax.named_scope('qmc.reconfig'):
            idx = reconfigure(kr, pop.gather(w))
            moved_all = jax.tree.map(pop.gather, moved)
            idx_local = pop.take_local(idx, ens.r.shape[0])
            ens_next = jax.tree.map(lambda a: a[idx_local], moved_all)

        mean_w = pop.mean(w)
        log_hist, g_weight = global_weight_update(state.log_w_hist, mean_w)
        # active AOs per electron of the proposals, which every generation
        # evaluates (VMC and SEM read the block's last configuration)
        fill = pop.mean(st.ao_count.astype(jnp.float32))
        out = (pop.mean(moved.e_loc), g_weight, acc_frac,
               pop.mean(crossed.astype(jnp.float32)), mean_w, fill)
        return DMCState(ens=ens_next, log_w_hist=log_hist,
                        e_trial=state.e_trial), out

    def block_stats(self, params, state: DMCState, outs,
                    pop: Population) -> DriverStats:
        """Global-weight-weighted mixed estimator over the block."""
        e, gw, acc, cross, w, fill = outs      # (steps,) replicated scalars
        wsum = jnp.sum(gw)
        return DriverStats(
            weight=wsum,
            e_mean=jnp.sum(gw * e) / wsum,
            e2_mean=jnp.sum(gw * e ** 2) / wsum,
            aux=dict(accept=jnp.mean(acc), pop_weight=jnp.mean(w),
                     sign_flips=jnp.mean(cross), ao_fill=jnp.mean(fill)))

    def feedback(self, state: DMCState, e_estimate) -> DMCState:
        """Between-block E_T update (routed through ``update_e_trial``)."""
        return update_e_trial(state, e_estimate, damping=self.damping)


def init_dmc(ens: WalkerEnsemble, e_trial: float,
             window: int = 20) -> DMCState:
    """DMC state around an equilibrated ensemble (unit weight history)."""
    return DMCState(ens=ens,
                    log_w_hist=jnp.zeros((window,), jnp.float32),
                    e_trial=jnp.float32(e_trial))


def update_e_trial(state: DMCState, e_estimate, damping: float = 0.5):
    """Between-block E_T feedback (population control is already exact;
    this just keeps weights O(1)).  The one damping knob — every E_T
    update path (including runtime feedback) goes through here."""
    et = (1 - damping) * state.e_trial + damping * e_estimate
    return state._replace(e_trial=jnp.float32(et))


def dmc_step(cfg, params, state: DMCState, key, tau):
    """One DMC generation (single-device, unsharded)."""
    prop = DMCPropagator(cfg, e_trial=0.0, tau=tau)
    return prop.propagate(params, state, key, Population())


def _from_spec(cfg, tau, e_trial, equil_steps):
    """RunSpec factory: default E_T is the crude -0.5 Ha/electron guess."""
    e0 = e_trial if e_trial is not None else -0.5 * cfg.n_elec
    return DMCPropagator(cfg, e_trial=e0, tau=tau,
                         equil_steps=equil_steps)


register_method('dmc', _from_spec, default_tau=0.02)
