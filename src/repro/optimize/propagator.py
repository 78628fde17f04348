"""``opt-vmc``: VMC sampling + per-step moment accumulation for SR / LM.

``OptVMCPropagator`` is the standard all-electron Metropolis propagator
(``core.vmc.VMCPropagator``) plus, each generation, the global means of

    O        (P,)    ∂ ln|Ψ| / ∂ p_i per walker, population-averaged
    O E_L    (P,)
    O Oᵀ     (P, P)
    O Oᵀ E_L (P, P)   (the extra moment the linear method needs)

reduced shard-aware through ``Population.mean0`` so the estimator is
identical under walker-axis sharding.  ``block_stats`` averages the
per-step means over the block and returns them as *array-valued* aux
entries; ``runtime.blocks.BlockAccumulator.from_stats`` flattens arrays
into indexed scalar keys (``opt_o/3``, ``opt_oo/1/2``) so the moments ride
the unchanged weighted-mean merge rule through worker merge, wire
encoding, and database storage.  Both SR and the linear method consume the
same four moments — one propagator serves both solvers.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.driver import (BlockStats as DriverStats, Population,
                               merge_accepted, register_method)
from repro.core.vmc import (VMCPropagator, evaluate_ensemble,
                            propose_diffusion)
from repro.optimize.estimators import make_o_fn, n_params, traced_vector


class OptVMCPropagator(VMCPropagator):
    """VMC sampling with SR/linear-method moment estimators (§II.A + SR)."""

    aux_fields = VMCPropagator.aux_fields + ('opt_o', 'opt_eo', 'opt_oo',
                                             'opt_oeo')

    def __init__(self, cfg, tau: float = 0.3, spread: float = 1.5):
        super().__init__(cfg, tau=tau, spread=spread)
        self.n_opt = n_params(cfg)
        self._o_fn = None            # built lazily: closures don't pickle

    @property
    def o_fn(self):
        """The per-walker ∂ln|Ψ|/∂p gradient function (lazily built)."""
        if self._o_fn is None:
            self._o_fn = make_o_fn(self.cfg)
        return self._o_fn

    def __getstate__(self):
        """Drop the jax closure so the propagator ships to worker
        processes; each process rebuilds it on first use."""
        state = self.__dict__.copy()
        state['_o_fn'] = None
        return state

    def propagate(self, params, ens, key, pop: Population):
        """One Metropolis generation + the four optimization moments."""
        new, log_ratio, u, _ = propose_diffusion(self.cfg, params, ens,
                                                 key, pop, self.tau)
        accept = jnp.log(u) < log_ratio
        merged = merge_accepted(new, ens, accept)
        vec = traced_vector(self.cfg, params)
        O = jax.vmap(self.o_fn, in_axes=(None, None, 0))(
            vec, params, merged.r)                       # (W_local, P)
        e = merged.e_loc
        OO = O[:, :, None] * O[:, None, :]               # (W_local, P, P)
        out = (pop.mean(e), pop.mean(e * e), pop.mean(accept),
               pop.mean0(O), pop.mean0(O * e[:, None]),
               pop.mean0(OO), pop.mean0(OO * e[:, None, None]))
        return merged, out

    def block_stats(self, params, ens, outs, pop: Population) -> DriverStats:
        """Reduce per-step outputs; moments land as array aux entries."""
        e, e2, acc, o, eo, oo, oeo = outs      # leading axis: (steps,)
        _, st = evaluate_ensemble(self.cfg, params, ens.r)
        w = jnp.float32(e.shape[0] * pop.size(ens.r))
        return DriverStats(
            weight=w, e_mean=jnp.mean(e), e2_mean=jnp.mean(e2),
            aux=dict(accept=jnp.mean(acc),
                     ao_fill=pop.mean(st.ao_count.astype(jnp.float32)),
                     e_kin=pop.mean(st.e_kin), e_pot=pop.mean(st.e_pot),
                     opt_o=jnp.mean(o, axis=0),
                     opt_eo=jnp.mean(eo, axis=0),
                     opt_oo=jnp.mean(oo, axis=0),
                     opt_oeo=jnp.mean(oeo, axis=0)))


register_method('opt-vmc',
                lambda cfg, tau, e_trial, equil_steps:
                OptVMCPropagator(cfg, tau=tau),
                default_tau=0.3)
