"""A configuration handed to the program under test as data.

The program's system catalog (``repro.systems.build_system``) resolves
names to systems it generates itself.  A configuration here holds its
molecule as data, so the program gets it as a model gets its weights:
the atoms, the shells and the MO coefficients of ``system.py``, through
the program's own basis builder (``core.basis.build_basis``) and
wavefunction assembly (``systems.bench.build_bench_wavefunction``), the
sparse method its paper systems use.  ``install`` makes ``build_run``
resolve the run's system to it; everything after that is the program's.
"""
from __future__ import annotations

import numpy as np

import system


def wavefunction(conf: dict):
    """(WavefunctionConfig, WavefunctionParams) of a configuration."""
    from repro.core.basis import Shell, build_basis
    from repro.systems.bench import BenchSystem, build_bench_wavefunction
    from repro.systems.molecule import Molecule
    sysd = system.build(conf)
    shells = [Shell(a, l, tuple(ex), tuple(co))
              for a, l, ex, co in system.shells(conf)]
    basis = build_basis(shells, sysd.coords.shape[0],
                        eps=float(conf['eps_ao']))
    mol = Molecule(conf['name'], sysd.coords.astype(np.float64),
                   sysd.charges.astype(np.float64), sysd.n_up, sysd.n_dn)
    bench = BenchSystem(name=conf['name'], mol=mol, basis=basis,
                        mos=sysd.mo,
                        a_density=float(np.mean(sysd.mo != 0)))
    return build_bench_wavefunction(bench, method=conf['mo_product'],
                                    k_max=int(conf['k_max']))


def install(conf: dict) -> None:
    """Make ``build_run`` build the configuration's wavefunction."""
    import repro.launch.spec as spec
    wf = wavefunction(conf)

    def build_system(name, n_det=1, ci_seed=0, screen_eps=None):
        if n_det != 1 or screen_eps is not None:
            raise ValueError('a benchmark configuration is one determinant '
                             'without screening')
        return wf

    spec.build_system = build_system
