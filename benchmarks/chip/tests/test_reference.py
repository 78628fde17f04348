"""The plain reference against the program's evaluation, on the CPU.

At the ``smallest`` configuration with a few walkers the reference's
dense float32 evaluation agrees with ``psi_state_batched`` (sign,
log|psi|, drift, local energy), and the same comparison fails when the
reference's MO product is rounded to bfloat16.  The program's basis,
built by its own code from the configuration's shells, is the
reference's bit for bit.
"""
import ast
import json
from pathlib import Path

import numpy as np
import pytest

import compare
import reference
import system

CHIP = Path(__file__).resolve().parents[1]


def _config(name):
    return json.loads((CHIP / 'configs' / f'{name}.json').read_text())


def test_reference_imports_nothing_of_the_program():
    for f in ('reference.py', 'system.py', 'compare.py', 'geometry.py'):
        tree = ast.parse((CHIP / f).read_text())
        mods = [n.module or '' for n in ast.walk(tree)
                if isinstance(n, ast.ImportFrom)]
        mods += [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
                 for a in n.names]
        assert not [m for m in mods if m.split('.')[0] == 'repro'], f


def test_configuration_rebuilt_bit_for_bit():
    import program
    conf = _config('smallest')
    s = system.build(conf)
    cfg, params = program.wavefunction(conf)
    assert s.n_ao == conf['n_ao'] == cfg.basis.n_ao
    assert s.n_elec == conf['n_elec'] == cfg.n_up + cfg.n_dn
    assert np.array_equal(s.mo, np.asarray(params.mo))
    assert np.array_equal(s.coords, np.asarray(params.coords))
    assert np.array_equal(s.charges, np.asarray(params.charges))
    for k in ('ao_atom', 'ao_pow', 'prim_coeff', 'prim_exp',
              'atom_radius2'):
        assert np.array_equal(getattr(s, k), getattr(cfg.basis, k)), k


def test_configuration_holds_the_papers_counts():
    """158 electrons, 404 AOs, 79 orbitals a spin (Table IV), and a
    geometry that geometry.py reproduces."""
    import geometry
    conf = _config('smallest')
    s = system.build(conf)
    assert (s.n_elec, s.n_ao, s.n_up, s.n_dn) == (158, 404, 79, 79)
    el, bonds, hyb = geometry.graph()
    assert [a[0] for a in conf['atoms']] == el
    t = geometry.targets(el, bonds, hyb)
    x = s.coords.astype(np.float64) / geometry.ANGSTROM
    gap = max(abs(np.linalg.norm(x[i] - x[j]) - d) for (i, j), d in t.items())
    assert gap < 0.1


@pytest.fixture(scope='module')
def smallest_walkers():
    import jax
    from repro.core.sem import SEMVMCPropagator
    from repro.core.driver import EnsembleDriver
    import program
    cfg, params = program.wavefunction(_config('smallest'))
    r0 = system.initial_walkers(system.build(_config('smallest')), 6, 4)
    # a few single-electron sweeps take the start off its
    # worst-conditioned configurations
    drv = EnsembleDriver(SEMVMCPropagator(cfg, 0.3), 3)
    st = drv.init(params, jax.random.PRNGKey(4), 6, r0)
    st, _ = drv.run_block(params, st, jax.random.PRNGKey(5))
    from repro.core.wavefunction import psi_state_batched
    r = st.ens.r
    ps = jax.jit(lambda R: psi_state_batched(cfg, params, R))(r)
    prog = dict(sign=np.asarray(ps.sign), log_psi=np.asarray(ps.log_psi),
                drift=np.asarray(ps.drift), e_loc=np.asarray(ps.e_loc))
    return np.asarray(r), prog


def _numbers(r, prog, mo_precision):
    sysd = system.build(_config('smallest'))
    ref = {k: np.asarray(v) for k, v in
           reference.Reference(sysd).evaluate(r).items()}
    if mo_precision == 'highest':
        return compare.final_state(prog, ref)
    orig = reference.mm

    def mm(spec, a, b, precision='highest'):
        if spec == 'oa,aef->oef':          # the AO -> MO product only
            precision = mo_precision
        return orig(spec, a, b, precision)

    reference.mm = mm
    try:
        low = {k: np.asarray(v) for k, v in
               reference.Reference(sysd).evaluate(r).items()}
    finally:
        reference.mm = orig
    return compare.final_state({k: low[k] for k in prog}, ref)


def test_reference_agrees_with_program(smallest_walkers):
    r, prog = smallest_walkers
    got = _numbers(r, prog, 'highest')
    assert got['sign_flips'] == 0
    assert got['eloc_med'] < 1e-5
    assert got['drift_med'] < 1e-4
    assert got['logpsi_med'] < 1e-2


def test_bf16_mo_product_fails_the_comparison(smallest_walkers):
    r, prog = smallest_walkers
    sound = _numbers(r, prog, 'highest')
    low = _numbers(r, prog, 'bf16')
    assert low['eloc_med'] > 10 * max(sound['eloc_med'], 1e-7)
    assert low['drift_med'] > 10 * max(sound['drift_med'], 1e-7)
