"""Mixed-precision storage policy (DESIGN.md §13): drift bounds + inertness.

The contract under test: with ``cfg.precision`` in ('bf16', 'fp16') the
maintained SEM inverses and CI P-tables REST in the reduced dtype while
every ratio, Sherman–Morrison update, Newton–Schulz correction and energy
contraction accumulates in fp32 — so after k < cfg.sem_refresh sweeps the
running state still tracks a fresh full-precision recompute within the
per-dtype contract ``slater.drift_tolerance(precision)``, for BOTH spin
blocks and across the spin-boundary electron j = n_up.  The default
``'fp32'`` policy must be structurally bitwise-inert (the cast helpers
return the stored arrays THEMSELVES), and reduced precision is critical
data: it enters the CRC-32 run key while fp32 keeps pre-existing keys
stable.

``test_sweep_jaxpr_has_no_fp64`` is the dtype-drift regression for
satellite (3): under ``jax_enable_x64`` un-pinned numpy constants (basis
tables, Metropolis uniform draws) silently promote the whole sweep to
fp64; the sweep jaxprs — dense, screened and fused — must stay f64-free.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sem, slater
from repro.core.driver import EnsembleDriver, Population
from repro.core.sem import SEMVMCPropagator, evaluate_sem
from repro.core.vmc import sample_positions
from repro.systems import build_system
from repro.systems.molecule import build_wavefunction, h2, water

jax.config.update('jax_enable_x64', False)

LOW_PRECISIONS = ('bf16', 'fp16')


@pytest.fixture(scope='module')
def water_wf():
    return build_wavefunction(*water())


def _f64(x):
    """Any storage dtype (incl. bfloat16) -> numpy float64 for comparison."""
    return np.asarray(jnp.asarray(x, jnp.float32), np.float64)


def _assert_drift_within_contract(ens, fresh, cfg):
    """Running minv/logdet vs fresh fp32 recompute within the per-dtype
    tolerance (minv relative to the block's own magnitude, logdet
    absolute, sign exact) — the §6 contract scaled per storage dtype.

    The stored state is read back through ``sem._to_compute`` (the same
    boundary the sweep uses), which also undoes the exact fp16 exponent
    shift."""
    precision = cfg.precision
    rel, abs_ld = slater.drift_tolerance(precision)
    for f in ('minv_up', 'minv_dn'):
        a = _f64(sem._to_compute(getattr(ens, f), cfg))
        b = _f64(getattr(fresh, f))
        if a.size == 0:
            continue
        scale = max(np.max(np.abs(b)), 1.0)
        assert np.max(np.abs(a - b)) / scale <= rel, (f, precision)
    np.testing.assert_allclose(_f64(ens.logdet), _f64(fresh.logdet),
                               atol=abs_ld)
    np.testing.assert_array_equal(np.asarray(ens.sign),
                                  np.asarray(fresh.sign))


# ---------------------------------------------------------------------------
# policy tables + fp32 inertness
# ---------------------------------------------------------------------------
def test_policy_tables_consistent():
    """slater's precision tables cover exactly the public PRECISIONS, and
    launch.spec's jax-free mirror stays in sync."""
    from repro.launch import spec as launch_spec
    assert slater.PRECISIONS == ('fp32', 'bf16', 'fp16')
    assert launch_spec.PRECISIONS == slater.PRECISIONS
    assert slater.storage_dtype('fp32') == jnp.float32
    assert slater.storage_dtype('bf16') == jnp.bfloat16
    assert slater.storage_dtype('fp16') == jnp.float16
    for p in slater.PRECISIONS:
        nbytes = slater.precision_bytes(p)
        assert nbytes == jnp.dtype(slater.storage_dtype(p)).itemsize
        rel, abs_ld = slater.drift_tolerance(p)
        assert 0 < rel < 1 and 0 < abs_ld


def test_fp32_policy_is_structurally_inert(water_wf):
    """At the default precision the cast helpers return the stored arrays
    THEMSELVES (object identity — no casts, no copies, bitwise-inert by
    construction), and the resting state is plain float32."""
    cfg, params = water_wf
    assert cfg.precision == 'fp32'
    x = jnp.ones((2, 3, 3), jnp.float32)
    assert sem._to_compute(x, cfg) is x
    assert sem._to_storage(x, cfg) is x
    r = sample_positions(params, jax.random.PRNGKey(0), 4, cfg.n_elec)
    ens = evaluate_sem(cfg, params, r)
    assert ens.minv_up.dtype == jnp.float32
    assert ens.minv_dn.dtype == jnp.float32


def test_fp32_trajectory_identical_to_default(water_wf):
    """A config that spells out precision='fp32' walks bitwise like the
    untouched default config — the policy adds nothing at fp32."""
    cfg, params = water_wf
    outs = []
    for c in (cfg, dataclasses.replace(cfg, precision='fp32')):
        prop = SEMVMCPropagator(c, step_size=0.4)
        drv = EnsembleDriver(prop, steps=2, donate=False)
        st = drv.init(params, jax.random.PRNGKey(0), 4)
        st, _ = drv.run_block(params, st, jax.random.PRNGKey(1))
        outs.append(st.ens)
    for a, b in zip(outs[0], outs[1]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize('precision', LOW_PRECISIONS)
def test_low_precision_state_is_quantized(water_wf, precision):
    """bf16/fp16 resting state: the (W, n, n) inverses carry the storage
    dtype; positions, sign and logdet stay float32 (never quantized)."""
    cfg, params = water_wf
    cfg = dataclasses.replace(cfg, precision=precision)
    r = sample_positions(params, jax.random.PRNGKey(0), 4, cfg.n_elec)
    ens = evaluate_sem(cfg, params, r)
    want = slater.storage_dtype(precision)
    assert ens.minv_up.dtype == want and ens.minv_dn.dtype == want
    assert ens.r.dtype == jnp.float32
    assert ens.sign.dtype == jnp.float32
    assert ens.logdet.dtype == jnp.float32


def test_low_precision_multidet_tables_quantized():
    """With cfg.ci the shared P-tables rest in the storage dtype too."""
    cfg, params = build_system('water', n_det=4, ci_seed=3)
    cfg = dataclasses.replace(cfg, precision='bf16')
    r = sample_positions(params, jax.random.PRNGKey(0), 3, cfg.n_elec)
    ens = evaluate_sem(cfg, params, r)
    assert ens.p_up.dtype == jnp.bfloat16
    assert ens.p_dn.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# drift bounds: k < sem_refresh sweeps vs fresh recompute, per dtype
# ---------------------------------------------------------------------------
@pytest.mark.parametrize('method', ['dense', 'fused'])
@pytest.mark.parametrize('precision', ('fp32',) + LOW_PRECISIONS)
def test_sweeps_track_fresh_recompute_mixed_precision(water_wf, precision,
                                                      method):
    """k=3 < sem_refresh sweeps of quantize -> upcast -> sweep -> requantize
    cycles: both spin blocks' minv and the logdet stay within the per-dtype
    drift contract of a fresh fp32 recompute — through the per-move path
    AND the fused sweep."""
    cfg, params = water_wf
    cfg = dataclasses.replace(cfg, precision=precision, method=method)
    prop = SEMVMCPropagator(cfg, step_size=0.4)
    drv = EnsembleDriver(prop, steps=3, donate=False)
    st = drv.init(params, jax.random.PRNGKey(0), 8)
    st, stats = drv.run_block(params, st, jax.random.PRNGKey(1))
    assert 0.0 < float(stats.aux['accept']) < 1.0
    assert np.isfinite(float(stats.e_mean))
    fresh = evaluate_sem(dataclasses.replace(cfg, precision='fp32'),
                         params, st.ens.r)
    _assert_drift_within_contract(st.ens, fresh, cfg)


@pytest.mark.parametrize('precision', LOW_PRECISIONS)
def test_spin_boundary_electron_mixed_precision(water_wf, precision):
    """One trial of exactly electron j = n_up from quantized storage: the
    dn-block inverse, upcast and swept in fp32, tracks a fresh recompute
    within the dtype's contract (the storage boundary doesn't blur the
    spin-block boundary)."""
    cfg, params = water_wf
    cfg = dataclasses.replace(cfg, precision=precision)
    r = sample_positions(params, jax.random.PRNGKey(3), 4, cfg.n_elec)
    ens = evaluate_sem(cfg, params, r)
    assert ens.minv_dn.dtype == slater.storage_dtype(precision)
    wkeys = Population().walker_keys(jax.random.PRNGKey(5), 4)
    _, A_dn = sem._mo_blocks(cfg, params)
    carry = (ens.r, sem._to_compute(ens.minv_dn, cfg))
    (r2, minv_dn), _ = sem._sweep_spin_block(
        cfg, params, A_dn, cfg.n_up, 1, wkeys, 0.5, carry)
    assert np.any(np.asarray(r2) != np.asarray(r)), 'no move accepted'
    moved = np.any(np.asarray(r2) != np.asarray(r), axis=-1)
    assert not np.any(np.delete(moved, cfg.n_up, axis=1))
    fresh = evaluate_sem(dataclasses.replace(cfg, precision='fp32'),
                         params, r2)
    rel, _ = slater.drift_tolerance(precision)
    scale = max(np.max(np.abs(_f64(fresh.minv_dn))), 1.0)
    assert np.max(np.abs(_f64(minv_dn) - _f64(fresh.minv_dn))) / scale <= rel


# ---------------------------------------------------------------------------
# end-to-end: reduced-precision energies statistically match fp32
# ---------------------------------------------------------------------------
def _run_e2e(system, precision, blocks=4, walkers=8, steps=10):
    from repro.launch.spec import RunSpec, build_run
    spec = RunSpec(system=system, method='fused-vmc', precision=precision,
                   max_blocks=blocks, n_walkers=walkers, steps=steps,
                   n_workers=1)
    return build_run(spec).run()


@pytest.mark.parametrize('precision', LOW_PRECISIONS)
def test_h2_energy_within_3sigma_of_fp32(precision):
    """fused-vmc H2: bf16/fp16 block energies agree with the fp32 run
    within 3 sigma of the combined block-mean errors (ISSUE acceptance)."""
    ref = _run_e2e('h2', 'fp32')
    low = _run_e2e('h2', precision)
    assert np.isfinite(low.energy) and low.error > 0
    sigma = np.hypot(ref.error, low.error)
    assert abs(low.energy - ref.energy) <= 3.0 * sigma, \
        (precision, low.energy, ref.energy, sigma)


@pytest.mark.slow
def test_water_energy_within_3sigma_of_fp32():
    """Same 3-sigma agreement on water (10 electrons, both spin blocks)."""
    ref = _run_e2e('water', 'fp32', blocks=3, walkers=8, steps=8)
    low = _run_e2e('water', 'bf16', blocks=3, walkers=8, steps=8)
    sigma = np.hypot(ref.error, low.error)
    assert abs(low.energy - ref.energy) <= 3.0 * sigma, \
        (low.energy, ref.energy, sigma)


# ---------------------------------------------------------------------------
# run key: reduced precision is critical data, fp32 keeps keys stable
# ---------------------------------------------------------------------------
def test_precision_enters_run_key(tmp_path):
    """bf16/fp16/fp32 specs get three distinct run keys; the fp32 key adds
    no payload entry beyond what an identical pre-policy spec carried."""
    from repro.launch.spec import RunSpec, build_run
    keys = {}
    for p in ('fp32',) + LOW_PRECISIONS:
        spec = RunSpec(system='h2', method='fused-vmc', precision=p,
                       max_blocks=1, n_walkers=4, steps=2, n_workers=1,
                       db=str(tmp_path / f'{p}.sqlite'))
        keys[p] = build_run(spec).run_key
    assert len(set(keys.values())) == 3


def test_run_spec_rejects_unknown_precision():
    from repro.launch.spec import RunSpec
    with pytest.raises(ValueError, match='precision'):
        RunSpec(system='h2', precision='fp8')


# ---------------------------------------------------------------------------
# satellite (3): no silent fp64 promotion anywhere in the sweep
# ---------------------------------------------------------------------------
def _sweep_jaxpr(cfg, params, path):
    """Trace one sweep under jax_enable_x64 on fp32 operands.

    The state/keys are built OUTSIDE the x64 context (f32, like a real
    run); the trace then exposes any un-pinned internal constant — basis
    tables (``aos._basis_consts``), uniform draws — that would promote."""
    W = 2
    r = sample_positions(params, jax.random.PRNGKey(0), W, cfg.n_elec)
    ens = evaluate_sem(cfg, params, r)
    wkeys = Population().walker_keys(jax.random.PRNGKey(1), W)
    with jax.enable_x64(True):
        if path == 'fused':
            jx = jax.make_jaxpr(
                lambda e, k: sem._fused_sweeps(
                    cfg, params, e, e.minv_up, e.minv_dn, e.p_up, e.p_dn,
                    k, 0.4))(ens, wkeys)
        else:
            A_up, _ = sem._mo_blocks(cfg, params)
            carry = (ens.r, ens.minv_up)
            jx = jax.make_jaxpr(
                lambda c, k: sem._sweep_spin_block(
                    cfg, params, A_up, 0, cfg.n_up, k, 0.4, c))(carry, wkeys)
    return str(jx)


@pytest.mark.parametrize('screened', [False, True],
                         ids=['dense', 'screened'])
@pytest.mark.parametrize('path', ['permove', 'fused'])
def test_sweep_jaxpr_has_no_fp64(path, screened):
    """Regression: with jax_enable_x64 active the sweep jaxpr (dense,
    screened, and fused variants) materializes no f64 ARRAY — the dtype
    pins in ``aos._basis_consts``, the Metropolis draws and the Jastrow
    spin factors hold.  Weak-typed ``f64[]`` scalars (python-float
    literals like the 0.0 arm of a ``jnp.where``) are tolerated: they
    convert at the op boundary and never carry data."""
    import re
    cfg, params = (build_system('water', screen_eps=1e-6) if screened
                   else build_wavefunction(*water()))
    if screened:
        assert cfg.screening is not None and not cfg.screening.exhaustive
    text = _sweep_jaxpr(cfg, params, path)
    leaks = sorted(set(re.findall(r'f64\[\d[^\]]*\]', text)))
    assert not leaks, f'fp64 arrays in the {path} sweep jaxpr: {leaks}'
