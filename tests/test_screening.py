"""Distance-screened AO->Slater pipeline: exactness, structure, scaling.

The contract under test (DESIGN.md §11):

* eps = 0 drops only the dense path's exact zeros, so every screened
  evaluation — MO tensor, psi_state, psi_state_batched, a full SEM sweep —
  is BITWISE identical to its unscreened counterpart;
* eps < 0 builds an exhaustive structure that routes to the unscreened
  branches (the feature flag is inert);
* eps > 0 drops AO values bounded by eps * |poly| at the cutoff sphere;
* the cell-list candidate sets are supersets of the brute-force
  within-radius sets (screening can only drop what the radii allow);
* the structure is built once per wavefunction (``screening.build_count``)
  and the sparse fallback mask rebuild never fires in the per-sweep
  pipeline (``aos.mask_fallback_count``);
* the fitted cost exponent of the screened sweep stays sub-quadratic while
  the dense sweep does not (slow tier; the committed BENCH_scaling.json is
  gated by tools/bench_gate.py on the same metric).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                      # pragma: no cover
    HAVE_HYPOTHESIS = False


def seed_property(max_examples):
    """Hypothesis ``@given(seed)`` when available (CI), otherwise a fixed
    seed sweep — the properties hold for every seed either way."""
    def deco(fn):
        if HAVE_HYPOTHESIS:
            return settings(max_examples=max_examples, deadline=None)(
                given(st.integers(min_value=0, max_value=10 ** 6))(fn))
        return pytest.mark.parametrize('seed', range(5))(fn)
    return deco


from repro.core import aos, screening, wavefunction as wf
from repro.core.basis import ao_cutoff_radii
from repro.core.screening import (_build_cell_list, _cell_ids,
                                  active_ao_lists, active_mo_lists,
                                  build_screening)
from repro.systems.bench import (build_bench_wavefunction,
                                 make_bench_system, synthetic_chain)

_SYS = {}


def _system(n_elec=60):
    if n_elec not in _SYS:
        _SYS[n_elec] = make_bench_system('micro-peptide', n_elec=n_elec,
                                         seed=5)
    return _SYS[n_elec]


def _positions(sys, seed=0, n=None):
    rng = np.random.default_rng(seed)
    n = n or sys.mol.n_elec
    at = rng.integers(0, sys.mol.coords.shape[0], n)
    return jnp.asarray(sys.mol.coords[at]
                       + rng.normal(scale=1.2, size=(n, 3)), jnp.float32)


# ---------------------------------------------------------------------------
# cell-list structure properties
# ---------------------------------------------------------------------------
@seed_property(25)
def test_cell_list_candidates_superset_of_brute_force(seed):
    """27-neighborhood members cover every point within the cell edge h —
    for query points inside, at the edge of, and far outside the grid."""
    rng = np.random.default_rng(seed)
    n_pts = int(rng.integers(1, 40))
    pts = rng.uniform(-8, 8, (n_pts, 3))
    h = float(rng.uniform(0.5, 6.0))
    cl = _build_cell_list(pts, h)
    q = np.concatenate([rng.uniform(-12, 12, (20, 3)),
                        pts[rng.integers(0, n_pts, 5)]
                        + rng.normal(scale=h, size=(5, 3))])
    cid = np.asarray(_cell_ids(cl, jnp.asarray(q, jnp.float32)))
    members = np.asarray(cl.members)[cid]
    valid = np.asarray(cl.valid)[cid]
    for i in range(q.shape[0]):
        cand = set(members[i][valid[i]].tolist())
        near = np.where(np.sum((pts - q[i]) ** 2, -1) < h * h)[0]
        missing = set(near.tolist()) - cand
        assert not missing, (q[i], h, missing)


def test_budget_cannot_overflow():
    """Static budget == max 27-neighborhood population: every candidate of
    every query cell fits, so active counts never exceed the budget."""
    s = _system()
    scr = build_screening(s.basis, s.mol.coords, s.mos, eps=1e-8)
    r = _positions(s, seed=1, n=200)
    _, active, count = active_ao_lists(scr, r)
    assert int(jnp.max(count)) <= scr.ao_budget
    assert active.shape[-1] == scr.ao_budget


# ---------------------------------------------------------------------------
# screened AO evaluation: agreement with the dense block
# ---------------------------------------------------------------------------
@seed_property(10)
def test_screened_ao_block_bitwise_at_active_slots(seed):
    """Screened B equals the gathered dense B exactly where active; slots
    outside the candidate/active set hold exact zeros."""
    s = _system()
    scr = build_screening(s.basis, s.mol.coords, s.mos, eps=1e-8)
    r = _positions(s, seed=seed, n=16)
    idx, active, _ = active_ao_lists(scr, r)
    Bp = aos.eval_ao_block_screened(s.basis, s.mol.coords, r, idx, active)
    B, _ = aos.eval_ao_block(s.basis, s.mol.coords, r)     # (n_ao, N, 5)
    Bg = jnp.moveaxis(B, 0, 1)[jnp.arange(r.shape[0])[:, None], idx]
    np.testing.assert_array_equal(
        np.asarray(jnp.where(active[..., None], Bp, 0.0)),
        np.asarray(jnp.where(active[..., None], Bg, 0.0)))
    assert float(jnp.max(jnp.abs(jnp.where(active[..., None], 0.0, Bp)))) \
        == 0.0


@seed_property(10)
def test_eps_cutoff_drops_only_bounded_values(seed):
    """The documented B-level bound |dropped chi| <= eps * |poly|, split
    into its two exact halves: (1) every dropped dense-nonzero slot lies
    beyond its AO's eps-cutoff radius; (2) the abs radial envelope g stays
    below eps everywhere past that radius (monotone Gaussian tail) — so
    chi = poly * g of a dropped slot is bounded by eps * |poly|."""
    eps = 10.0 ** -int(np.random.default_rng(seed).integers(2, 6))
    s = _system()
    scr = build_screening(s.basis, s.mol.coords, s.mos, eps=eps)
    r = _positions(s, seed=seed + 1, n=12)
    n_e, n_ao = r.shape[0], s.basis.n_ao
    idx, active, _ = active_ao_lists(scr, r)
    member = np.zeros((n_e, n_ao), bool)
    # ufunc.at: candidate lists repeat padding ids, plain fancy |= would
    # let an inactive duplicate overwrite an active slot
    np.logical_or.at(
        member,
        (np.broadcast_to(np.arange(n_e)[:, None], idx.shape),
         np.asarray(idx)),
        np.asarray(active))
    B, _ = aos.eval_ao_block(s.basis, s.mol.coords, r)
    vals = np.asarray(B[..., 0]).T                          # (n_e, n_ao)
    d = np.asarray(r, np.float64)[:, None, :] \
        - s.mol.coords[s.basis.ao_atom]
    r2 = np.sum(d * d, -1)                                  # (n_e, n_ao)
    r_cut = ao_cutoff_radii(s.basis, eps)                   # (n_ao,)
    dropped = (~member) & (vals != 0.0)
    # (1) dense-nonzero slots are only dropped beyond the cutoff sphere
    # (small slack: distances screen in float32)
    assert np.all(r2[dropped] >= (r_cut ** 2)[None].repeat(n_e, 0)[dropped]
                  * (1 - 1e-3))
    # (2) |g| < eps on a grid spanning the tail past every cutoff
    rr = r_cut[:, None] * np.linspace(1.0, 3.0, 13)[None]   # (n_ao, 13)
    g_tail = np.sum(np.abs(s.basis.prim_coeff)[:, None, :]
                    * np.exp(-np.minimum(
                        s.basis.prim_exp[:, None, :]
                        * (rr ** 2)[..., None], 700.0)), -1)
    assert np.all(g_tail <= eps * (1 + 1e-5))


def test_ao_cutoff_radii_monotone_in_eps():
    s = _system()
    r_tight = ao_cutoff_radii(s.basis, 1e-4)
    r_loose = ao_cutoff_radii(s.basis, 1e-10)
    assert np.all(r_loose >= r_tight)
    assert np.all(np.isinf(ao_cutoff_radii(s.basis, 0.0)))


# ---------------------------------------------------------------------------
# eps = 0: bitwise-identical physics across every evaluation surface
# ---------------------------------------------------------------------------
def _pair(n_elec=80, eps=0.0, method='sparse'):
    """Unscreened and screened configs of one system.  The unscreened side
    runs the packed product without truncating: the 80-electron system
    has 172 AOs, more than ``k_max`` 160, and at most 144 active at any
    point of a grid over the molecule (``_assert_packs_untruncated``)."""
    s = _system(n_elec)
    cfg_d, params = build_bench_wavefunction(s, method=method, k_max=160)
    cfg_s, _ = build_bench_wavefunction(s, method=method, k_max=160,
                                        screen_eps=eps)
    return s, cfg_d, cfg_s, params


def _assert_packs_untruncated(cfg, params, r):
    """The packed route runs (``k_max < n_ao``) and truncates nothing at
    these positions (every active count fits ``k_max``)."""
    assert cfg.k_max < cfg.basis.n_ao
    _, atom_active = aos.eval_ao_block(cfg.basis, params.coords, r)
    count = aos.atom_to_ao(atom_active, cfg.basis.ao_atom).sum(-1)
    assert int(jnp.max(count)) <= cfg.k_max


def test_eps0_psi_state_bitwise():
    s, cfg_d, cfg_s, params = _pair()
    r = _positions(s, seed=2)
    _assert_packs_untruncated(cfg_d, params, r)
    a = wf.psi_state(cfg_d, params, r)
    b = wf.psi_state(cfg_s, params, r)
    for field in ('log_psi', 'drift', 'e_loc', 'e_kin', 'e_pot'):
        np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                      np.asarray(getattr(b, field)), field)
    np.testing.assert_array_equal(np.asarray(a.ao_count),
                                  np.asarray(b.ao_count))


def test_eps0_psi_state_batched_bitwise():
    s, cfg_d, cfg_s, params = _pair()
    R = jnp.stack([_positions(s, seed=i) for i in range(4)])
    _assert_packs_untruncated(cfg_d, params, R)
    a = wf.psi_state_batched(cfg_d, params, R)
    b = wf.psi_state_batched(cfg_s, params, R)
    for field in ('log_psi', 'drift', 'e_loc'):
        np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                      np.asarray(getattr(b, field)), field)


def test_eps0_sem_sweep_bitwise():
    """One full single-electron-move sweep (the production hot path):
    positions AND local energies stay bitwise identical under screening."""
    from repro.core.driver import Population
    from repro.core.sem import SEMVMCPropagator
    s, cfg_d, cfg_s, params = _pair()
    pop = Population()
    out = {}
    for tag, cfg in (('dense', cfg_d), ('screened', cfg_s)):
        prop = SEMVMCPropagator(cfg, step_size=0.4)
        state = prop.init(params, jax.random.PRNGKey(0), 4)
        state, _ = prop.propagate(params, state, jax.random.PRNGKey(1), pop)
        out[tag] = state
    np.testing.assert_array_equal(np.asarray(out['dense'].ens.r),
                                  np.asarray(out['screened'].ens.r))
    np.testing.assert_array_equal(np.asarray(out['dense'].ens.e_loc),
                                  np.asarray(out['screened'].ens.e_loc))


def test_eps0_mo_screened_tensor_bitwise():
    """Forced MO support screening (active-MO x active-AO double gather)
    reproduces the unscreened MO tensor bitwise: reach radii derive from
    exact support, so dropped rows are exact zeros."""
    s = synthetic_chain(158)
    cfg_d, params = build_bench_wavefunction(s, method='sparse')
    scr = build_screening(s.basis, s.mol.coords, np.asarray(params.mo),
                          eps=0.0, mo_screen=True)
    assert scr.mo_cells is not None
    cfg_s = wf.WavefunctionConfig(
        basis=cfg_d.basis, n_up=cfg_d.n_up, n_dn=cfg_d.n_dn,
        k_max=cfg_d.k_max, shared_orbitals=True, method='sparse',
        screening=scr)
    r = _positions(s, seed=3)
    C_d, _ = wf._mo_tensor(cfg_d, params, r)
    C_s, _ = wf._mo_tensor(cfg_s, params, r)
    np.testing.assert_array_equal(np.asarray(C_d), np.asarray(C_s))
    mo_idx, mo_valid = active_mo_lists(scr, r)
    assert int(jnp.sum(mo_valid)) > 0


def test_exhaustive_routes_to_unscreened_branch_bitwise():
    """eps < 0 builds an exhaustive structure that must be bitwise inert —
    same code path, same floats as screening=None."""
    s, cfg_d, cfg_x, params = _pair(eps=-1.0)
    assert cfg_x.screening is not None and cfg_x.screening.exhaustive
    assert not wf._screening_active(cfg_x)
    r = _positions(s, seed=4)
    _assert_packs_untruncated(cfg_d, params, r)
    a = wf.psi_state(cfg_d, params, r)
    b = wf.psi_state(cfg_x, params, r)
    np.testing.assert_array_equal(np.asarray(a.log_psi),
                                  np.asarray(b.log_psi))
    np.testing.assert_array_equal(np.asarray(a.e_loc), np.asarray(b.e_loc))


# ---------------------------------------------------------------------------
# construction discipline: one-time build, no mask-fallback rebuilds
# ---------------------------------------------------------------------------
def test_screening_structure_built_once():
    s = _system()
    before = screening.build_count()
    cfg, params = build_bench_wavefunction(s, method='sparse', k_max=160,
                                           screen_eps=0.0)
    assert screening.build_count() == before + 1
    r = _positions(s, seed=5)
    for _ in range(3):
        wf.psi_state(cfg, params, r)
    wf.psi_state_batched(cfg, params, r[None])
    assert screening.build_count() == before + 1, \
        'evaluations must reuse the one-time cell structure'


def test_sparse_pipeline_never_rebuilds_ao_mask():
    """Regression for the hoisted ``active_ao_indices`` mask: the per-sweep
    pipeline passes the precomputed ao_mask, so the trace-time fallback
    rebuild (aos.mask_fallback_count) must not fire."""
    from repro.core.driver import Population
    from repro.core.sem import SEMVMCPropagator
    s = _system(80)
    cfg, params = build_bench_wavefunction(s, method='sparse', k_max=160)
    before = aos.mask_fallback_count()
    packed = wf.product_route_counts()['packed']
    r = _positions(s, seed=6)
    _assert_packs_untruncated(cfg, params, r)
    wf.psi_state(cfg, params, r)
    wf.psi_state_batched(cfg, params, jnp.stack([r, r]))
    prop = SEMVMCPropagator(cfg, step_size=0.4)
    state = prop.init(params, jax.random.PRNGKey(0), 2)
    prop.propagate(params, state, jax.random.PRNGKey(1), Population())
    assert aos.mask_fallback_count() == before
    assert wf.product_route_counts()['packed'] >= packed + 3
    # the instrumented fallback still exists for direct API callers
    B, atom_active = aos.eval_ao_block(cfg.basis, params.coords, r)
    aos.active_ao_indices(cfg.basis, atom_active, cfg.k_max)
    assert aos.mask_fallback_count() == before + 1


# ---------------------------------------------------------------------------
# run-key semantics
# ---------------------------------------------------------------------------
def test_run_key_screening_semantics():
    """Off / exhaustive / exact keep the historical key (bitwise-identical
    estimator); eps > 0 is critical data and must change it."""
    from repro.launch.spec import RunSpec, build_run
    base = RunSpec(system='water', n_workers=1, n_walkers=4, max_blocks=1)
    k_off = build_run(base).run_key
    assert build_run(base.replace(screen_eps=0.0)).run_key == k_off
    assert build_run(base.replace(screen_eps=1e-6)).run_key != k_off


# ---------------------------------------------------------------------------
# scaling law (slow tier; mirrors the bench_gate'd BENCH_scaling.json)
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_scaling_law_screened_subquadratic_dense_not():
    from benchmarks.tables import table_scaling
    rows = table_scaling(quick=True)
    exp = {r['method']: r['exponent'] for r in rows
           if r['system'] == 'chain-fit'}
    assert exp['screened'] < 2.0, rows
    assert exp['dense'] >= 2.0, rows
