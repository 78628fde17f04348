"""Ahead-of-time TPU v5e compiles of the five Pallas kernels at paper widths.

Interpret mode checks what a kernel computes but not whether the TPU's
compiler accepts it: block shapes off the (8, 128) tiling and grid steps
that overflow VMEM are refused only by the real compiler.  These tests
compile every kernel's ``ops`` entry point for a described (not attached)
v5e chip at the widths of the paper's ``smallest`` (79 electrons per spin,
346 AOs) and ``1amb`` (866/865, 3804 AOs) systems, with the tiles
``kernels.platform.kernel_plan`` picks for the TPU, and check that the
result holds a compiled kernel (``tpu_custom_call``).  Where the plan
refuses a width, the test asserts the refusal.  Nothing runs.

The topology is described inside a fixture (never at import), and the
persistent compilation cache is off around the compiles: entries written
for a chip that is not attached could not be read back here.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import platform as kplat
from repro.kernels.platform import KernelRefused, kernel_plan
from repro.systems.bench import synthetic_ci

W = 64
# (n_up, n_dn, n_ao, screening budget, walkers for the MO products)
SYSTEMS = {
    'smallest': (79, 79, 346, 352, 64),
    '1amb': (866, 865, 3804, 512, 4),
}


@pytest.fixture(scope='module')
def one_chip():
    """A single described v5e chip, with the persistent cache off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:                  # no TPU compiler installed
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update('jax_enable_compilation_cache', was)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_plan(monkeypatch):
    """Plan kernels as on a TPU while this process runs on the CPU."""
    monkeypatch.setattr(kplat, 'default_platform', lambda: 'tpu')


def _compile(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'tpu_custom_call' in text, 'no compiled Pallas kernel'


F32, I32, BOOL = jnp.float32, jnp.int32, jnp.bool_


@pytest.mark.parametrize('system', list(SYSTEMS))
def test_sparse_mo_compiles(one_chip, tpu_plan, system):
    from repro.kernels.sparse_mo.ops import sparse_mo_products
    n_up, n_dn, n_ao, _, w = SYSTEMS[system]
    n_e = w * (n_up + n_dn)
    _compile(one_chip, sparse_mo_products, ((n_up, n_ao), F32),
             ((n_ao, n_e, 5), F32), ((n_e, n_ao), BOOL))


@pytest.mark.parametrize('system', list(SYSTEMS))
def test_screened_mo_compiles(one_chip, tpu_plan, system):
    from repro.kernels.screened_mo.ops import screened_mo_products
    n_up, n_dn, n_ao, K, w = SYSTEMS[system]
    n_e = w * (n_up + n_dn)
    _compile(one_chip, screened_mo_products, ((n_up, n_ao), F32),
             ((n_e, K, 5), F32), ((n_e, K), I32), ((n_e, K), BOOL))


@pytest.mark.parametrize('system', list(SYSTEMS))
def test_sem_update_compiles(one_chip, tpu_plan, system):
    from repro.kernels.sem_update.ops import sem_rank1_update
    n = SYSTEMS[system][0]
    _compile(one_chip, sem_rank1_update, ((W, n, n), F32), ((W, n), F32),
             ((W, n), F32), ((W,), BOOL), ((), I32))


@pytest.mark.parametrize('system', list(SYSTEMS))
def test_multidet_ratio_compiles(one_chip, tpu_plan, system):
    from repro.kernels.multidet_ratio.ops import multidet_ratios
    n_up, n_dn = SYSTEMS[system][:2]
    n_orb, n_det = n_up + max(8, n_up // 2), 1000
    ci = synthetic_ci(n_up, n_dn, n_orb, n_det, seed=0)

    def fn(P, g, row, r_other):
        return multidet_ratios(P, g, row, ci.holes_up, ci.parts_up,
                               ci.coeffs, r_other)

    _compile(one_chip, fn, ((W, n_orb, n_up), F32), ((W, n_orb), F32),
             ((W, n_up), F32), ((W, n_det), F32))


def _fused(n, n_e):
    from repro.kernels.fused_sweep.ops import fused_sweep_block

    def fn(minv, phi, r, r_prop, en, logu):
        return fused_sweep_block(minv, phi, r, r_prop, en, logu,
                                 jnp.float32(1.0), offset=0, n_up=n,
                                 use_kernel=True, tile_w=8)

    shapes = (((W, n, n), F32), ((W, n, n), F32), ((W, n_e, 3), F32),
              ((W, n, 3), F32), ((W, n), F32), ((W, n), F32))
    return fn, shapes


def test_fused_sweep_compiles_smallest(one_chip, tpu_plan):
    n_up, n_dn = SYSTEMS['smallest'][:2]
    fn, shapes = _fused(n_up, n_up + n_dn)
    _compile(one_chip, fn, *shapes)


def test_fused_sweep_refused_1amb(one_chip, tpu_plan):
    """A walker tile of the whole (866, 866) inverse cannot fit VMEM: the
    plan refuses the width by name instead of running something else."""
    n_up, n_dn = SYSTEMS['1amb'][:2]
    fn, shapes = _fused(n_up, n_up + n_dn)
    with pytest.raises(KernelRefused, match=r'fused_sweep.*n=866'):
        _compile(one_chip, fn, *shapes)


# ---------------------------------------------------------------------------
# the platform rule itself (no topology needed)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize('kernel,tiles,dims', [
    ('sparse_mo', (8, 8, 8), {}),
    ('screened_mo', (16, 32, 4), dict(n_ao=346)),
    ('sem_update', (8, 0), dict(n=79)),
    ('multidet_ratio', (8, 8), {}),
    ('fused_sweep', (4,), dict(n=79, n_e=158)),
])
def test_plan_interprets_off_tpu_and_compiles_on_tpu(kernel, tiles, dims):
    cpu = kernel_plan(kernel, tiles, platform='cpu', **dims)
    assert cpu.interpret and cpu.tiles == tiles
    tpu = kernel_plan(kernel, tiles, platform='tpu', **dims)
    assert not tpu.interpret
    # the default platform here is the CPU
    assert kernel_plan(kernel, tiles, **dims).interpret


@pytest.fixture(scope='module')
def smallest_mo_pass(one_chip):
    """HLO text of the ensemble MO tensor pass at `smallest` with 64
    walkers, compiled for a v5e."""
    from repro.core.wavefunction import _mo_tensor_ensemble
    from repro.systems import build_system
    cfg, params = build_system('smallest')
    ps = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                     sharding=one_chip),
                      params)
    R = jax.ShapeDtypeStruct((W, cfg.n_elec, 3), F32, sharding=one_chip)
    return cfg, jax.jit(lambda p, r: _mo_tensor_ensemble(cfg, p, r)[0]).lower(
        ps, R).compile().as_text()


def test_mo_tensor_pass_has_no_walker_batched_gather(smallest_mo_pass):
    """The ensemble MO tensor pass at `smallest` with 64 walkers compiles
    for a v5e without gathering a walker-batched operand by constant
    indices: that gather (the AO power table, ``aos._monomial_1d``) halted
    the chip.  Only the packed product's data-dependent gathers may
    remain, where it runs."""
    import re
    _, text = smallest_mo_pass
    batched = re.findall(rf'gather\(.*slice_sizes={{{W},', text)
    assert not batched, batched[:2]


def test_mo_tensor_pass_runs_the_dense_product(smallest_mo_pass):
    """At `smallest` ``k_max`` (512) is at least ``n_ao`` (346), so the pass
    takes the dense product: no active-AO index build (its sorts and
    scatters) and no ``qmc.ao_index`` scope in the compiled program."""
    import re
    cfg, text = smallest_mo_pass
    assert cfg.k_max >= cfg.basis.n_ao
    ops = set(re.findall(r'= \S+ ([\w-]+)\(', text))
    assert not ops & {'sort', 'scatter'}, ops & {'sort', 'scatter'}
    assert 'qmc.ao_index' not in text
    assert 'qmc.mo_product' in text


def test_plan_tpu_tiles_are_aligned():
    assert kernel_plan('sparse_mo', (8, 8, 8),
                       platform='tpu').tiles == (128, 128, 128)
    assert kernel_plan('screened_mo', (8, 8, 8), platform='tpu',
                       n_ao=3804).tiles == (128, 128, 8)
    assert kernel_plan('multidet_ratio', (4, 8),
                       platform='tpu').tiles == (8, 128)
    tw, tr = kernel_plan('sem_update', (8, 0), platform='tpu', n=866).tiles
    assert tw == 8 and tr % 8 == 0 and 896 % tr == 0
    (fw,) = kernel_plan('fused_sweep', (4,), platform='tpu', n=79,
                        n_e=158).tiles
    assert fw % 8 == 0


def test_plan_refuses_by_kernel_and_width():
    with pytest.raises(KernelRefused, match=r'fused_sweep.*n=866'):
        kernel_plan('fused_sweep', (8,), platform='tpu', n=866, n_e=1731)
    with pytest.raises(KernelRefused, match=r'fused_sweep.*n_det=10'):
        kernel_plan('fused_sweep', (8,), platform='tpu', n=5, n_e=10,
                    n_det=10)
    # interpret mode never refuses
    assert kernel_plan('fused_sweep', (8,), platform='cpu', n=866,
                       n_e=1731).interpret
