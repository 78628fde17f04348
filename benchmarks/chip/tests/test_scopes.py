"""Per-layer device time from the program's scopes and spans (scopes.py)."""
from pathlib import Path

import pytest

from bench import load_module

CHIP = Path(__file__).resolve().parents[1]
sc = load_module(CHIP / 'scopes.py')

# window 0..1000 ns; sub-blocks 100-500 and 550-900 are complete, 950-1100
# runs past the window's end; two generations a sub-block
SPANS = [('bench.window', 0, 1000),
         ('qmc.subblock', 100, 500), ('qmc.merge', 500, 520),
         ('qmc.forward', 530, 545), ('qmc.subblock', 550, 900),
         ('qmc.poll', 920, 940), ('qmc.subblock', 950, 1100)]
OPS = [
    ('%while.1', 'jit(f)/while', 105, 300),                     # container
    ('%fusion.1', 'jit(f)/while/body/qmc.ao_eval/mul:', 110, 150),
    ('%fusion.2', 'jit(f)/while/body/vmap(qmc.slater)/jit(inv)/lu:', 150,
     250),
    ('%fusion.3', 'jit(f)/qmc.block_stats/qmc.ao_eval/mul:', 300, 340),
    ('%fusion.4', 'jit(f)/qmc.block_stats/reduce_sum:', 340, 350),
    ('%rng.1', 'jit(_fold_in)/threefry2x32:', 350, 360),
    ('%fusion.9', 'jit(f)/while/body/qmc.ao_eval/mul:', 510, 540),  # between
    ('%fusion.1', 'jit(f)/while/body/qmc.ao_eval/mul:', 560, 600),
    ('%fusion.2', 'jit(f)/while/body/vmap(qmc.slater)/jit(inv)/lu:', 600,
     700),
    ('%fusion.5', 'jit(f)/qmc.block_stats/qmc.slater/dot_general:', 700,
     720),
    ('%fusion.1', 'jit(f)/while/body/qmc.ao_eval/mul:', 960, 990),  # late
]


def test_innermost_layer_and_phase():
    assert sc.layer_of('jit(f)/qmc.sem_move/qmc.ao_eval/mul:') == \
        ('ao_eval', False)
    assert sc.layer_of('jit(f)/qmc.block_stats/vmap(qmc.slater)/lu:') == \
        ('slater', True)
    assert sc.layer_of('jit(f)/qmc.block_stats/reduce_sum:') == (None, True)
    assert sc.layer_of('jit(<lambda>)/dot_general:') == (None, False)


def test_reduce_by_hand():
    red = sc.reduce({'/device:TPU:0': OPS}, SPANS, steps=2)
    assert red['subblocks'] == 2 and red['generations'] == 4
    ms = 1e-6 / 4                      # ns in the two sub-blocks -> ms/gen
    # containers are left out; the layer is the innermost scope
    assert red['layer_ms']['ao_eval'] == pytest.approx((40 + 40 + 40) * ms)
    assert red['layer_ms']['slater'] == pytest.approx((100 + 100 + 20) * ms)
    assert red['layer_ms']['mo_product'] == 0.0
    assert red['unscoped_ms'] == pytest.approx((10 + 10) * ms)
    # block_stats: inclusive, per sub-block
    assert red['block_stats_ms'] == pytest.approx((40 + 10 + 20) * 1e-6 / 2)
    # busy: the union of the sub-blocks' ops, the loop included
    assert red['busy_ms'] == pytest.approx((255 + 160) * ms)
    assert red['unscoped_share'] == pytest.approx(100 * 20 / 415)
    # idle in the whole window: 0-105, 360-510, 540-560, 720-960 and
    # 990-1000 (525 ns); no qmc. span covers the head 0-100, 545-550,
    # 900-920 or 940-950 (135 ns)
    assert red['idle_unattributed_share'] == pytest.approx(100 * 135 / 525)


def test_head_of_the_window_counts_as_unattributed():
    """The profiler starts inside a sub-block whose span it never sees:
    the idle time after that sub-block's ops is unattributed until a
    recorded span opens, and the layer times come from the one complete
    sub-block alone."""
    spans = [('bench.window', 0, 1000), ('qmc.merge', 300, 310),
             ('qmc.submit', 310, 320), ('qmc.subblock', 330, 900)]
    ops = [('%fusion.1', 'jit(f)/while/body/qmc.ao_eval/mul:', 0, 250),
           ('%fusion.1', 'jit(f)/while/body/qmc.ao_eval/mul:', 340, 880)]
    red = sc.reduce({'/device:TPU:0': ops}, spans, steps=2)
    assert red['subblocks'] == 1
    assert red['layer_ms']['ao_eval'] == pytest.approx(540e-6 / 2)
    # idle 250-340 and 880-1000 (210 ns); uncovered 250-300, 320-330 and
    # 900-1000 (160 ns)
    assert red['idle_unattributed_share'] == pytest.approx(100 * 160 / 210)


def test_copies_of_an_unseen_subblock_are_attributed():
    """``qmc.fetch`` opens once the device is done, so the copies of the
    sub-block the profiler started in are in the trace and cover the idle
    time after its ops, though its ``qmc.subblock`` span is not."""
    spans = [('bench.window', 0, 900), ('qmc.fetch', 251, 300),
             ('qmc.merge', 300, 310), ('qmc.submit', 310, 320),
             ('qmc.subblock', 320, 900)]
    ops = [('%fusion.1', 'jit(f)/while/body/qmc.ao_eval/mul:', 0, 250),
           ('%fusion.1', 'jit(f)/while/body/qmc.ao_eval/mul:', 340, 880)]
    red = sc.reduce({'/device:TPU:0': ops}, spans, steps=2)
    assert red['subblocks'] == 1
    # idle 250-340 and 880-900 (110 ns); uncovered 250-251 (1 ns)
    assert red['idle_unattributed_share'] == pytest.approx(100 * 1 / 110)


def test_ops_without_a_path_take_the_layer_around_them():
    """A compiler-made op (no metadata) between two ops of one layer is
    that layer's; between two layers, or after glue, it has none."""
    ops = [('%while.1', 'jit(f)/while', 100, 400),
           ('%fusion.1', 'jit(f)/qmc.ao_index/cumsum:', 110, 120),
           ('%sort.1', '', 120, 160),                  # -> ao_index
           ('%fusion.2', '', 160, 200),                # -> ao_index
           ('%fusion.3', 'jit(f)/qmc.ao_index/scatter:', 200, 210),
           ('%copy.1', '', 210, 230),                  # ao_index | slater
           ('%fusion.4', 'jit(f)/qmc.slater/lu:', 230, 300),
           ('%fusion.5', 'jit(f)/add:', 300, 310),
           ('%copy.2', '', 310, 320),                  # glue | block_stats
           ('%fusion.6', 'jit(f)/qmc.block_stats/qmc.slater/dot:', 320,
            330),
           ('%copy.3', '', 330, 340),                  # -> block_stats slater
           ('%fusion.7', 'jit(f)/qmc.block_stats/qmc.slater/dot:', 340,
            350)]
    got = [x for (n, _, _, _), x in zip(ops, sc._attribute(ops))
           if not n.startswith('%while')]
    assert got == [('ao_index', False)] * 4 + [(None, False),
                                                ('slater', False),
                                                (None, False), (None, False),
                                                ('slater', True),
                                                ('slater', True),
                                                ('slater', True)]
    red = sc.reduce({'/device:TPU:0': ops},
                    [('bench.window', 0, 500), ('qmc.subblock', 50, 450)],
                    steps=1)
    assert red['layer_ms']['ao_index'] == pytest.approx(100e-6)
    assert red['inherited_ms'] == pytest.approx(90e-6)
    # busy 100-400 in the sub-block, the loop included: 300 ns
    assert red['inherited_share'] == pytest.approx(100 * 90 / 300)
    assert red['unscoped_ms'] == pytest.approx(40e-6)


def test_minus():
    assert sc._minus([(0, 10), (20, 30)], [(5, 8), (9, 25)]) == \
        [(0, 5), (8, 9), (25, 30)]
    assert sc._minus([(0, 10)], []) == [(0, 10)]


def test_stale_metadata_reports_no_scope_metric(capsys):
    ops = [(n, 'jit(<lambda>)/while/body/mul:', s, e) for n, _, s, e in OPS]
    red = sc.reduce({'/device:TPU:0': ops}, SPANS, steps=2)
    assert 'layer_ms' not in red and 'unscoped_share' not in red
    assert 'block_stats_ms' not in red
    assert red['idle_unattributed_share'] == pytest.approx(100 * 135 / 525)
    assert 'stale' in capsys.readouterr().err
    ctx = dict(scopes=red)
    for name in ('ao_eval_ms', 'slater_ms', 'unscoped_share',
                 'inherited_share', 'block_stats_ms'):
        assert load_module(CHIP / 'metrics' / f'{name}.py').read(ctx) \
            is None


def test_program_without_spans_reduces_to_none():
    bench_only = [s for s in SPANS if not s[0].startswith('qmc.')]
    assert sc.reduce({'/device:TPU:0': OPS}, bench_only, steps=2) is None
    for name in ('ao_eval_ms', 'idle_unattributed_share', 'sem_move_ms'):
        reader = load_module(CHIP / 'metrics' / f'{name}.py').read
        assert reader(dict(scopes=None)) is None


def _write_xspace(path, ops, spans):
    """An ``.xplane.pb`` holding ``ops`` on one TPU's op line and ``spans``
    on one host thread (times in ns); ``tf_op`` is stored by value on odd
    ops and by reference to a stat name on even ones, as profilers do."""
    space = sc._xspace()()
    dev = space.planes.add(name='/device:TPU:0')
    dev.stat_metadata.add(key=1, value=dict(id=1, name='tf_op'))
    line = dev.lines.add(name='XLA Ops', timestamp_ns=0)
    for i, (name, op, s, e) in enumerate(ops):
        mid, ref = 10 + i, 1000 + i
        meta = dev.event_metadata.add(key=mid).value
        meta.id, meta.name = mid, f'{name} = fusion(...)'
        if i % 2:
            meta.stats.add(metadata_id=1, str_value=op)
        else:
            dev.stat_metadata.add(key=ref, value=dict(id=ref, name=op))
            meta.stats.add(metadata_id=1, ref_value=ref)
        line.events.add(metadata_id=mid, offset_ps=int(s * 1000),
                        duration_ps=int((e - s) * 1000))
    host = space.planes.add(name='/host:CPU')
    thread = host.lines.add(name='worker', timestamp_ns=0)
    for i, (name, s, e) in enumerate(spans):
        host.event_metadata.add(key=i + 1, value=dict(id=i + 1, name=name))
        thread.events.add(metadata_id=i + 1, offset_ps=int(s * 1000),
                          duration_ps=int((e - s) * 1000))
    path.write_bytes(space.SerializeToString())


def test_readers_reduce_the_trace_of_the_calling_run_cell(tmp_path):
    """The readers find the trace file and the steps in ``run_cell``'s
    frame, read the file and reduce once per ``ctx``; they report nothing
    without a trace file, and fail without such a frame or on a file that
    is no XSpace."""
    from google.protobuf.message import DecodeError
    path = tmp_path / 't.xplane.pb'
    _write_xspace(path, OPS, SPANS)

    def run_cell(found, steps):
        ctx = {}
        values = {name: load_module(CHIP / 'metrics' / f'{name}.py')
                  .read(ctx) for name in ('ao_eval_ms', 'unscoped_share')}
        return values, ctx

    values, ctx = run_cell([str(path)], 2)
    want = sc.reduce({'/device:TPU:0': OPS}, SPANS, steps=2)
    assert values['ao_eval_ms'] == pytest.approx(want['layer_ms']['ao_eval'])
    got = ctx['scopes']
    assert got['layer_ms'] == pytest.approx(want.pop('layer_ms'))
    assert {k: got[k] for k in want} == pytest.approx(want)
    assert values['unscoped_share'] == pytest.approx(want['unscoped_share'])
    assert run_cell([], 2) == ({'ao_eval_ms': None,
                                'unscoped_share': None}, {'scopes': None})
    with pytest.raises(LookupError):
        sc.of({})
    bad = tmp_path / 'bad.xplane.pb'
    bad.write_bytes(b'\xff\xff')                     # not an XSpace
    with pytest.raises(DecodeError):
        run_cell([str(bad)], 2)


def test_load_reads_a_recorded_chip_trace():
    """``load`` decodes a real TPU v5e trace (``record_trace.py``) to the
    ops ``ProfileData`` sees, each with the ``tf_op`` path only the proto
    holds; the program there writes no ``qmc.`` span, so it reduces to
    None, as the parent commit's traces do."""
    data = CHIP / 'tests' / 'data' / 'small.xplane.pb'
    devices, spans = sc.load(data)
    want, want_spans = sc._trace.load(data)
    assert devices.keys() == want.keys() == {'/device:TPU:0'}
    ops, ref = devices['/device:TPU:0'], want['/device:TPU:0']
    assert [o[0] for o in ops] == [r[0] for r in ref] and len(ops) == 4
    assert [o[2:] for o in ops] == [pytest.approx(r[1:], abs=5)
                                    for r in ref]
    assert {o[1] for o in ops} == {'jit(<lambda>)/dot_general:'}
    assert spans == [s for s in want_spans if s[0] == 'bench.window']
    assert sc.reduce(devices, spans, steps=2) is None
