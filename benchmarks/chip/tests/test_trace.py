"""Trace reduction: busy union, idle share, top ops and idle gaps."""
from pathlib import Path

import pytest

from bench import load_module

CHIP = Path(__file__).resolve().parents[1]
tr = load_module(CHIP / 'trace.py')
DATA = Path(__file__).resolve().parent / 'data' / 'small.xplane.pb'


def test_union_merges_overlaps():
    assert tr.union([(5, 15), (0, 10), (20, 30), (30, 31)]) == \
        [(0, 15), (20, 31)]


def test_reduce_by_hand():
    # window 0..100 ns; ops on one device at 10-30, 20-40 and 60-70:
    # busy = 30 + 10 = 40 ns, idle share 0.6; gaps 0-10, 40-60, 70-100.
    devices = {'/device:TPU:0': [('dot', 10, 30), ('dot', 20, 40),
                                 ('exp', 60, 70)]}
    spans = [('bench.window', 0, 100), ('bench.run_subblock', 0, 55),
             ('bench.db_append', 65, 100)]
    red = tr.reduce(devices, spans)
    assert red['busy_s'] == pytest.approx(40e-9)
    assert red['window_s'] == pytest.approx(100e-9)
    assert red['idle_share'] == pytest.approx(0.6)
    assert red['device_ops'] == [['dot', pytest.approx(40e-9)],
                                 ['exp', pytest.approx(10e-9)]]
    assert red['idle_gaps'] == [['db_append', pytest.approx(30e-9)],
                                ['run_subblock', pytest.approx(20e-9)],
                                ['run_subblock', pytest.approx(10e-9)]]


def test_reduce_needs_window_and_device():
    assert tr.reduce({}, [('bench.window', 0, 1)]) is None
    assert tr.reduce({'/device:TPU:0': [('a', 0, 1)]}, []) is None


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e by ``record_trace.py``: four calls
    of one jitted program, a 20 ms host pause in ``bench.db_append``."""
    devices, spans = tr.load(DATA)
    assert list(devices) == ['/device:TPU:0']
    red = tr.reduce(devices, spans)
    win = [e - s for n, s, e in spans if n == 'bench.window'][0] * 1e-9
    assert red['window_s'] == pytest.approx(win)
    assert 0 < red['busy_s'] < red['window_s']
    # the longest gap is the host pause, and it is named by its span
    name, secs = red['idle_gaps'][0]
    assert name == 'db_append' and 0.02 <= secs < red['window_s']
    # the busy time is the union of the ops, never more than their sum
    assert red['busy_s'] <= sum(v for _, v in red['device_ops']) + 1e-12
