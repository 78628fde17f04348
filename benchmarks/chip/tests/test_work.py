"""Work models against hand counts, and the peak table."""
from pathlib import Path

import pytest

import bench

CHIP = Path(__file__).resolve().parents[1]


def test_all_electron_hand_count():
    # H2-sized: one electron per spin, a = 4 active AOs, 3 walkers.
    # MO product 10 * n_orb * a * n_e = 10 * 1 * 4 * 2 = 80; per spin
    # block LU + inverse 2 n^3 = 2 and ratio contractions 8 n^2 = 8.
    per_walker = 80 + 2 * (2 + 8)
    assert bench.work('all_electron').flops(1, 1, 4.0, 3) == \
        pytest.approx(3 * per_walker)


def test_sem_hand_count():
    # same sizes, 3 primitives per AO, half the moves accepted.  Per spin
    # block: one move with AO values 2*3*4 = 24, MO values 2*1*4 = 8,
    # ratio 2; rank-1 update 0.5 * 4 = 2; LU 2/3 and contractions 8.
    per_spin = 24 + 8 + 2 + 2 + 2 / 3 + 8
    per_walker = 80 + 2 * per_spin
    assert bench.work('sem').flops(1, 1, 4.0, 3, accept=0.5, prims=3.0) == \
        pytest.approx(3 * per_walker)


def test_peaks_table():
    p = bench.peak('TPU v5 lite')
    assert p['bf16_flops_per_s'] == 197e12
    assert p['hbm_bytes_per_s'] == 819e9
    with pytest.raises(bench.BenchmarkError):
        bench.peak('cpu')
