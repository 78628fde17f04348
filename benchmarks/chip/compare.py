"""The numbers that decide ``correct``: program against plain reference.

Three comparisons, each made on what the timed window produced:

* ``final_state``: the walkers the window ended with, as the program's
  block program left them (sign, log|psi|, drift, local energy, and for
  single-electron moves the maintained inverses), against the
  reference's dense evaluation of the same positions;
* ``replay``: the window's last sub-block replayed by the reference from
  the same walkers and keys, against what the program returned for it
  (where every walker ended up);
* ``stored``: every block the database stored against the weighted merge
  of the sub-block statistics the worker produced (exact).

Each number has its limit in the cell's file (``limits``); a cell
compares the numbers it has a limit for.  ``correct`` is true when every
one is at or under its limit.
"""
from __future__ import annotations

import numpy as np

# a walker's final position counts as the reference's when every electron
# lies within this distance (bohr) of it: rounding moves a position by
# ~1e-6 bohr, a different Metropolis decision by ~sqrt(tau) >= 0.03 bohr
SAME_POSITION = 1e-3


def _f64(x):
    return np.asarray(x, np.float64)


def final_state(prog: dict, ref: dict) -> dict:
    """Gaps between the program's final walkers and the reference's
    evaluation of the same positions, at the median walker (a walker near
    a node has a near-singular Slater matrix, on which two float32
    evaluations part by far more: PERF.md), and the walkers whose sign
    differs."""
    e_ref = _f64(ref['e_loc'])
    # each walker's gap relative to its own local energy, or to the
    # median walker's where that is larger: near a node E_L diverges and
    # float32 evaluation orders part by ~kappa * 1e-7 of it
    scale = np.maximum(np.abs(e_ref), max(float(np.median(np.abs(e_ref))),
                                          1.0))
    gap_e = np.abs(_f64(prog['e_loc']) - e_ref) / scale
    out = dict(
        eloc_med=float(np.median(gap_e)),
        logpsi_med=float(np.median(
            np.abs(_f64(prog['log_psi']) - _f64(ref['log_psi']))
            / np.maximum(np.abs(_f64(ref['log_psi'])), 1.0))),
        sign_flips=float(np.sum(_f64(prog['sign']) != _f64(ref['sign']))))
    if 'drift' in prog:
        d_ref = _f64(ref['drift'])
        gap = np.max(np.abs(_f64(prog['drift']) - d_ref), axis=(1, 2))
        out['drift_med'] = float(np.median(
            gap / np.maximum(np.max(np.abs(d_ref), axis=(1, 2)), 1.0)))
    if 'minv_up' in prog:
        per = []
        for k in ('minv_up', 'minv_dn'):
            b = _f64(ref[k])
            gap = np.max(np.abs(_f64(prog[k]) - b), axis=(1, 2))
            per.append(gap / np.maximum(np.max(np.abs(b), axis=(1, 2)), 1.0))
        out['minv_med'] = float(np.median(np.maximum(*per)))
    return out


def replay(prog_r, ref_r) -> dict:
    """The replayed sub-block: the share of walkers that ended elsewhere
    than the reference sent them.  (The sub-block's energy is not
    compared: a few walkers near a node or a nucleus carry local energies
    of 1e4 Ha and more, which two float32 evaluations part by a per cent,
    so its gap does not separate the program from the control; PERF.md.)"""
    dist = np.max(np.abs(_f64(prog_r) - _f64(ref_r)), axis=(1, 2))
    return dict(moved_off=float(np.mean(dist > SAME_POSITION)))


def stored(rows: list, subblocks: list, per_block: int,
           late: float) -> dict:
    """Stored block rows against the merge of the worker's sub-blocks.

    ``rows``: (block_id, weight, e_mean, e2_mean) as the database holds
    them; ``subblocks``: (t_end, weight, e_mean, e2_mean) in the worker's
    order.  A block whose last sub-block ended after ``late`` (while the
    manager was shutting its data path down) may be missing; every other
    block must be stored, and every stored block must match.
    """
    want = []
    for b in range(0, len(subblocks), per_block):
        part = subblocks[b:b + per_block]
        w = sum(p[1] for p in part)
        want.append((part[-1][0] > late, (
            w, sum(p[1] * p[2] for p in part) / w,
            sum(p[1] * p[3] for p in part) / w)))
    have = {int(r[0]): r[1:] for r in rows}
    bad = 0
    for i, (optional, exp) in enumerate(want):
        got = have.get(i)
        if got is None:
            bad += not optional
        elif not np.allclose(got, exp, rtol=1e-9, atol=0.0):
            bad += 1
    bad += sum(1 for i in have if i >= len(want))
    return dict(db_mismatch=float(bad))


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Compare each number that has a limit; -> (correct, checks)."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = value is not None and np.isfinite(value) and value <= limit
        ok = ok and bool(good)
        checks[name] = dict(value=value, limit=limit)
    return ok, checks
