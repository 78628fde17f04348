"""Benchmark harness: one benchmark per paper table (I-V) + repo extras.

    PYTHONPATH=src python -m benchmarks.run [--full] [--tables I,IV,VI] \
        [--json OUT.json]

Prints one CSV-ish line per measurement; ``--json`` additionally writes the
rows as structured JSON (list of row objects + run metadata) so perf
trajectories can accumulate in ``BENCH_*.json`` files.  --full runs the big
systems (1ZE7/1AMB, minutes on CPU); default is the quick set.  Table VI is
the ensemble-flattened vs per-walker-vmap comparison; Table VII is the
unified-driver block loop, single-device vs walker-mesh-sharded (run under
XLA_FLAGS=--xla_force_host_platform_device_count=8 to see the sharded
rows); Table VIII compares single-electron-move sweeps (Sherman–Morrison
inverse updates) against per-move full recompute and the all-electron
propagator; Table IX is the backend parallel-efficiency table (thread vs
process workers, steady-state blocks/s from stored block timestamps);
Table X is the multideterminant ratio benchmark (shared-inverse SMW
tables vs per-determinant slogdet at n_det = 1..1000); Table XI is the
TCP grid-backend efficiency table (localhost qmc_worker subprocesses over
sockets vs thread/process at equal worker counts); Table XII is the
wavefunction-optimization table (opt-vmc energy/variance trajectory at
n_det = 1/100 plus the per-sub-block moment-accumulation overhead vs
plain VMC); Table XIII is the distance-screening scaling law (per-SEM-sweep
wavefunction-construction cost, screened vs dense, over the growing
``synthetic_chain`` systems, with fitted log-log exponents — the rows
``tools/bench_gate.py`` gates against the committed BENCH_scaling.json);
Table XIV is the multi-tenant service-throughput table (N concurrent
``QMCService`` runs over one fixed worker pool vs the whole pool behind a
single run — aggregate blocks/s, ``vs_single`` and the min/max ``fairness``
ratio); Table XV is the fused-sweep SEM table (whole-sweep fused
propagation vs the per-move dispatch loop at the same walker count, the
per-walker sweep cost against the committed Table VIII baseline, and the
mixed-precision resting state footprint per ``cfg.precision`` — gated
against the committed BENCH_fused.json).  TPU-side roofline numbers live
in experiments/roofline + EXPERIMENTS.md §Roofline.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(_ROOT))
sys.path.insert(0, str(_ROOT / 'src'))      # `repro` without PYTHONPATH=src

from benchmarks import tables as T
from repro.compile_cache import enable_compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument('--full', action='store_true')
    ap.add_argument('--tables',
                    default='I,II,III,IV,V,VI,VII,VIII,IX,X,XI,XII,XIII,'
                            'XIV,XV')
    ap.add_argument('--json', metavar='OUT.json', default=None,
                    help='also write rows as structured JSON')
    args = ap.parse_args(argv)
    enable_compile_cache()
    quick = not args.full
    want = set(args.tables.upper().split(','))

    fns = {'I': T.table1, 'II': T.table2, 'III': T.table3, 'IV': T.table4,
           'V': T.table5, 'VI': T.table_ensemble, 'VII': T.table_driver,
           'VIII': T.table_sem, 'IX': T.table_runtime,
           'X': T.table_multidet, 'XI': T.table_grid,
           'XII': T.table_opt, 'XIII': T.table_scaling,
           'XIV': T.table_serve, 'XV': T.table_fused}
    unknown = want - set(fns)
    if unknown:
        print(f'# unknown tables ignored: {",".join(sorted(unknown))} '
              f'(valid: {",".join(fns)})', flush=True)
    failures = 0
    all_rows = []
    timings = {}
    for tab, fn in fns.items():
        if tab not in want:
            continue
        print(f'# === Table {tab} ===', flush=True)
        t0 = time.time()
        try:
            rows = fn(quick=quick)
            all_rows.extend(rows)
            for row in rows:
                print(','.join(f'{k}={v}' for k, v in row.items()),
                      flush=True)
        except Exception as e:                      # pragma: no cover
            failures += 1
            print(f'table={tab},status=FAILED,error={e!r}', flush=True)
        timings[tab] = round(time.time() - t0, 1)
        print(f'# table {tab} took {timings[tab]}s', flush=True)

    if args.json:
        doc = {
            'meta': {
                'utc': time.strftime('%Y-%m-%dT%H:%M:%SZ', time.gmtime()),
                'platform': platform.platform(),
                'python': platform.python_version(),
                'quick': quick,
                'tables': sorted(want & set(fns)),
                'table_seconds': timings,
                'failures': failures,
            },
            'rows': all_rows,
        }
        out = Path(args.json)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(doc, indent=2) + '\n')
        print(f'# wrote {len(all_rows)} rows to {args.json}', flush=True)
    return 1 if failures else 0


if __name__ == '__main__':
    raise SystemExit(main())
