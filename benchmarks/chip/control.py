"""Program and control readings of a cell's comparison, over many seeds.

    python3 benchmarks/chip/control.py --workload smallest.vmc \
        --seconds 4 --seeds 11 12 13

In one process that holds the chip: for each seed, one short run of the
cell as ``run.py`` makes it (same walkers, same block program, same
comparison), printing one JSON line with the program's numbers and, for
each lower reference precision (``high``: three bfloat16 passes, the
nearest below the configuration's float32 at HIGHEST; ``bf16``: one
pass), the numbers the reference at that precision gives in the
program's place, and whether the cell's own comparison (``compare.judge``
with the cell's limits, on every number the control gives) finds the
control correct: it must not.  The limits in ``workloads/<cell>.json``
were set from these readings: above the largest program reading, below
the smallest ``high`` reading.  A run that has no chip fails, as
``run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import compare  # noqa: E402
import run  # noqa: E402


def judged(result: dict) -> dict:
    """Each control's ``correct`` under the cell's limits."""
    limits = {k: v['limit'] for k, v in result['checks'].items()}
    return {prec: compare.judge(nums, {k: limits[k] for k in nums
                                       if k in limits})[0]
            for prec, nums in result['control'].items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--seeds', type=int, nargs='+', required=True)
    ap.add_argument('--controls', nargs='+', default=['high'])
    args = ap.parse_args(argv)
    for seed in args.seeds:
        try:
            r = run.run_cell(args.workload, seed, args.seconds, False,
                             controls=tuple(args.controls))
        except run.NoChip as e:
            print(f'control: {e}', file=sys.stderr)
            return 2
        print(json.dumps(dict(
            seed=seed, correct=r['correct'],
            program={k: v['value'] for k, v in r['checks'].items()},
            control=r['control'], control_correct=judged(r),
            metrics=r['metrics'])), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
