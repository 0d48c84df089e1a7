#!/usr/bin/env python3
"""The control of the path-following check (``bench/entries/
batch_follow.py``), at the cell's own size.

    python3 bench/control_follow.py --workload <cell> --seeds 1 2 3

``bench/control.py``'s job for a cell whose check follows the program's
path: for each seed it draws the table a run's first window reduction
gets, computes the plain reference in bfloat16 with float32 sums
(granule multiplicities, counts and per-class terms held in bfloat16),
puts that answer, its own greedy path included, in the program's place,
and prints the numbers the run would compare, beside their limits: the
control has to fail at least one of them.  The benchmark's own runs never
run this; its readings set the upper end of each limit (PERF.md).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def control(config: dict, workload: dict, seed: int) -> dict:
    """The numbers of the bfloat16 control for ``seed``, and those that
    fail their limits."""
    from bench import gen, reference
    from bench.entries import batch, batch_follow

    shape = gen.Shape.of(config["table"])
    options = workload["options"]
    compared = dict(workload["limits"], **workload["path_limits"])
    ref_g = reference.granules(*batch.table(shape, workload, seed, 1)
                               .weighted_rows())
    ctl_g = ref_g[:2] + (reference._Arith("bfloat16").hold(ref_g[2])
                         .astype(np.int64),)
    delta = workload["deltas"][0]
    ctl = reference.reduce(*ctl_g, delta=delta, v_max=shape.v_max,
                           precision="bfloat16",
                           tol=options.get("tol", 1e-6),
                           tie_tol=options.get("tie_tol", 1e-5))
    nums = batch_follow.compare(
        types.SimpleNamespace(**ctl), ctl_g, ref_g, delta, shape.v_max,
        options, workload["path_limits"]["pick_gap"], inner=ctl["inner"])
    return {"seed": seed, "delta": delta,
            "numbers": {k: float(v) for k, v in nums.items()},
            "fails": [k for k in nums if k in compared
                      and nums[k] > compared[k]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[0] = str(ROOT)
    from bench import harness

    _, config, workload = harness.find_cell(harness.benchmark(ROOT),
                                            args.workload)
    failed_all = True
    for seed in args.seeds:
        t0 = time.perf_counter()
        out = control(config, workload, seed)
        failed_all &= bool(out["fails"])
        print(json.dumps(dict(out, seconds=time.perf_counter() - t0)),
              flush=True)
    print(json.dumps({"control_fails_every_seed": failed_all}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
