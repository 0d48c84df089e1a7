#!/usr/bin/env python3
"""The control of a cell's correctness check, at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

For each seed it draws the table a run's first window reduction gets (the
resident table for a resident cell), computes the plain reference in
float64 and again in bfloat16 with float32 sums (granule multiplicities,
counts and per-class terms held in bfloat16), puts the bfloat16 answer in
the program's place, and prints the numbers the run would compare,
beside their limits: the control has to fail at least one of them.  The
benchmark's own runs never run this; its readings set the upper end of each
limit (PERF.md).
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[0] = str(ROOT)
    from bench import gen, harness, reference
    from bench.entries.batch import compare, per_measure, table

    _, config, workload = harness.find_cell(harness.benchmark(ROOT),
                                            args.workload)
    shape = gen.Shape.of(config["table"])
    limits = workload["limits"]
    failed_all = True
    for seed in args.seeds:
        t = 0 if workload["source"] == "granules" else 1
        ref_g = reference.granules(*table(shape, workload, seed, t)
                                   .weighted_rows())
        # the control's granules: the same rows, multiplicities in bfloat16
        ctl_g = ref_g[:2] + (reference._Arith("bfloat16").hold(ref_g[2])
                             .astype(np.int64),)
        for delta in workload["deltas"]:
            t0 = time.perf_counter()
            ctl = reference.reduce(*ctl_g, delta=delta, v_max=shape.v_max,
                                   precision="bfloat16",
                                   tol=workload["options"].get("tol", 1e-6),
                                   tie_tol=workload["options"].get(
                                       "tie_tol", 1e-5))
            nums = per_measure(compare(
                types.SimpleNamespace(**ctl), ctl_g, ref_g, delta,
                shape.v_max, workload["options"], inner=ctl["inner"]),
                delta, limits)
            fails = [k for k in nums if k in limits and nums[k] > limits[k]]
            failed_all &= bool(fails)
            print(json.dumps({"seed": seed, "delta": delta,
                              "numbers": {k: float(v) for k, v in nums.items()},
                              "fails": fails,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    print(json.dumps({"control_fails_every_seed": failed_all}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
