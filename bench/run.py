#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed as ``setup_s``) makes the cell's inputs from ``--seed`` and
warms up every shape the window uses; the window then runs the cell's units
back to back for ``--seconds`` and closes when the first unit that ends
after that ends.  Once it has closed, what the timed units produced is
compared with the plain reference in ``bench/reference.py``.  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics from a profiler trace of the window),
``device``, with ``--trace 1`` ``breakdown``, and ``checks``: each number
compared beside its limit, which also end standard error.  Without a TPU,
or with fewer chips than the cell asks for, it exits 1 and prints no result.

JAX's persistent compilation cache is kept in ``.jax_cache/`` at the root of
the checkout, whatever the environment says, so that only the first run of a
cell in a checkout compiles.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def configure_jax() -> None:
    """The persistent compile cache at its fixed place in the checkout,
    holding every executable however small or quick to compile, and never
    evicting one."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from repro.launch import compile_cache

    compile_cache.configure()
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the checkout's root, not bench/, leads the import path
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))
    from bench import harness

    try:
        bm = harness.benchmark(ROOT)
        cell, config, workload = harness.find_cell(bm, args.workload)
        configure_jax()
        result = harness.run(cell, config, workload, bm, seed=args.seed,
                             seconds=args.seconds, trace=bool(args.trace),
                             t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"bench: {e}; there is no CPU fallback", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
