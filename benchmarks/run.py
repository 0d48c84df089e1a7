"""Benchmark entrypoint: ``PYTHONPATH=src python -m benchmarks.run``.

One section per paper table/figure (paper_tables.py) + kernel micro-benches.
Pass table names to run a subset: ``python -m benchmarks.run table_12 fig_9``;
``--list`` prints every selectable section and preset.
Results are printed as aligned text and mirrored to benchmarks/results.json;
``--tag NAME`` additionally snapshots them to ``benchmarks/BENCH_NAME.json``
(timestamped), building the per-PR perf trajectory — see benchmarks/README.md.
"""
from __future__ import annotations

import json
import re
import sys
import time


def _print_rows(name: str, rows) -> None:
    print(f"\n=== {name} ===")
    if not rows:
        print("(empty)")
        return
    keys = list(rows[0].keys())
    widths = {k: max(len(str(k)), *(len(str(r.get(k, ''))) for r in rows)) for k in keys}
    print("  ".join(str(k).ljust(widths[k]) for k in keys))
    for r in rows:
        print("  ".join(str(r.get(k, "")).ljust(widths[k]) for k in keys))


# Named section bundles: ``--preset NAME`` runs the bundle and snapshots it
# as benchmarks/BENCH_NAME.json (an implicit --tag NAME).
def _environment() -> dict:
    """The machine stamp on every BENCH_*.json snapshot: enough to tell
    whether two snapshots in the perf trajectory are comparable."""
    import datetime
    import os

    import jax

    return {
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "cpu_count": os.cpu_count(),
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
    }


PRESETS = {
    "engine": ["engine_host_vs_device"],
    "ensemble": ["ensemble_stacked_vs_sequential"],
    "kernels": ["contingency_backends", "fused_theta_vs_unfused"],
    "ingest": ["ingest_stream_vs_monolithic"],
    "sweep": ["sweep_ladder_speedup"],
    "service": ["service_incremental_vs_recompute"],
    "serve": ["serve_batched_vs_single_flight", "serve_dedup_and_admission"],
    "autotune": ["autotune_tile_selection", "autotune_dispatch_bound"],
    "chaos": ["chaos_refold_vs_rebuild", "chaos_restart_warm_vs_cold",
              "chaos_fault_storm_absorbed"],
    "obs": ["obs_tracer_overhead", "obs_trace_chaos"],
}


def main() -> None:
    from repro.launch import compile_cache

    compile_cache.configure()

    from .autotune_bench import ALL_AUTOTUNE_BENCHES
    from .chaos_bench import ALL_CHAOS_BENCHES
    from .engine_bench import ALL_ENGINE_BENCHES
    from .ensemble_bench import ALL_ENSEMBLE_BENCHES
    from .ingest_bench import ALL_INGEST_BENCHES, EXPLICIT_BENCHES
    from .kernel_bench import ALL_BENCHES
    from .obs_bench import ALL_OBS_BENCHES
    from .paper_tables import ALL_TABLES
    from .serve_bench import ALL_SERVE_BENCHES
    from .service_bench import ALL_SERVICE_BENCHES

    # accept both "--flag VALUE" and "--flag=VALUE"
    argv = []
    for a in sys.argv[1:]:
        if a.startswith("--preset=") or a.startswith("--tag="):
            argv.extend(a.split("=", 1))
        else:
            argv.append(a)
    tag = None
    if "--tag" in argv:
        i = argv.index("--tag")
        if i + 1 >= len(argv):
            sys.exit("usage: python -m benchmarks.run [SECTION ...] "
                     "[--preset NAME] [--tag NAME]")
        tag = argv[i + 1]
        if not re.fullmatch(r"[A-Za-z0-9._-]+", tag):
            sys.exit(f"invalid --tag {tag!r}: use letters, digits, '.', '_', '-'")
        argv = argv[:i] + argv[i + 2:]
    if "--preset" in argv:
        i = argv.index("--preset")
        if i + 1 >= len(argv) or argv[i + 1] not in PRESETS:
            sys.exit(f"--preset expects one of: {', '.join(sorted(PRESETS))}")
        preset = argv[i + 1]
        argv = argv[:i] + [s for s in PRESETS[preset] if s not in argv] + argv[i + 2:]
        tag = tag or preset
    wanted = argv or None
    jobs = {**ALL_TABLES, **ALL_BENCHES, **ALL_ENGINE_BENCHES,
            **ALL_ENSEMBLE_BENCHES, **ALL_INGEST_BENCHES,
            **ALL_SERVICE_BENCHES, **ALL_SERVE_BENCHES,
            **ALL_AUTOTUNE_BENCHES, **ALL_CHAOS_BENCHES,
            **ALL_OBS_BENCHES}
    # long-running sections run only when named, never via the no-arg path
    selectable = {**jobs, **EXPLICIT_BENCHES}
    if "--list" in argv:
        print("sections:")
        for name in sorted(selectable):
            note = "  (explicit-only)" if name in EXPLICIT_BENCHES else ""
            print(f"  {name}{note}")
        print("presets (--preset NAME, implies --tag NAME):")
        for name in sorted(PRESETS):
            print(f"  {name}: {', '.join(PRESETS[name])}")
        return
    if wanted:
        unknown = [s for s in wanted if s not in selectable]
        if unknown:
            sys.exit(f"unknown section(s): {', '.join(unknown)}\n"
                     f"available: {', '.join(sorted(selectable))}")
        jobs = {k: v for k, v in selectable.items() if k in wanted}

    results = {}
    for name, fn in jobs.items():
        t0 = time.perf_counter()
        rows = fn()
        results[name] = rows
        _print_rows(name, rows)
        print(f"[{name}: {time.perf_counter() - t0:.1f}s]")

    with open("benchmarks/results.json", "w") as f:
        json.dump(results, f, indent=2)
    print("\nwritten: benchmarks/results.json")

    if tag is not None:
        snap = f"benchmarks/BENCH_{tag}.json"
        # merge by section: partial runs refresh what they ran without
        # destroying a snapshot's other sections (e.g. BENCH_ingest.json
        # holds the CI-smoke section AND the paper-scale evidence); each
        # section keeps its own timestamp so carried-over evidence is
        # distinguishable from freshly regenerated rows
        sections, section_times = {}, {}
        try:
            with open(snap) as f:
                prev = json.load(f)
            sections = prev.get("sections", {})
            section_times = prev.get("section_times", {})
        except (OSError, json.JSONDecodeError):
            pass
        now = int(time.time())
        sections.update(results)
        section_times.update({name: now for name in results})
        with open(snap, "w") as f:
            json.dump({"tag": tag, "unix_time": now,
                       "environment": _environment(),
                       "section_times": section_times,
                       "sections": sections}, f, indent=2)
        print(f"written: {snap}")


if __name__ == "__main__":
    main()
