"""Attribute-reduction launcher (the paper's CLI):

    python -m repro.launch.reduce --dataset mushroom --delta SCE
    python -m repro.launch.reduce --dataset sdss --delta PR --distributed --mesh 4,2
    python -m repro.launch.reduce --dataset kdd99 --stream

``--distributed`` runs the mesh MDP implementation (requires the process to
have been started with enough devices, e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

``--stream`` runs the dataset at its *full* Table-5 shape through streaming
GrC ingestion (DESIGN.md §3.6): the table is generated and granulated in
``--chunk-rows`` chunks, so peak host memory is O(chunk + granularity
capacity) — never the 5M×41 array.  ``--max-rows``/``--max-attrs`` apply
only to the non-streaming path.
"""
from __future__ import annotations

import argparse
import json


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", required=True)
    ap.add_argument("--delta", default="SCE", choices=["PR", "SCE", "LCE", "CCE"])
    ap.add_argument("--stream", action="store_true",
                    help="full paper-scale shape via streaming GrC ingestion")
    ap.add_argument("--chunk-rows", type=int, default=65536,
                    help="rows granulated per streaming chunk")
    ap.add_argument("--max-rows", type=int, default=None,
                    help="row cap, non-streaming path only (default 20000)")
    ap.add_argument("--max-attrs", type=int, default=None,
                    help="attribute cap, non-streaming path only (default 64)")
    ap.add_argument("--max-features", type=int, default=None)
    ap.add_argument("--mode", default="incremental", choices=["incremental", "spark"])
    ap.add_argument("--backend", default="segment",
                    choices=["segment", "onehot", "pallas", "fused",
                             "fused_xla", "sweep", "sweep_xla"],
                    help="Θ evaluation backend (fused = PR-1 Pallas kernel; "
                         "sweep/sweep_xla = PR-4 read-once candidate sweep)")
    ap.add_argument("--bin-ladder", default="off", choices=["on", "off"],
                    help="K-adaptive bin ladder for the candidate sweep "
                         "(DESIGN.md §5.3): early iterations pay "
                         "K-proportional work, zero recompiles on the "
                         "device engine")
    ap.add_argument("--engine", default="auto", choices=["auto", "host", "device"],
                    help="greedy loop: device-resident while_loop or legacy host loop")
    ap.add_argument("--selector", default="analytic",
                    choices=["heuristic", "analytic", "pinned"],
                    help="kernel tile / ladder-rung selection (DESIGN.md "
                         "§5.2): analytic = roofline cost model (default), "
                         "heuristic = legacy VMEM-occupancy rule, pinned = "
                         "kernel-module defaults")
    ap.add_argument("--shrink", action="store_true",
                    help="FSPA universe shrinking (drop pure classes)")
    ap.add_argument("--mp-chunk", type=int, default=64)
    ap.add_argument("--ensemble", default=None, metavar="MEASURES",
                    help="comma-separated measure grid (or 'all' = "
                         "PR,SCE,LCE,CCE) run as ONE stacked engine "
                         "dispatch (DESIGN.md §3.8); --shrink/"
                         "--max-features apply to every member")
    ap.add_argument("--bags", type=int, default=None, metavar="N",
                    help="with --ensemble: N bagged (bootstrap-reweighted) "
                         "replicas per measure, seeds 0..N-1")
    ap.add_argument("--no-grc", action="store_true")
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--mesh", default="4,2", help="data,model (distributed)")
    ap.add_argument("--collective", default="all_reduce",
                    choices=["all_reduce", "reduce_scatter", "fused"])
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    from repro.launch import compile_cache

    compile_cache.configure()

    from repro.data import paper_dataset, scaled_paper_dataset

    if args.stream:
        # refuse inapplicable knobs rather than silently ignoring them
        # (same policy as the --distributed block below)
        dropped = [name for name, off_default in [
            ("--max-rows", args.max_rows is not None),
            ("--max-attrs", args.max_attrs is not None),
            # --no-grc would materialize the full table (HAR has no
            # compressed representation to stream into), silently voiding
            # the O(chunk + capacity) memory bound --stream promises
            ("--no-grc", args.no_grc),
        ] if off_default]
        if dropped:
            ap.error(f"{', '.join(dropped)} not supported with --stream "
                     "(streaming runs the full Table-5 shape under GrC init)")
        source = paper_dataset(args.dataset)
        table_shape = [source.n_rows, source.n_attrs]
        x = d = None
    else:
        source = None
        x, d = scaled_paper_dataset(
            args.dataset,
            max_rows=args.max_rows if args.max_rows is not None else 20000,
            max_attrs=args.max_attrs if args.max_attrs is not None else 64,
        ).table()
        table_shape = list(x.shape)

    ladder = args.bin_ladder == "on"
    if args.ensemble is not None:
        from repro.core.engine import ENSEMBLE_BACKENDS
        from repro.core.reduction import plar_reduce_ensemble

        # refuse inapplicable knobs rather than silently ignoring them
        dropped = [name for name, off_default in [
            ("--distributed", args.distributed),
            ("--engine", args.engine == "host"),
            ("--backend", args.backend not in ENSEMBLE_BACKENDS),
            ("--delta", args.delta != "SCE"),  # the grid IS the measure knob
        ] if off_default]
        if dropped:
            ap.error(f"{', '.join(dropped)} not supported with --ensemble "
                     f"(stacked engine backends: "
                     f"{', '.join(ENSEMBLE_BACKENDS)}; measures go in the "
                     f"--ensemble list)")
        measures_ = (["PR", "SCE", "LCE", "CCE"] if args.ensemble == "all"
                     else [s.strip() for s in args.ensemble.split(",")])
        configs = [{"delta": dd, "shrink": args.shrink,
                    "max_features": args.max_features} for dd in measures_]
        seeds = None if args.bags is None else list(range(args.bags))
        rs = plar_reduce_ensemble(
            x, d, source=source, chunk_rows=args.chunk_rows, configs=configs,
            seeds=seeds, mode=args.mode, backend=args.backend, ladder=ladder,
            selector=args.selector, mp_chunk=args.mp_chunk,
            grc_init=not args.no_grc)
        grid = [{"delta": dd} if seeds is None else {"delta": dd, "seed": s}
                for dd in measures_ for s in (seeds or [None])]
        out = {
            "dataset": args.dataset, "table_shape": table_shape,
            "ensemble": [
                {**g, "reduct": r.reduct, "core": r.core,
                 "theta_full": r.theta_full, "iterations": r.iterations,
                 "elapsed_s": round(r.elapsed_s, 3)}
                for g, r in zip(grid, rs)],
        }
        if args.json:
            print(json.dumps(out, indent=2))
        else:
            print(f"{'dataset':>14}: {out['dataset']}")
            print(f"{'table_shape':>14}: {out['table_shape']}")
            for e in out["ensemble"]:
                tag = e["delta"] + (f"/bag{e['seed']}" if "seed" in e else "")
                print(f"{tag:>14}: reduct={e['reduct']} "
                      f"theta_full={e['theta_full']:.6f}")
        return

    if args.distributed:
        # the mesh driver has no mode/shrink knobs and only the mesh-capable
        # Θ backends — refuse rather than silently ignoring them
        dropped = [name for name, off_default in [
            ("--mode", args.mode != "incremental"),
            ("--backend", args.backend not in ("segment", "sweep_xla")),
            ("--shrink", args.shrink),
            ("--mp-chunk", args.mp_chunk != 64),
        ] if off_default]
        if dropped:
            ap.error(f"{', '.join(dropped)} not supported with --distributed")

        from repro.core.distributed import plar_reduce_distributed
        from repro.distributed.api import make_mesh

        shape = tuple(int(v) for v in args.mesh.split(","))
        mesh = make_mesh(shape, ("data", "model"))
        r = plar_reduce_distributed(x, d, mesh, source=source,
                                    chunk_rows=args.chunk_rows,
                                    delta=args.delta,
                                    max_features=args.max_features,
                                    collective=args.collective,
                                    backend=args.backend, ladder=ladder,
                                    selector=args.selector,
                                    engine=args.engine)
    else:
        from repro.core import plar_reduce

        r = plar_reduce(x, d, source=source, chunk_rows=args.chunk_rows,
                        delta=args.delta, mode=args.mode,
                        backend=args.backend, ladder=ladder,
                        selector=args.selector,
                        engine=args.engine, shrink=args.shrink,
                        mp_chunk=args.mp_chunk, grc_init=not args.no_grc,
                        max_features=args.max_features)

    out = {
        "dataset": args.dataset, "delta": args.delta,
        "table_shape": table_shape,
        "reduct": r.reduct, "core": r.core,
        "theta_full": r.theta_full, "iterations": r.iterations,
        "n_evaluations": r.n_evaluations, "elapsed_s": round(r.elapsed_s, 3),
    }
    if args.json:
        print(json.dumps(out, indent=2))
    else:
        for k, v in out.items():
            print(f"{k:>14}: {v}")


if __name__ == "__main__":
    main()
