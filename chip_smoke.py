#!/usr/bin/env python3
"""Smoke test of PLAR on TPU chips, through the entry points a user calls.

    python chip_smoke.py              # one chip: phases 1-5 below
    python chip_smoke.py --chips 4    # four chips: the mesh path only

One chip, one process (a chip belongs to one process at a time):

1. device   — platform, kind and count; anything but a TPU exits 1.
2. kdd99    — the paper's flagship at its Table-5 shape, 5,000,000 × 41
              generated from :data:`SEED`, streamed chunkwise onto the
              device by ``plar_reduce(source=...)`` and reduced by the
              device engine.  A second stream rebuilds the granules while a
              numpy witness counts the distinct rows of C ∪ D and of R ∪ D
              on the host: the granule count, the (R-class, decision) cell
              count and Θ(D|C), Θ(D|R) recomputed on the chip must match it,
              and Θ(D|R) must reach Θ(D|C).  The engine then runs again on
              the rebuilt granules, already compiled, which times the greedy
              loop alone.
3. mushroom — the full 5644 × 22 table: the device engine's reduct equals
              ``core/oracle.py``'s, and its Θ history is byte-identical to
              the host ``segment`` loop's.
4. kernels  — ``backend="pallas"|"fused"|"sweep"`` on the full shuttle table,
              compiled by Mosaic (interpret mode is off on a TPU): reduct
              and Θ history equal the ``segment`` run's.
5. service  — ``ReductServer`` over the full shuttle table: half submitted,
              4 update batches, 4 mixed-measure queries per round (stacked
              dispatch); the final reduct equals a batch ``plar_reduce``.

``--chips 4`` runs ``plar_reduce_distributed`` (segment backend, the
``all_reduce`` and ``reduce_scatter`` collectives) on a 2×2 mesh of the four
chips and compares each reduct with single-chip ``plar_reduce`` on the same
table; it also checks that each device of the mesh holds the granules of
its own data shard.

Any failed check exits non-zero.  The last stdout line is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed.  Wall-clock times are of each
phase's first run in the process, so they include compiles.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# The kdd99 engine's candidate chunk.  Compiled for a v5e at granule
# capacity 2^17, 41 attributes, 23 classes and v_max 10, the default
# segment engine needs 6.8 GiB of temporaries at mp_chunk=8 and 13.5 GiB at
# 16, of the chip's 15.75 GiB; 32 and up do not fit (the 23-class axis is
# padded to 128 lanes).  Guarded by tests/test_tpu_compile.py.
KDD99_MP_CHUNK = 8
# The mesh table: the largest Table-5 table whose 2×2 mesh program fits a
# v5e's HBM (kdd99 and weka15360 do not: the mesh evaluates every candidate
# of a model shard at once).
MESH_TABLE = "gisette"
# The seed every table is generated from.
SEED = 0


class Failed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise Failed(what)


def hist_bytes(r):
    import numpy as np

    return [np.float32(t).tobytes() for t in r.theta_history]


class CompileCounter:
    """Executables compiled or loaded from JAX's persistent cache, and the
    cache hits among them, from JAX's own monitoring events."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == self.COMPILE_EVENT:
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, **_):
            if event == self.CACHE_HIT_EVENT:
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


class DistinctRows:
    """Distinct rows of a stream of row blocks and their weights, in numpy
    on the host: the witness the device's grouping is checked against.
    Rows are compared as byte strings; values must be below 256."""

    def __init__(self):
        self.keys = None
        self.counts = None

    def add(self, rows, weights=None):
        import numpy as np

        rows = np.ascontiguousarray(rows, np.uint8)
        keys = rows.view(np.dtype((np.void, rows.shape[1])))[:, 0]
        counts = np.ones(len(keys), np.int64) if weights is None else weights
        if self.keys is not None:
            keys = np.concatenate([self.keys, keys])
            counts = np.concatenate([self.counts, counts])
        self.keys, inv = np.unique(keys, return_inverse=True)
        self.counts = np.bincount(inv, weights=counts).astype(np.int64)

    def sce(self) -> float:
        """Θ_SCE(D | the other columns), the last column being D:
        -Σ c·log(c/e) / n over the (class, decision) cells."""
        import numpy as np

        width = self.keys.dtype.itemsize
        rows = self.keys.view(np.uint8).reshape(-1, width)
        cond = np.ascontiguousarray(rows[:, :-1])
        _, cls = np.unique(cond.view(np.dtype((np.void, width - 1)))[:, 0],
                           return_inverse=True)
        c = self.counts.astype(np.float64)
        e = np.bincount(cls, weights=c)[cls]
        return float(-(c * np.log(c / e)).sum() / c.sum())


def phase_kdd99() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import obs
    from repro.core import measures
    from repro.core.granularity import build_granularity_streaming
    from repro.core.plan import contingency_from_ids, subset_ids
    from repro.core.reduction import plar_reduce
    from repro.data import paper_dataset

    stream = paper_dataset("kdd99", seed=SEED)
    print(f"  table {stream.n_rows} x {stream.n_attrs}, {stream.n_dec} "
          f"classes, v_max {stream.v_max}; SCE, chunk_rows 65536, "
          f"mp_chunk {KDD99_MP_CHUNK}, greedy phase unbounded", flush=True)
    tracer = obs.enable()
    tracer.clear()
    t0 = time.perf_counter()
    r = plar_reduce(source=stream, chunk_rows=65536, delta="SCE",
                    mp_chunk=KDD99_MP_CHUNK)
    wall = time.perf_counter() - t0
    recs = tracer.records()
    folds = [s for s in recs if s.name == "pipeline.fold_chunk"]
    disp = [s for s in recs if s.name == "engine.dispatch"][-1]
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    print(f"  granules {folds[-1].args['granules']}, capacity "
          f"{disp.args['cap']}, reduct {r.reduct} (core {r.core}), "
          f"iterations {r.iterations}", flush=True)
    print(f"  plar_reduce {wall:.1f}s first run incl. compiles: "
          f"{len(folds)} chunk folds {sum(s.dur for s in folds):.1f}s, "
          f"engine dispatch {disp.dur:.1f}s; peak_bytes_in_use {peak}",
          flush=True)
    check(r.theta_history[-1] <= measures.f32_threshold(r.theta_full, 1e-6),
          f"engine stopped at Θ(D|R) {r.theta_history[-1]!r} within tol "
          f"of Θ(D|C) {r.theta_full!r}")

    # A second stream rebuilds the granules on the chip while numpy counts
    # the distinct rows of C ∪ D and R ∪ D on the host.
    cols = list(r.reduct)
    wit_c, wit_r = DistinctRows(), DistinctRows()

    def witnessed_chunks():
        for x, d in stream.chunks(65536):
            wit_c.add(np.column_stack([x, d]))
            wit_r.add(np.column_stack([x[:, cols], d]))
            yield x, d

    t0 = time.perf_counter()
    g = build_granularity_streaming(witnessed_chunks(), n_dec=stream.n_dec,
                                    v_max=stream.v_max)
    print(f"  second stream with the numpy witness "
          f"{time.perf_counter() - t0:.1f}s", flush=True)
    check(int(g.n_total) == stream.n_rows, "every row streamed")
    check(int(g.num) == len(wit_c.keys),
          f"{int(g.num)} granules == {len(wit_c.keys)} distinct rows of "
          f"C ∪ D (numpy)")

    # The engine again, on the rebuilt granules: compiled, so the dispatch
    # is the greedy loop alone.
    tracer.clear()
    r2 = plar_reduce(source=g, delta="SCE", mp_chunk=KDD99_MP_CHUNK)
    disp2 = [s for s in tracer.records() if s.name == "engine.dispatch"][-1]
    obs.disable()
    print(f"  second engine dispatch, no compile: {disp2.dur:.3f}s for "
          f"{r2.iterations} iterations", flush=True)
    check(not disp2.args["compiled"] and disp2.args["cap"] == disp.args["cap"],
          "second dispatch reused the compiled engine")
    check(r2.reduct == r.reduct and hist_bytes(r2) == hist_bytes(r),
          "second run's reduct and Θ history == first run's")

    # Θ(D|R) on the chip, outside the engine, against the witness
    ids, _ = subset_ids(g, jnp.asarray(cols, jnp.int32), exact=True)
    cont = contingency_from_ids(ids, g.d, g.w, g.valid, n_bins=g.capacity,
                                m=g.n_dec)
    theta_r = float(measures.evaluate("SCE", cont, g.n_total))
    cells = int(jnp.count_nonzero(cont))
    wit_theta_c, wit_theta_r = wit_c.sce(), wit_r.sce()
    # tol, plus the f32 rounding of a different summation order
    slack = 1e-6 + 2.0 ** -20 * abs(r.theta_full)
    print(f"  Θ(D|C) engine {r.theta_full!r}, numpy {wit_theta_c!r}; "
          f"Θ(D|R) chip {theta_r!r} over {cells} cells, numpy "
          f"{wit_theta_r!r} over {len(wit_r.keys)} distinct rows of R ∪ D",
          flush=True)
    check(cells == len(wit_r.keys),
          "(R-class, decision) cells == distinct rows of R ∪ D (numpy)")
    check(abs(r.theta_full - wit_theta_c) <= slack
          and abs(theta_r - wit_theta_r) <= slack,
          f"Θ(D|C) and Θ(D|R) == numpy's within {slack:.3g}")
    check(theta_r - r.theta_full <= slack,
          f"recomputed Θ(D|R) reaches Θ(D|C) within {slack:.3g}")


def phase_mushroom() -> None:
    from repro.core.oracle import reduct_oracle
    from repro.core.reduction import plar_reduce
    from repro.data import paper_dataset

    t = paper_dataset("mushroom", seed=SEED)
    x, d = t.table()
    kw = dict(delta="SCE", n_dec=t.n_dec, v_max=t.v_max)
    t0 = time.perf_counter()
    dev = plar_reduce(x, d, engine="device", **kw)
    host = plar_reduce(x, d, engine="host", **kw)
    orc = reduct_oracle("SCE", x, d)
    print(f"  table {x.shape[0]} x {x.shape[1]}: device {dev.reduct}, host "
          f"{host.reduct}, oracle {orc} "
          f"({time.perf_counter() - t0:.1f}s first run incl. compiles)",
          flush=True)
    check(dev.reduct == orc, "device engine reduct == core/oracle.py reduct")
    check(dev.reduct == host.reduct
          and hist_bytes(dev) == hist_bytes(host),
          "device Θ history byte-identical to the host segment loop's")


def phase_kernels() -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.reduction import plar_reduce
    from repro.data import paper_dataset
    from repro.kernels.contingency.fused import fused_theta_pallas
    from repro.kernels.contingency.kernel import contingency_pallas
    from repro.kernels.contingency.sweep import sweep_theta_pallas
    from repro.kernels.platform import interpret_mode

    check(not interpret_mode(), "interpret mode is off on this platform")
    packed = jnp.zeros((8, 1024), jnp.int32)
    wd = jnp.zeros((1024, 128), jnp.float32)
    lowered = {
        "pallas": contingency_pallas.lower(packed, wd, n_bins=256),
        "fused": fused_theta_pallas.lower(packed, wd, n_bins=256,
                                          delta="SCE"),
        "sweep": sweep_theta_pallas.lower(packed, packed[0], wd, v_max=4,
                                          n_bins=256, delta="SCE"),
    }
    for name, low in lowered.items():
        check("tpu_custom_call" in low.compile().as_text(),
              f"{name} kernel compiles to a Mosaic custom call")

    t = paper_dataset("shuttle", seed=SEED)
    x, d = t.table()
    kw = dict(delta="SCE", n_dec=t.n_dec, v_max=t.v_max, engine="host")
    t0 = time.perf_counter()
    ref = plar_reduce(x, d, backend="segment", **kw)
    print(f"  table {x.shape[0]} x {x.shape[1]}: segment {ref.reduct} "
          f"({time.perf_counter() - t0:.1f}s)", flush=True)
    for backend in ("pallas", "fused", "sweep"):
        t0 = time.perf_counter()
        r = plar_reduce(x, d, backend=backend, **kw)
        jax.block_until_ready(r.theta_history)
        print(f"  {backend}: {r.reduct} ({time.perf_counter() - t0:.1f}s "
              f"first run incl. compiles)", flush=True)
        check(r.reduct == ref.reduct and hist_bytes(r) == hist_bytes(ref),
              f"{backend} reduct and Θ history == segment's")


def phase_service() -> None:
    from repro.core.reduction import plar_reduce
    from repro.data import paper_dataset
    from repro.launch.reduce_server import stream_updates
    from repro.service import ReductServer

    t = paper_dataset("shuttle", seed=SEED)
    x, d = t.table()

    async def drive():
        async with ReductServer() as srv:
            final, events = await stream_updates(
                srv, x, d, n_dec=t.n_dec, v_max=t.v_max, delta="SCE",
                client_measures=("PR", "LCE", "CCE"), updates=4)
            return final, events, dict(srv.stats), srv.metrics.summary()

    t0 = time.perf_counter()
    final, events, stats, metrics = asyncio.run(drive())
    for e in events:
        print(f"  {e['event']:>9}: +{e['rows']} rows, {e['granules']} "
              f"granules, {e['latency_s']}s, reduct {e['reduct']}",
              flush=True)
    batch = plar_reduce(x, d, delta="SCE", n_dec=t.n_dec, v_max=t.v_max)
    print(f"  {len(events)} rounds x 4 queries in "
          f"{time.perf_counter() - t0:.1f}s first run incl. compiles; "
          f"engine_runs {stats['engine_runs']}, mean batch occupancy "
          f"{metrics['mean_batch_occupancy']}", flush=True)
    check(metrics["mean_batch_occupancy"] > 1,
          "mixed-measure queries shared stacked dispatches")
    check(final.reduct == batch.reduct,
          f"final reduct {final.reduct} == batch plar_reduce {batch.reduct}")


def phase_mesh() -> None:
    import jax
    import numpy as np

    # the mesh driver's own ingestion, to look at where it put the granules
    from repro.core.distributed import (_granularity_from_source,
                                        plar_reduce_distributed)
    from repro.core.reduction import plar_reduce
    from repro.data import paper_dataset
    from repro.distributed.api import make_mesh

    stream = paper_dataset(MESH_TABLE, seed=SEED)
    print(f"  table {MESH_TABLE} {stream.n_rows} x {stream.n_attrs}",
          flush=True)
    t0 = time.perf_counter()
    single = plar_reduce(source=stream, delta="SCE")
    print(f"  single chip: {single.reduct} "
          f"({time.perf_counter() - t0:.1f}s first run incl. compiles)",
          flush=True)
    mesh = make_mesh((2, 2), ("data", "model"))
    for collective in ("all_reduce", "reduce_scatter"):
        t0 = time.perf_counter()
        r = plar_reduce_distributed(source=stream, mesh=mesh, delta="SCE",
                                    collective=collective)
        print(f"  mesh 2x2 {collective}: {r.reduct} "
              f"({time.perf_counter() - t0:.1f}s first run incl. compiles)",
              flush=True)
        check(r.reduct == single.reduct,
              f"{collective} mesh reduct == single-chip reduct")
    check(len({d.id for d in mesh.devices.flat}) == len(jax.devices()),
          "the mesh spans every chip")

    # Placement: the device that holds row block s of the granule table
    # must hold the granules of data shard s — the rows s·n/S..(s+1)·n/S of
    # every chunk — checked against numpy over the same slices.
    nd = mesh.shape["data"]
    wit = [DistinctRows() for _ in range(nd)]
    for xc, dc in stream.chunks(65536):
        n = len(xc)
        for s in range(nd):
            lo, hi = s * n // nd, (s + 1) * n // nd
            wit[s].add(np.column_stack([xc[lo:hi], dc[lo:hi]]))
    gx, gd, gw, gv, _ = _granularity_from_source(
        stream, mesh, n_dec=stream.n_dec, v_max=stream.v_max,
        chunk_rows=65536)
    cap_ps = gx.shape[0] // nd
    on_dev = {f: {sh.device: sh for sh in a.addressable_shards}
              for f, a in (("x", gx), ("d", gd), ("w", gw), ("v", gv))}
    held = []
    for dev, sh in on_dev["x"].items():
        s = (sh.index[0].start or 0) // cap_ps
        live = np.asarray(on_dev["v"][dev].data)
        got = DistinctRows()
        got.add(np.column_stack([np.asarray(sh.data)[live],
                                 np.asarray(on_dev["d"][dev].data)[live]]),
                np.asarray(on_dev["w"][dev].data)[live].astype(np.int64))
        held.append((s, dev.id, int(live.sum())))
        check(np.array_equal(got.keys, wit[s].keys)
              and np.array_equal(got.counts, wit[s].counts),
              f"device {dev.id} holds data shard {s}'s "
              f"{len(wit[s].keys)} granules")
    check(len(held) == len(jax.devices()),
          f"granule blocks on every chip (shard, device, granules): "
          f"{sorted(held)}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke: the repro package is not next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    from repro.launch import compile_cache

    cache_dir = compile_cache.configure()
    import jax

    compiles = CompileCounter()
    devs = jax.devices()
    dev = devs[0]
    print(f"[device] platform {dev.platform}, kind {dev.device_kind!r}, "
          f"count {len(devs)}; compile cache {cache_dir}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; this smoke test has no CPU fallback",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 1

    phases = ([("mesh", phase_mesh)] if args.chips == 4 else
              [("kdd99", phase_kdd99), ("mushroom", phase_mushroom),
               ("kernels", phase_kernels), ("service", phase_service)])
    t_all = time.perf_counter()
    for name, fn in phases:
        print(f"[{name}]", flush=True)
        t0 = time.perf_counter()
        try:
            fn()
        except Failed:
            return 1
        print(f"[{name}] passed in {time.perf_counter() - t0:.1f}s "
              f"(first run, includes compiles)", flush=True)
    print(f"[compiles] {compiles.compiles} executables compiled or loaded "
          f"from the persistent cache in {compiles.compile_s:.1f}s, "
          f"{compiles.cache_hits} of them loaded; total "
          f"{time.perf_counter() - t_all:.1f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
