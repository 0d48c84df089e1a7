"""Streaming GrC ingestion benchmark: build throughput + peak host memory.

The paper's premise (PLAR §3.3, Fig. 9) is that the granularity
representation is small enough to cache — but *getting there* used to
require the uncompressed ``(n_rows, n_attrs)`` table resident on the host.
This section measures what the streaming build (DESIGN.md §3.6) buys:

* ``ingest_stream_vs_monolithic`` — same table, both ingestion paths, in
  this process (one process owns the accelerator; a child could not reach
  it).  Each path's peak host memory is the ``tracemalloc`` high-water
  mark of its host allocations — the numpy row chunks or the whole table —
  reset between paths.  Streaming peaks at O(chunk + granularity
  capacity); monolithic at O(n_rows · n_attrs).
* ``ingest_paper_scale`` — the Table-5 flagship kdd99 at its full 5M×41
  shape, streaming only (the whole point: the monolithic path at this shape
  is exactly what we no longer need).  Granule counts are asserted equal
  between paths where both run.

Snapshot with ``python -m benchmarks.run --preset ingest`` →
``benchmarks/BENCH_ingest.json`` (CI runs the preset as a smoke step; the
paper-scale section is included via ``python -m benchmarks.run
ingest_paper_scale --tag ingest`` when refreshing the acceptance evidence).
"""
from __future__ import annotations

import dataclasses
import time
import tracemalloc
from typing import Dict, List


def _ingest(mode: str, name: str, n_rows: int, chunk_rows: int) -> Dict:
    """One ingestion path; its granule count, wall time and host peak."""
    import jax.numpy as jnp

    from repro.core import build_granularity, build_granularity_streaming
    from repro.data import paper_dataset

    t = paper_dataset(name)
    if n_rows:
        t = dataclasses.replace(t, n_rows=n_rows)
    tracemalloc.start()
    tracemalloc.reset_peak()
    t0 = time.perf_counter()
    try:
        if mode == "monolithic":
            x, d = t.table()
            g = build_granularity(jnp.asarray(x), jnp.asarray(d),
                                  n_dec=t.n_dec, v_max=t.v_max)
            del x, d
        else:
            g = build_granularity_streaming(t.chunks(chunk_rows),
                                            n_dec=t.n_dec, v_max=t.v_max)
        granules = int(g.num)
        elapsed = time.perf_counter() - t0
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    return {"granules": granules, "elapsed_s": round(elapsed, 2),
            "peak_host_mb": round(peak / 2**20, 1)}


def _row(name: str, mode: str, n_rows: int, chunk_rows: int, stats: Dict) -> Dict:
    return {
        "dataset": name,
        "rows": n_rows,
        "mode": mode,
        "chunk_rows": chunk_rows if mode == "streaming" else "-",
        "granules": stats["granules"],
        "elapsed_s": stats["elapsed_s"],
        "peak_host_mb": stats["peak_host_mb"],
        "krows_per_s": round(n_rows / max(stats["elapsed_s"], 1e-9) / 1e3, 1),
    }


def ingest_stream_vs_monolithic() -> List[Dict]:
    """Both paths on a kdd99-shaped table capped to a CI-friendly row count."""
    rows: List[Dict] = []
    shapes = [("kdd99", 1_000_000, 65536), ("shuttle", 58_000, 8192)]
    for name, n_rows, chunk_rows in shapes:
        mono = _ingest("monolithic", name, n_rows, chunk_rows)
        stream = _ingest("streaming", name, n_rows, chunk_rows)
        assert mono["granules"] == stream["granules"], (name, mono, stream)
        rows.append(_row(name, "monolithic", n_rows, chunk_rows, mono))
        rows.append(_row(name, "streaming", n_rows, chunk_rows, stream))
        rows.append({
            "dataset": name, "rows": n_rows, "mode": "host_peak_ratio",
            "chunk_rows": "-", "granules": "-", "elapsed_s": "-",
            "peak_host_mb": round(mono["peak_host_mb"]
                                  / max(stream["peak_host_mb"], 0.1), 2),
            "krows_per_s": "-",
        })
    return rows


def ingest_paper_scale() -> List[Dict]:
    """kdd99 at the full Table-5 shape (5M×41), streaming only."""
    name, chunk_rows = "kdd99", 65536
    from repro.data import paper_dataset

    n_rows = paper_dataset(name).n_rows
    stream = _ingest("streaming", name, 0, chunk_rows)
    return [_row(name, "streaming", n_rows, chunk_rows, stream)]


ALL_INGEST_BENCHES = {
    "ingest_stream_vs_monolithic": ingest_stream_vs_monolithic,
}

# Addressable by explicit name only — a ~5-8 min 5M-row build does not
# belong in the no-arg run-everything path (run.py merges these into the
# job table only when a wanted section names them).
EXPLICIT_BENCHES = {
    "ingest_paper_scale": ingest_paper_scale,
}
