"""The program's spans inside ``plar_reduce`` (DESIGN.md §3.11), and their
place on the ``jax.profiler`` clock.

A streamed reduction records, per chunk, its host→device copy
(``ingest.h2d``) beside ``pipeline.fold_chunk``, which holds the chunk's
grouping (``ingest.granulate``) and, where the chunk fills the pending run,
the run's merge into the accumulator (``ingest.merge``); then Θ(D|C), the core and the engine's dispatch, all
inside one ``reduction.plar_reduce`` root.  The benchmark's per-layer
metrics read these names, so a rename fails here and not silently there.
Tracing off records nothing and leaves the reduct as it was.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import plar_reduce, resolve_granularity
from repro.core.engine import _forced_attrs, init_state, make_engine_run
from repro.core.granularity import (
    build_granularity, exact_class_ids, merge_granularity)

ROOT = Path(__file__).resolve().parents[1]
CHUNK = 256


class Rows:
    """A row source (the ``GranuleSource`` protocol) over host arrays."""

    def __init__(self, x, d):
        self.x, self.d = x, d
        self.n_dec, self.v_max = int(d.max()) + 1, int(x.max()) + 1

    def n_chunks(self, chunk_rows):
        return -(-len(self.x) // chunk_rows)

    def chunk(self, i, chunk_rows):
        rows = slice(i * chunk_rows, (i + 1) * chunk_rows)
        return self.x[rows], self.d[rows]


def table():
    """1,000 rows over 5 attributes whose class is (x0 + x1) mod 3: the core
    is {0, 1}, and the fold takes 4 chunks of 256."""
    rng = np.random.default_rng(0)
    x = rng.integers(0, 4, size=(1000, 5)).astype(np.int32)
    d = ((x[:, 0] + x[:, 1]) % 3).astype(np.int32)
    return Rows(x, d)


@pytest.fixture
def tracer():
    t = obs.get_tracer()
    was = t.enabled
    t.enable()
    t.clear()
    yield t
    t.clear()
    t.enabled = was


def traced_reduce(tracer, source, **kw):
    result = plar_reduce(source=source, chunk_rows=CHUNK, delta="SCE", **kw)
    return result, [r for r in tracer.records() if r.ph == "X"]


def inside(inner, outer):
    return (outer.t_start <= inner.t_start
            and inner.t_start + inner.dur <= outer.t_start + outer.dur + 1e-9)


def named(recs, name):
    return [r for r in recs if r.name == name]


def test_streamed_reduction_spans_nest_under_the_root(tracer):
    src = table()
    result, recs = traced_reduce(tracer, src)
    assert result.core == [0, 1]
    (root,) = named(recs, "reduction.plar_reduce")
    assert all(inside(r, root) for r in recs)
    n_chunks = src.n_chunks(CHUNK)
    h2d = named(recs, "ingest.h2d")
    every_fold = named(recs, "pipeline.fold_chunk")
    folds = [f for f in every_fold if f.args["rows"]]
    assert len(h2d) == len(folds) == n_chunks
    for copy, fold in zip(h2d, folds):
        # the copy precedes its fold, outside it
        assert copy.t_start + copy.dur <= fold.t_start
        assert copy.args["rows"] == fold.args["rows"]
        assert copy.args["bytes"] == copy.args["rows"] * (5 + 1) * 4
    granulate = named(recs, "ingest.granulate")
    assert len(granulate) == n_chunks
    # a merge per flush, inside the fold of the chunk that filled the run
    # to the accumulator's capacity, or in the fold's closing span
    merge = named(recs, "ingest.merge")
    acc_cap, run = granulate[0].args["capacity"], []
    flushes = iter(merge)
    for fold, gran in zip(folds, granulate):
        assert inside(gran, fold)
        if fold is folds[0]:
            continue
        run.append(gran.args["capacity"])
        if sum(run) >= acc_cap:
            m = next(flushes)
            assert inside(m, fold) and m.args["chunks"] == len(run)
            assert m.args["granules"] == fold.args["granules"]
            acc_cap, run = m.args["capacity"], []
    if run:
        (closing,) = [f for f in every_fold if not f.args["rows"]]
        m = next(flushes)
        assert inside(m, closing) and m.args["chunks"] == len(run)
    assert next(flushes, None) is None
    assert len(merge) < n_chunks - 1
    assert sum(m.args["chunks"] for m in merge) == n_chunks - 1
    (theta,) = named(recs, "reduction.theta_full")
    (core,) = named(recs, "reduction.core")
    (engine,) = named(recs, "engine.dispatch")
    assert every_fold[-1].t_start + every_fold[-1].dur <= theta.t_start
    assert theta.t_start + theta.dur <= core.t_start
    assert core.t_start + core.dur <= engine.t_start
    assert core.args == {"A": 5, "path": "exact"}
    cap = resolve_granularity(source=src, chunk_rows=CHUNK).capacity
    assert root.args == {"delta": "SCE", "engine": "device", "source": "rows",
                         "A": 5, "capacity": cap, "k": len(result.reduct)}


def wide_table():
    """400 rows over 300 attributes, past the exact core's 128, so the core
    groups by fingerprint sketch."""
    rng = np.random.default_rng(1)
    x = rng.integers(0, 2, size=(400, 300)).astype(np.int32)
    return Rows(x, ((x[:, 0] + x[:, 1]) % 2).astype(np.int32))


@pytest.mark.parametrize("path", ["exact", "sketch"])
def test_the_core_spans_and_readbacks_of_each_path(tracer, path):
    """The sketch path records its fingerprints and its one dispatch of A
    groupings inside ``reduction.core``; each path counts its device-to-host
    reads in the process registry, traced or not: one a call on either path
    (the exact path's prefix and suffix scans read back all A Θs at once)."""
    src = table() if path == "exact" else wide_table()
    A = src.x.shape[1]
    other = {"exact": "sketch", "sketch": "exact"}[path]
    readbacks = obs.counter(f"plar_core_readbacks_{path}_total")
    others = obs.counter(f"plar_core_readbacks_{other}_total")
    for traced in (True, False):
        tracer.enabled = traced
        tracer.clear()
        before, before_other = readbacks.value, others.value
        _, recs = traced_reduce(tracer, src)
        assert readbacks.value - before == 1
        assert others.value == before_other
        if not traced:
            assert recs == []
            continue
        (core,) = named(recs, "reduction.core")
        assert core.args == {"A": A, "path": path}
        prints, sketch = (named(recs, "core.fingerprints"),
                          named(recs, "core.sketch"))
        if path == "exact":
            assert prints == sketch == []
            continue
        (prints,), (sketch,) = prints, sketch
        assert inside(prints, core) and inside(sketch, core)
        assert prints.t_start + prints.dur <= sketch.t_start
        cap = resolve_granularity(source=src, chunk_rows=CHUNK).capacity
        assert sketch.args == {"A": A, "chunks": -(-A // 64), "capacity": cap}


def test_a_merge_that_overflows_records_its_rebuild(tracer):
    """A merge whose granules overflow its capacity is built again; the
    process registry counts it, traced or not, for ``/metrics``.  A
    streamed fold merges at a capacity that holds both operands, so it
    rebuilds nothing."""
    rebuilds = obs.counter("plar_merge_rebuilds_total")
    rows = np.arange(128, dtype=np.int32)
    x = np.stack([rows // 16, rows % 16], axis=1)
    halves = [build_granularity(jnp.asarray(x[h]), jnp.zeros((64,), jnp.int32),
                                n_dec=1, v_max=16)
              for h in (slice(0, 64), slice(64, 128))]
    for traced in (True, False):
        tracer.enabled = traced
        before = rebuilds.value
        merged = merge_granularity(*halves)
        assert int(merged.num) == merged.capacity == 128
        assert rebuilds.value == before + 1
        assert f"plar_merge_rebuilds_total {rebuilds.value}" in \
            obs.render_prometheus().splitlines()
        before = rebuilds.value
        plar_reduce(source=table(), chunk_rows=CHUNK, delta="SCE")
        assert rebuilds.value == before


@pytest.mark.parametrize("kind", ["granules", "arrays"])
def test_reductions_from_granules_or_arrays_fold_nothing(tracer, kind):
    src = table()
    if kind == "granules":
        g = resolve_granularity(source=src, chunk_rows=CHUNK)
        tracer.clear()
        _, recs = traced_reduce(tracer, g)
    else:
        result = plar_reduce(src.x, src.d, delta="SCE")
        recs = [r for r in tracer.records() if r.ph == "X"]
        assert result.core == [0, 1]
    names = {r.name for r in recs}
    assert not names & {"ingest.h2d", "pipeline.fold_chunk",
                        "ingest.granulate", "ingest.merge"}
    assert {"reduction.theta_full", "reduction.core",
            "engine.dispatch"} <= names
    (root,) = named(recs, "reduction.plar_reduce")
    assert root.args["source"] == kind


def test_the_host_engine_closes_the_root_too(tracer):
    result, recs = traced_reduce(tracer, table(), engine="host")
    (root,) = named(recs, "reduction.plar_reduce")
    assert root.args["engine"] == "host"
    assert root.args["k"] == len(result.reduct)
    assert not named(recs, "engine.dispatch")


def test_tracing_off_records_nothing_and_keeps_the_reduct(tracer):
    on, _ = traced_reduce(tracer, table())
    tracer.disable()
    tracer.clear()
    off = plar_reduce(source=table(), chunk_rows=CHUNK, delta="SCE")
    assert tracer.recorded == 0 and len(tracer) == 0
    assert (off.reduct, off.core) == (on.reduct, on.core)
    assert np.float64(off.theta_full).tobytes() == \
        np.float64(on.theta_full).tobytes()
    assert np.asarray(off.theta_history, np.float64).tobytes() == \
        np.asarray(on.theta_history, np.float64).tobytes()


def test_every_span_is_on_the_profiler_clock(tracer, tmp_path):
    """Each recorded span is a host event of the same name in the
    ``.xplane.pb``, at one offset from the ring's clock."""
    from bench import trace as tr

    src = table()
    plar_reduce(source=src, chunk_rows=CHUNK, delta="SCE")   # compile
    tracer.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        plar_reduce(source=src, chunk_rows=CHUNK, delta="SCE")
    recs = tracer.records()
    host = tr.load(tr.find_xplane(str(tmp_path)))["host"]
    by_name = {}
    for name, s, _ in sorted(host, key=lambda ev: ev[1]):
        by_name.setdefault(name, []).append(s)
    offsets = []
    for name in {r.name for r in recs}:
        ring = sorted(r.t_start for r in recs if r.name == name)
        marks = by_name.get(name, [])
        assert len(marks) == len(ring), name
        offsets += [m - r * 1e9 for m, r in zip(marks, ring)]
    # per chunk a copy, a fold and a grouping; two merges (of 1 and of 2
    # chunks' tables); then Θ(D|C), the core, the engine and the root
    assert len(offsets) == len(recs) == 3 * src.n_chunks(CHUNK) + 2 + 4
    assert max(offsets) - min(offsets) < 1e6       # 1 ms, in ns


@pytest.mark.parametrize("env", [{}, {"REPRO_TRACE": "1"}])
def test_importing_obs_leaves_jax_unloaded(env):
    code = ("import sys, repro.obs; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    base = {k: v for k, v in os.environ.items() if k != "REPRO_TRACE"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=ROOT, timeout=120,
                       env=dict(base, PYTHONPATH=str(ROOT / "src"), **env))
    assert r.returncode == 0, r.stderr


def test_device_ops_carry_named_scopes():
    """The engine body's evaluation, advance and pick, and the exact
    grouping, name their ops in the lowered program's locations."""
    g = resolve_granularity(source=table(), chunk_rows=CHUNK)
    A, cap = g.n_attrs, g.capacity
    runner = make_engine_run("SCE", "incremental", "segment", A, cap,
                             g.n_dec, g.v_max, 1e-6, 1e-5, False, A, 4)
    text = runner.lower(
        init_state(cap, A, g.valid), g.x, g.d, g.w, g.n_total,
        jnp.float32(0.0), _forced_attrs(A, []), jnp.int32(0),
    ).as_text(debug_info=True)
    for scope in ("eval_candidates", "advance", "select"):
        assert f"/{scope}/" in text, scope
    grouping = exact_class_ids.lower(g.x, g.valid, radix=4).as_text(
        debug_info=True)
    assert "/group_columns/" in grouping
