"""Wide tables: the core's fingerprint sketch (more than 128 attributes) on
Gisette-shaped tables, 2 values a column, 2 classes, every row distinct.

The program is held to the paper's oracle (``core/oracle.py``) and to the
benchmark's float64 reference (``bench/reference.py``): the same granules,
core and reduct.  The sketch core is one program per shape, its answers
those of the closure it replaced and of an exact grouping per attribute,
and a second table of the same shape compiles nothing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen, reference
from bench.entries.batch import granules_differ
from repro.core import measures, oracle, plar_reduce, reduction
from repro.core.granularity import dyn_column_terms
from repro.core.plan import contingency_from_ids, ids_by_sort

SHAPES = [(500, 300), (200, 1000)]
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def gisette(n_rows, n_attrs, seed=3):
    """A Gisette-shaped table whose near-duplicate rows give it a core."""
    shape = gen.Shape(n_rows=n_rows, n_attrs=n_attrs, v_max=2, n_dec=2,
                      distinct_fraction=1.0, near_duplicates=0.2,
                      near_duplicate_attrs=2)
    return gen.Table(shape, seed)


def closure_core(gran, delta, chunk=64):
    """The sketch core as it was: a closure over the table, jitted anew on
    every call, one read-back a chunk of 64 attributes."""
    A, cap = gran.n_attrs, gran.capacity
    out = np.zeros((A,), np.float64)
    h1, h2 = reduction._full_fingerprints(gran.x, gran.valid)

    @jax.jit
    def chunk_fn(cand_cols):
        def one(col):
            t1 = dyn_column_terms(gran.x, col, 0)
            t2 = dyn_column_terms(gran.x, col, 7919)
            ids, _k = ids_by_sort([h2 - t2, h1 - t1], gran.valid)
            cont = contingency_from_ids(ids, gran.d, gran.w, gran.valid,
                                        n_bins=cap, m=gran.n_dec)
            return measures.evaluate(delta, cont, gran.n_total)

        return jax.lax.map(one, cand_cols)

    for s in range(0, A, chunk):
        cols = np.arange(s, min(s + chunk, A), dtype=np.int32)
        padded = np.concatenate([cols, np.zeros((chunk - len(cols),),
                                                np.int32)])
        out[s:s + len(cols)] = np.asarray(
            chunk_fn(jnp.asarray(padded)))[:len(cols)]
    return out


@pytest.mark.parametrize("n_rows,n_attrs", SHAPES)
def test_wide_reduction_matches_oracle_and_reference(n_rows, n_attrs):
    t = gisette(n_rows, n_attrs)
    assert reduction._core_path(True, n_attrs) == "sketch"
    gran = reduction.resolve_granularity(source=t)
    valid = np.asarray(gran.valid)
    got = (np.asarray(gran.x)[valid], np.asarray(gran.d)[valid],
           np.asarray(gran.w)[valid])
    ref_g = reference.granules(*t.weighted_rows())
    assert granules_differ(got, ref_g) == 0

    r = plar_reduce(source=t, delta="SCE", engine="device")
    ref = reference.reduce(*ref_g, delta="SCE", v_max=2)
    assert r.core and r.core == ref["core"]
    assert r.reduct == ref["reduct"] == oracle.reduct_oracle("SCE", t.x, t.d)
    np.testing.assert_allclose(r.theta_full, ref["theta_full"], atol=1e-6)
    np.testing.assert_allclose(r.theta_history, ref["theta_history"],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("delta", ["PR", "SCE", "LCE", "CCE"])
def test_sketch_core_matches_the_closure_it_replaced(delta):
    for n_rows, n_attrs in SHAPES:
        gran = reduction.resolve_granularity(source=gisette(n_rows, n_attrs))
        new = reduction._core_inner_thetas(gran, delta, exact=True)
        old = closure_core(gran, delta)
        # the closure folded |U| into a constant, and XLA divides by a
        # constant as a multiply by its reciprocal: 1 ulp apart where the
        # quotient is not exact; the program now divides by |U|, as Θ(D|C)
        # and the engine do
        np.testing.assert_array_max_ulp(new.astype(np.float32),
                                        old.astype(np.float32), 1)


@pytest.mark.parametrize("delta", ["PR", "SCE"])
def test_sketch_core_equals_an_exact_grouping_per_attribute(monkeypatch,
                                                            delta):
    for n_rows, n_attrs in SHAPES:
        gran = reduction.resolve_granularity(source=gisette(n_rows, n_attrs))
        sketch = reduction._core_inner_thetas(gran, delta, exact=True)
        with monkeypatch.context() as m:
            m.setattr(reduction, "_core_path", lambda exact, n: "exact")
            exact = reduction._core_inner_thetas(gran, delta, exact=True)
        np.testing.assert_allclose(sketch, exact, rtol=1e-5, atol=1e-6)


def test_a_second_table_of_one_shape_compiles_nothing():
    first, second = gisette(500, 300, seed=5), gisette(500, 300, seed=6)
    a = plar_reduce(source=first, delta="SCE", engine="device")
    info = reduction._sketch_core.cache_info()
    compiles = []

    def on_duration(event, duration, **_):
        if event == COMPILE_EVENT:
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        b = plar_reduce(source=second, delta="SCE", engine="device")
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []
    assert reduction._sketch_core.cache_info().misses == info.misses
    run = reduction._sketch_core("SCE", 512, 2, 64, 300)
    assert run._cache_size() == 1
    assert a.core and b.core and a.reduct != b.reduct
