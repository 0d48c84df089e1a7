"""Streaming GrC ingestion (DESIGN.md §3.6): monoid merge + bit-exact parity.

The contract under test: the decision table never has to exist whole —
granulating row chunks and folding them through ``merge_granularity`` gives
the *same* granularity (live prefix element-wise, any chunk size), the same
capacity after the pow2 shrink, and therefore byte-identical reducts and
Θ histories out of every driver.
"""
import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import (
    build_granularity,
    build_granularity_streaming,
    finish_fold,
    fold_chunk,
    merge_granularity,
    next_pow2,
    plar_reduce,
    fspa_reduce,
    resolve_granularity,
    with_capacity,
)
from repro.data import GranuleSource, TabularStream, paper_dataset, scaled_paper_dataset

DELTAS = ["PR", "SCE", "LCE", "CCE"]


def _live(g):
    num = int(g.num)
    return (np.asarray(g.x)[:num], np.asarray(g.d)[:num], np.asarray(g.w)[:num])


def _assert_same_granularity(a, b):
    """Equal live prefixes (the 'modulo padding' equivalence)."""
    assert int(a.num) == int(b.num)
    assert int(a.n_total) == int(b.n_total)
    for ga, gb in zip(_live(a), _live(b)):
        np.testing.assert_array_equal(ga, gb)


def _chunk_grans(x, d, sizes, v_max, n_dec):
    out = []
    lo = 0
    for s in sizes:
        out.append(build_granularity(
            jnp.asarray(x[lo:lo + s]), jnp.asarray(d[lo:lo + s]),
            n_dec=n_dec, v_max=v_max))
        lo += s
    assert lo == len(x)
    return out


def test_merge_monoid_associativity():
    """(a ⊕ b) ⊕ c == a ⊕ (b ⊕ c) == monolithic, up to padding."""
    rng = np.random.default_rng(0)
    x = rng.integers(0, 4, size=(300, 5)).astype(np.int32)
    d = rng.integers(0, 3, size=(300,)).astype(np.int32)
    a, b, c = _chunk_grans(x, d, [120, 97, 83], v_max=4, n_dec=3)
    left = merge_granularity(merge_granularity(a, b), c)
    right = merge_granularity(a, merge_granularity(b, c))
    mono = build_granularity(jnp.asarray(x), jnp.asarray(d), n_dec=3, v_max=4)
    _assert_same_granularity(left, right)
    _assert_same_granularity(left, mono)
    # commutativity rides along: the merged sort order ignores operand order
    _assert_same_granularity(merge_granularity(c, a), merge_granularity(a, c))


def test_merge_rejects_mismatched_metadata():
    rng = np.random.default_rng(1)
    x = rng.integers(0, 3, size=(50, 4)).astype(np.int32)
    d = rng.integers(0, 2, size=(50,)).astype(np.int32)
    a = build_granularity(jnp.asarray(x), jnp.asarray(d), n_dec=2, v_max=3)
    b = build_granularity(jnp.asarray(x), jnp.asarray(d), n_dec=2, v_max=4)
    with pytest.raises(ValueError, match="metadata"):
        merge_granularity(a, b)


@pytest.mark.parametrize("chunk_rows", [7, 64, 4096])
def test_streaming_build_chunk_size_invariant(chunk_rows):
    """Any chunking → identical Granularity modulo padding (and identical
    live *order*: the final merge re-sorts the full distinct-key set)."""
    t = TabularStream(n_rows=5000, n_attrs=10, v_max=4, n_dec=3,
                      distinct_fraction=0.1, seed=3)
    x, d = t.table()
    mono = build_granularity(jnp.asarray(x), jnp.asarray(d), n_dec=3, v_max=4)
    stream = build_granularity_streaming(t.chunks(chunk_rows), n_dec=3, v_max=4)
    _assert_same_granularity(stream, mono)


def test_capacity_doubling_growth():
    """Merging two full-to-capacity disjoint tables doubles the capacity;
    a fold over all-distinct rows keeps doubling as the live set grows."""
    x = np.arange(128, dtype=np.int32).reshape(128, 1) % 127
    x = np.stack([np.arange(128, dtype=np.int32), x[:, 0]], axis=1)
    d = np.zeros((128,), np.int32)
    a = build_granularity(jnp.asarray(x[:64]), jnp.asarray(d[:64]), n_dec=1, v_max=128)
    b = build_granularity(jnp.asarray(x[64:]), jnp.asarray(d[64:]), n_dec=1, v_max=128)
    assert a.capacity == b.capacity == 64
    m = merge_granularity(a, b)
    assert m.capacity == 128 and int(m.num) == 128

    # streaming fold over fully-distinct rows: capacity tracks next_pow2(seen)
    t = TabularStream(n_rows=1000, n_attrs=6, v_max=8, n_dec=2,
                      distinct_fraction=1.0, redundancy=0.0, seed=9)
    g = build_granularity_streaming(t.chunks(16), n_dec=2, v_max=8)
    assert g.capacity >= int(g.num)
    assert g.capacity <= 2 * int(g.num)  # pow2 policy: never more than 2× live


def test_fold_empty_chunk_is_identity():
    """An empty row chunk folds to the accumulator itself — the monoid
    identity — and an all-empty stream raises instead of returning nothing."""
    rng = np.random.default_rng(12)
    x = rng.integers(0, 3, size=(80, 4)).astype(np.int32)
    d = rng.integers(0, 2, size=(80,)).astype(np.int32)
    g = build_granularity(jnp.asarray(x), jnp.asarray(d), n_dec=2, v_max=3)
    empty_x = np.zeros((0, 4), np.int32)
    empty_d = np.zeros((0,), np.int32)
    assert fold_chunk(g, empty_x, empty_d, n_dec=2, v_max=3) is g
    assert fold_chunk(None, empty_x, empty_d, n_dec=2, v_max=3) is None
    # empty chunks interleaved in a stream do not disturb the fold
    chunks = [(x[:40], d[:40]), (empty_x, empty_d), (x[40:], d[40:])]
    _assert_same_granularity(
        build_granularity_streaming(iter(chunks), n_dec=2, v_max=3), g)
    with pytest.raises(ValueError, match="no non-empty chunks"):
        build_granularity_streaming(iter([(empty_x, empty_d)]), n_dec=2,
                                    v_max=3)


def _keyed_rows(keys):
    """Rows whose key ``k`` is the pair ``(k // 16, k % 16)``, class k mod 3."""
    keys = np.asarray(keys, np.int32)
    return np.stack([keys // 16, keys % 16], axis=1), keys % 3


# (keys, chunk_rows, chunk tables absorbed by each flush).  The run merges
# once its capacity reaches the accumulator's: an all-distinct table
# doubles the accumulator, so its runs double too; a table of 64 keys
# saturates it at 64, four 16-row chunks a flush; an accumulator no larger
# than a chunk's table merges every chunk; one chunk merges nothing.  The
# last chunk is short wherever chunk_rows does not divide the rows.
CADENCE = {
    "distinct-1-2-4": (np.arange(117), 16, [1, 2, 4]),
    "saturating-4s": (np.arange(16 * 14 + 3) * 7 % 64, 16, [1, 2, 4, 4, 3]),
    "every-chunk": (np.arange(100) * 7 % 4, 16, [1] * 6),
    "one-chunk": (np.arange(40), 64, []),
}


@pytest.mark.parametrize("case", list(CADENCE))
def test_fold_merges_a_run_per_accumulator_of_chunks(case):
    """Each flush merges the chunk tables pending since the last one, once
    their capacity reaches the accumulator's, in a merge that never
    overflows; the result is the monolithic build's, at capacity
    ``next_pow2(num)``, and an empty chunk leaves a pending fold as it is."""
    keys, chunk_rows, flushes = CADENCE[case]
    x, d = _keyed_rows(keys)
    kw = dict(n_dec=3, v_max=16)
    tracer = obs.get_tracer()
    was = tracer.enabled
    tracer.enable()
    tracer.clear()
    rebuilds = obs.counter("plar_merge_rebuilds_total")
    deferred = obs.counter("plar_fold_deferred_chunks_total")
    before = rebuilds.value, deferred.value
    empty = (np.zeros((0, 2), np.int32), np.zeros((0,), np.int32))
    try:
        acc = None
        for lo in range(0, len(keys), chunk_rows):
            acc = fold_chunk(acc, x[lo:lo + chunk_rows], d[lo:lo + chunk_rows],
                             **kw)
            assert fold_chunk(acc, *empty, **kw) is acc
        g = finish_fold(acc)
        recs = [r for r in tracer.records() if r.ph == "X"]
    finally:
        tracer.clear()
        tracer.enabled = was
    mono = build_granularity(jnp.asarray(x), jnp.asarray(d), **kw)
    _assert_same_granularity(g, mono)
    assert g.capacity == next_pow2(int(g.num))
    merges = [r for r in recs if r.name == "ingest.merge"]
    assert [m.args["chunks"] for m in merges] == flushes
    folds = [r for r in recs if r.name == "pipeline.fold_chunk"]
    for m in merges:
        assert any(f.t_start <= m.t_start
                   and m.t_start + m.dur <= f.t_start + f.dur for f in folds)
    assert rebuilds.value == before[0]
    assert deferred.value - before[1] == sum(k - 1 for k in flushes)


def test_merge_with_self_doubles_weights():
    """g ⊕ g: same granules (count and representatives), doubled
    multiplicities and |U| — weights merge additively, keys set-merge."""
    rng = np.random.default_rng(13)
    x = rng.integers(0, 4, size=(200, 5)).astype(np.int32)
    d = rng.integers(0, 3, size=(200,)).astype(np.int32)
    g = build_granularity(jnp.asarray(x), jnp.asarray(d), n_dec=3, v_max=4)
    m = merge_granularity(g, g)
    num = int(g.num)
    assert int(m.num) == num                      # granule count preserved
    assert int(m.n_total) == 2 * int(g.n_total)
    np.testing.assert_array_equal(np.asarray(m.x)[:num], np.asarray(g.x)[:num])
    np.testing.assert_array_equal(np.asarray(m.d)[:num], np.asarray(g.d)[:num])
    np.testing.assert_array_equal(np.asarray(m.w)[:num],
                                  2 * np.asarray(g.w)[:num])
    assert int(np.asarray(m.w)[num:].sum()) == 0  # padding stays zero-weight


def test_with_capacity_guard():
    rng = np.random.default_rng(4)
    x = rng.integers(0, 3, size=(100, 4)).astype(np.int32)
    d = rng.integers(0, 2, size=(100,)).astype(np.int32)
    g = build_granularity(jnp.asarray(x), jnp.asarray(d), n_dec=2, v_max=3)
    grown = with_capacity(g, 256)
    assert grown.capacity == 256 and int(grown.w[int(g.num):].sum()) == 0
    _assert_same_granularity(grown, g)
    with pytest.raises(ValueError, match="capacity"):
        with_capacity(g, int(g.num) // 2)


# The acceptance matrix: ≥4 paper datasets × 4 measures, chunk_rows=4096,
# byte-identical reduct / core / Θ history between source= and (x, d).
PARITY_DATASETS = ["mushroom", "shuttle", "kdd99", "weka15360"]


@pytest.mark.parametrize("delta", DELTAS)
@pytest.mark.parametrize("name", PARITY_DATASETS)
def test_streaming_reduction_bit_parity(name, delta):
    t = scaled_paper_dataset(name, max_rows=6000, max_attrs=16)
    assert t.n_rows > 4096  # ≥2 chunks, or the test proves nothing
    x, d = t.table()
    # pin n_dec/v_max to the stream's declared metadata: the array adapter
    # would otherwise infer them from realized data, and a seed where some
    # class never materializes would change n_bins and break byte parity
    mono = plar_reduce(x, d, delta=delta, n_dec=t.n_dec, v_max=t.v_max)
    stream = plar_reduce(source=t, chunk_rows=4096, delta=delta)
    assert stream.reduct == mono.reduct
    assert stream.core == mono.core
    assert stream.theta_full == mono.theta_full        # byte-identical f32
    assert stream.theta_history == mono.theta_history  # byte-identical f32


def test_prebuilt_granularity_source():
    t = scaled_paper_dataset("mushroom", max_rows=3000, max_attrs=12)
    x, d = t.table()
    g = build_granularity(jnp.asarray(x), jnp.asarray(d),
                          n_dec=t.n_dec, v_max=t.v_max)
    a = plar_reduce(x, d, delta="SCE")
    b = plar_reduce(source=g, delta="SCE")
    assert a.reduct == b.reduct and a.theta_history == b.theta_history


def test_source_materializes_for_raw_baselines():
    """grc_init=False (HAR/FSPA cost model) can't stream — the thin adapter
    materializes the chunks and the reduct matches the array path."""
    t = TabularStream(n_rows=900, n_attrs=6, v_max=3, n_dec=2,
                      distinct_fraction=0.3, seed=7)
    x, d = t.table()
    assert fspa_reduce(source=t, chunk_rows=128, delta="SCE").reduct == \
        fspa_reduce(x, d, delta="SCE").reduct


def test_resolve_granularity_validation():
    t = TabularStream(n_rows=100, n_attrs=4, seed=0)
    x, d = t.table()
    with pytest.raises(ValueError, match="not both"):
        resolve_granularity(x, d, source=t)
    with pytest.raises(ValueError, match="source="):
        resolve_granularity()
    with pytest.raises(TypeError, match="GranuleSource"):
        resolve_granularity(source=object())


def test_tabular_stream_is_granule_source():
    t = TabularStream(n_rows=100, n_attrs=4, seed=0)
    assert isinstance(t, GranuleSource)  # runtime attr/method check


def test_tabular_chunks_partition_table():
    """chunk(step) is pure in (seed, step) and chunk-size invariant."""
    t = TabularStream(n_rows=2500, n_attrs=5, distinct_fraction=0.2, seed=11)
    x, d = t.table()
    for cr in (7, 100, 4096):
        xs, ds = zip(*t.chunks(cr))
        np.testing.assert_array_equal(np.concatenate(xs), x)
        np.testing.assert_array_equal(np.concatenate(ds), d)
    x0a, _ = t.chunk(2, 100)
    x0b, _ = t.chunk(2, 100)
    np.testing.assert_array_equal(x0a, x0b)
    with pytest.raises(IndexError):
        t.chunk(t.n_chunks(100), 100)


def test_tabular_shard_partitions_chunk():
    """TokenStream's elastic contract, closed for TabularStream."""
    t = TabularStream(n_rows=2000, n_attrs=5, distinct_fraction=0.5, seed=13)
    full_x, full_d = t.chunk(0, 1024)
    for n_shards in (2, 3, 8):
        xs, ds = zip(*(t.shard(0, i, n_shards, 1024) for i in range(n_shards)))
        np.testing.assert_array_equal(np.concatenate(xs), full_x)
        np.testing.assert_array_equal(np.concatenate(ds), full_d)


def test_paper_dataset_unknown_name_lists_valid():
    with pytest.raises(ValueError, match="kdd99"):
        paper_dataset("no-such-dataset")
    with pytest.raises(ValueError, match="mushroom"):
        scaled_paper_dataset("also-missing")
