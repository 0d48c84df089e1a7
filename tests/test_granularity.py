"""GrC initialization: granularity build, coarsening, id packing/compaction."""
import numpy as np
import jax.numpy as jnp
import pytest

from _hyp import given, settings, st  # optional-hypothesis shim: property tests skip on bare envs

from repro.core import (
    build_granularity,
    compact_ids,
    pack_ids,
    regranulate,
    row_fingerprints,
)
from repro.core.granularity import column_terms


def _np_granules(x, d):
    rows = np.concatenate([x, d[:, None]], axis=1)
    uniq, counts = np.unique(rows, axis=0, return_counts=True)
    return uniq, counts


@pytest.mark.parametrize("exact", [True, False])
@pytest.mark.parametrize("seed", [0, 1])
def test_build_matches_numpy_unique(exact, seed):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 3, size=(300, 5)).astype(np.int32)
    d = rng.integers(0, 2, size=(300,)).astype(np.int32)
    g = build_granularity(jnp.asarray(x), jnp.asarray(d), n_dec=2, v_max=3, exact=exact)
    uniq, counts = _np_granules(x, d)
    assert int(g.num) == len(uniq)
    assert int(g.n_total) == 300
    got = np.concatenate(
        [np.asarray(g.x)[: int(g.num)], np.asarray(g.d)[: int(g.num), None]], axis=1
    )
    got_w = np.asarray(g.w)[: int(g.num)]
    # order-insensitive comparison
    order_got = np.lexsort(got.T[::-1])
    order_want = np.lexsort(uniq.T[::-1])
    np.testing.assert_array_equal(got[order_got], uniq[order_want])
    np.testing.assert_array_equal(got_w[order_got], counts[order_want])
    # padding slots carry zero weight
    assert np.all(np.asarray(g.w)[int(g.num):] == 0)


def test_paper_example1_table4():
    """Paper Example 1: Table 3 → Table 4 granularity representation."""
    x = np.array([[0, 0], [0, 0], [0, 0], [0, 1], [0, 1], [0, 1], [1, 0], [1, 1]], np.int32)
    d = np.array([0, 0, 1, 0, 0, 0, 1, 0], np.int32)  # Y=0, N=1
    g = build_granularity(jnp.asarray(x), jnp.asarray(d), n_dec=2, v_max=2)
    assert int(g.num) == 5                       # Table 4 has 5 granules
    assert int(g.n_total) == 8
    rows = {
        tuple(np.asarray(g.x)[i].tolist()) + (int(np.asarray(g.d)[i]),): int(np.asarray(g.w)[i])
        for i in range(5)
    }
    assert rows == {(0, 0, 0): 2, (0, 0, 1): 1, (0, 1, 0): 3, (1, 0, 1): 1, (1, 1, 0): 1}


def test_coarsening_merges_counts():
    """Corollary 3.3: G^(P) from G^(Q), P ⊆ Q — counts merge additively."""
    rng = np.random.default_rng(2)
    x = rng.integers(0, 3, size=(200, 4)).astype(np.int32)
    d = rng.integers(0, 2, size=(200,)).astype(np.int32)
    g_full = build_granularity(jnp.asarray(x), jnp.asarray(d), n_dec=2, v_max=3)
    g_p = regranulate(g_full, jnp.asarray([0, 2], jnp.int32))
    uniq, counts = _np_granules(x[:, [0, 2]], d)
    assert int(g_p.num) == len(uniq)
    assert int(np.asarray(g_p.w).sum()) == 200


def test_fingerprint_linearity():
    """h(row) = Σ_j term_j — removing a column is subtraction (linear sketch)."""
    rng = np.random.default_rng(4)
    x = rng.integers(0, 100, size=(50, 6)).astype(np.int32)
    h = row_fingerprints(jnp.asarray(x), 0)
    acc = jnp.zeros((50,), jnp.uint32)
    for j in range(6):
        acc = acc + column_terms(jnp.asarray(x[:, j]), j, 6, 0)
    np.testing.assert_array_equal(np.asarray(h), np.asarray(acc))
    # drop column 3 by subtraction == fingerprint of the subtable
    h_drop = h - column_terms(jnp.asarray(x[:, 3]), 3, 6, 0)
    cols = [0, 1, 2, 4, 5]
    # note: column seeds depend on (index, n_cols); rebuild with same seeds
    manual = jnp.zeros((50,), jnp.uint32)
    for j in cols:
        manual = manual + column_terms(jnp.asarray(x[:, j]), j, 6, 0)
    np.testing.assert_array_equal(np.asarray(h_drop), np.asarray(manual))


def test_pack_compact_roundtrip():
    """pack_ids refines exactly; compact_ids renumbers densely and stably."""
    rng = np.random.default_rng(5)
    n, v = 100, 4
    r = rng.integers(0, 7, size=(n,)).astype(np.int32)
    col = rng.integers(0, v, size=(n,)).astype(np.int32)
    valid = jnp.asarray(rng.random(n) > 0.1)
    packed = pack_ids(jnp.asarray(r), jnp.asarray(col), v)
    new_ids, k_new, presence = compact_ids(packed, valid, 7 * v)
    pairs = {(int(a), int(b)) for a, b, ok in zip(r, col, np.asarray(valid)) if ok}
    assert int(k_new) == len(pairs)
    # same (r, col) pair ⇒ same new id; different ⇒ different
    seen = {}
    for i in range(n):
        if not bool(np.asarray(valid)[i]):
            continue
        key = (int(r[i]), int(col[i]))
        nid = int(np.asarray(new_ids)[i])
        assert seen.setdefault(key, nid) == nid
    assert len(set(seen.values())) == len(pairs)


def test_compact_ids_commute_with_merge():
    """Presence bitmaps OR/psum across shards ⇒ identical global numbering.

    Simulates two data shards: merging bitmaps then ranking equals ranking
    the concatenated data — the property that lets distributed PLAR renumber
    without a gather (DESIGN.md §3.1).
    """
    rng = np.random.default_rng(6)
    from repro.core.granularity import ids_from_presence, presence_bitmap

    n_bins = 40
    p1 = jnp.asarray(rng.integers(0, n_bins, size=(60,)).astype(np.int32))
    p2 = jnp.asarray(rng.integers(0, n_bins, size=(60,)).astype(np.int32))
    v1 = jnp.ones((60,), bool)
    v2 = jnp.ones((60,), bool)
    bm = presence_bitmap(p1, v1, n_bins) + presence_bitmap(p2, v2, n_bins)  # "psum"
    ids1, k1 = ids_from_presence(bm, p1, v1)
    ids2, k2 = ids_from_presence(bm, p2, v2)
    both = jnp.concatenate([p1, p2])
    idsb, kb = ids_from_presence(presence_bitmap(both, jnp.ones((120,), bool), n_bins), both, jnp.ones((120,), bool))
    assert int(k1) == int(kb) == int(k2)
    np.testing.assert_array_equal(np.asarray(idsb[:60]), np.asarray(ids1))
    np.testing.assert_array_equal(np.asarray(idsb[60:]), np.asarray(ids2))


def test_distributed_merge_equals_global_build():
    """Per-shard granulation + weighted re-granulation == global granulation."""
    rng = np.random.default_rng(7)
    x = rng.integers(0, 3, size=(400, 4)).astype(np.int32)
    d = rng.integers(0, 2, size=(400,)).astype(np.int32)
    g_all = build_granularity(jnp.asarray(x), jnp.asarray(d), n_dec=2, v_max=3)

    shards = [build_granularity(jnp.asarray(x[i::2]), jnp.asarray(d[i::2]), n_dec=2, v_max=3) for i in range(2)]
    xs = jnp.concatenate([s.x for s in shards])
    ds = jnp.concatenate([s.d for s in shards])
    ws = jnp.concatenate([s.w for s in shards])
    vs = jnp.concatenate([s.valid for s in shards])
    merged = build_granularity(xs, ds, n_dec=2, v_max=3, w=ws, valid=vs)
    assert int(merged.num) == int(g_all.num)
    assert int(merged.n_total) == int(g_all.n_total) == 400


@settings(max_examples=30, deadline=None)
@given(
    n=st.integers(1, 200),
    a=st.integers(1, 6),
    vmax=st.integers(1, 5),
    seed=st.integers(0, 2**16),
)
def test_granularity_property(n, a, vmax, seed):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, vmax, size=(n, a)).astype(np.int32)
    d = rng.integers(0, 2, size=(n,)).astype(np.int32)
    g = build_granularity(jnp.asarray(x), jnp.asarray(d), n_dec=2, v_max=vmax)
    uniq, counts = _np_granules(x, d)
    assert int(g.num) == len(uniq)
    assert int(np.asarray(g.w).sum()) == n


@pytest.mark.parametrize("seed", [0, 1])
def test_exact_class_ids_number_like_lexsort(seed):
    # sort-free exact grouping numbers classes exactly as a lexsort with
    # column 0 as the primary key does, invalid rows at id 0
    from repro.core.granularity import exact_class_ids
    from repro.core.plan import ids_by_sort

    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.integers(0, 4, (300, 5)), jnp.int32)
    valid = jnp.asarray(rng.random(300) < 0.9)
    ids, k = exact_class_ids(x, valid, radix=4)
    ref_ids, ref_k = ids_by_sort([x[:, j] for j in range(5)][::-1], valid)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ref_ids))
    assert int(k) == int(ref_k)
