"""Property-test hardening pass (ISSUE 6 satellite): algebraic laws the
drivers rely on, checked over randomized inputs.

* ``merge_granularity`` is a monoid up to padding: associative, commutative,
  and any chunking of a table folds to the monolithic build (the §3.6
  streaming-ingestion correctness argument).
* ``DatasetHandle`` fingerprints are a pure function of content: invariant
  to row order and to how rows are split across create/update batches.

Each law lives in a plain checker function driven twice: by a deterministic
pinned test (runs on bare envs) and by a hypothesis ``@given`` test (skips
without hypothesis — see ``_hyp.py``), so the invariants are always
exercised and CI additionally explores the input space.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from _hyp import given, settings, st  # optional-hypothesis shim: property tests skip on bare envs

from repro.core import (
    build_granularity, finish_fold, fold_chunk, merge_granularity)
from repro.service import DatasetHandle, granularity_fingerprint


@pytest.fixture(scope="module", autouse=True)
def _free_compile_state():
    """Randomized shapes compile one executable per distinct (n, a) — drop
    them when the module finishes so long full-suite runs don't accumulate
    compile state (see test_ensemble.py's twin fixture)."""
    yield
    import jax

    jax.clear_caches()


def _table(rng, n, a, vmax, m):
    x = rng.integers(0, vmax, size=(n, a)).astype(np.int32)
    d = rng.integers(0, m, size=(n,)).astype(np.int32)
    return x, d


def _assert_same_content(ga, gb):
    """Equal up to padding: same live prefix (the merge emits it globally
    sorted, so prefix equality is well-defined) and same fingerprint."""
    na, nb = int(ga.num), int(gb.num)
    assert na == nb
    assert int(ga.n_total) == int(gb.n_total)
    np.testing.assert_array_equal(np.asarray(ga.x)[:na], np.asarray(gb.x)[:na])
    np.testing.assert_array_equal(np.asarray(ga.d)[:na], np.asarray(gb.d)[:na])
    np.testing.assert_array_equal(np.asarray(ga.w)[:na], np.asarray(gb.w)[:na])
    assert granularity_fingerprint(ga) == granularity_fingerprint(gb)


# ---------------------------------------------------------------------------
# merge_granularity is a monoid (up to padding)
# ---------------------------------------------------------------------------


def _check_merge_monoid(n, a, vmax, m, cut1, cut2, seed):
    rng = np.random.default_rng(seed)
    x, d = _table(rng, n, a, vmax, m)
    i, j = sorted((cut1 % (n + 1), cut2 % (n + 1)))
    parts = [(x[:i], d[:i]), (x[i:j], d[i:j]), (x[j:], d[j:])]
    kw = dict(n_dec=m, v_max=vmax)
    mono = build_granularity(jnp.asarray(x), jnp.asarray(d), **kw)

    # any chunking folds to the monolithic build (empty chunks included:
    # fold_chunk skips them, the identity element of the fold)
    acc = None
    for xc, dc in parts:
        acc = fold_chunk(acc, jnp.asarray(xc), jnp.asarray(dc), **kw)
    _assert_same_content(finish_fold(acc), mono)

    gs = [build_granularity(jnp.asarray(xc), jnp.asarray(dc), **kw)
          for xc, dc in parts if len(xc)]
    if len(gs) == 3:
        g1, g2, g3 = gs
        left = merge_granularity(merge_granularity(g1, g2), g3)
        right = merge_granularity(g1, merge_granularity(g2, g3))
        _assert_same_content(left, right)           # associativity
        _assert_same_content(left, mono)
    if len(gs) >= 2:
        _assert_same_content(merge_granularity(gs[0], gs[1]),
                             merge_granularity(gs[1], gs[0]))  # commutativity


@pytest.mark.parametrize("n,cut1,cut2,seed", [
    (120, 40, 80, 0),
    (97, 0, 97, 1),      # degenerate cuts: empty first and last chunk
    (50, 13, 13, 2),     # empty middle chunk
    (3, 1, 2, 3),        # single-row chunks
])
def test_merge_monoid_pinned(n, cut1, cut2, seed):
    _check_merge_monoid(n, 5, 4, 3, cut1, cut2, seed)


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 150),
    a=st.integers(1, 6),
    vmax=st.integers(1, 5),
    m=st.integers(1, 3),
    cut1=st.integers(0, 150),
    cut2=st.integers(0, 150),
    seed=st.integers(0, 2**16),
)
def test_merge_monoid_property(n, a, vmax, m, cut1, cut2, seed):
    _check_merge_monoid(n, a, vmax, m, cut1, cut2, seed)


# ---------------------------------------------------------------------------
# DatasetHandle fingerprint: pure function of content
# ---------------------------------------------------------------------------


def _check_fingerprint_invariance(n, a, vmax, m, cut_a, cut_b, seed):
    rng = np.random.default_rng(seed)
    x, d = _table(rng, n, a, vmax, m)
    perm = rng.permutation(n)
    i = 1 + cut_a % (n - 1) if n > 1 else 1
    j = 1 + cut_b % (n - 1) if n > 1 else 1

    def handle(xs, ds, cut):
        h = DatasetHandle.create(xs[:cut], ds[:cut], n_dec=m, v_max=vmax)
        if cut < len(xs):
            h.update(xs[cut:], ds[cut:])
        return h

    h1 = handle(x, d, i)
    h2 = handle(x[perm], d[perm], j)    # permuted rows, different batching
    assert h1.fingerprint == h2.fingerprint
    assert h1.n_granules == h2.n_granules

    # sensitivity: dropping a row (when that changes the content multiset)
    # must change the fingerprint
    if n > 1:
        h3 = handle(x[:-1], d[:-1], min(i, n - 1))
        same_content = any(
            np.array_equal(x[k], x[-1]) and d[k] == d[-1]
            for k in range(n - 1))
        if not same_content:
            assert h1.fingerprint != h3.fingerprint


@pytest.mark.parametrize("n,cut_a,cut_b,seed", [
    (200, 100, 37, 0),
    (2, 1, 1, 1),
    (64, 63, 1, 2),
])
def test_fingerprint_invariance_pinned(n, cut_a, cut_b, seed):
    _check_fingerprint_invariance(n, 5, 4, 3, cut_a, cut_b, seed)


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 120),
    a=st.integers(1, 6),
    vmax=st.integers(1, 4),
    m=st.integers(1, 3),
    cut_a=st.integers(0, 120),
    cut_b=st.integers(0, 120),
    seed=st.integers(0, 2**16),
)
def test_fingerprint_invariance_property(n, a, vmax, m, cut_a, cut_b, seed):
    _check_fingerprint_invariance(n, a, vmax, m, cut_a, cut_b, seed)
