"""The four significance measures vs the paper-literal numpy oracle."""
import numpy as np
import jax.numpy as jnp
import pytest

from _hyp import given, settings, st  # optional-hypothesis shim: property tests skip on bare envs

from repro.core import measures
from repro.core.oracle import theta_oracle
from repro.core.plan import contingency_from_ids, ids_by_sort

DELTAS = ["PR", "SCE", "LCE", "CCE"]


def _theta_via_decomposition(delta, x, d, cols):
    """Θ(D|B) through the granule/contingency path (paper §3.2)."""
    n = x.shape[0]
    if cols:
        keys = [jnp.asarray(x[:, c]) for c in cols][::-1]
    else:
        keys = [jnp.zeros(n, jnp.int32)]
    valid = jnp.ones(n, bool)
    ids, k = ids_by_sort(keys, valid)
    m = int(d.max()) + 1
    cont = contingency_from_ids(ids, jnp.asarray(d), jnp.ones(n, jnp.int32), valid, n_bins=n, m=m)
    return float(measures.evaluate(delta, cont, jnp.float32(n)))


@pytest.mark.parametrize("delta", DELTAS)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_theta_matches_oracle(delta, seed):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 3, size=(120, 6)).astype(np.int32)
    d = rng.integers(0, 3, size=(120,)).astype(np.int32)
    for cols in [[0], [1, 3], [0, 2, 4], list(range(6))]:
        got = _theta_via_decomposition(delta, x, d, cols)
        want = theta_oracle(delta, x, d, cols)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("delta", DELTAS)
def test_theta_monotone_under_refinement(delta):
    """Adding attributes never increases Θ (all four are anti-monotone).

    This is the rough-set property that makes greedy forward selection sound:
    Θ(D|B∪{a}) ≤ Θ(D|B), i.e. outer significance is non-negative.
    """
    rng = np.random.default_rng(42)
    x = rng.integers(0, 4, size=(200, 7)).astype(np.int32)
    d = rng.integers(0, 2, size=(200,)).astype(np.int32)
    cols: list = []
    prev = _theta_via_decomposition(delta, x, d, cols)
    for a in range(7):
        cols.append(a)
        cur = _theta_via_decomposition(delta, x, d, cols)
        assert cur <= prev + 1e-6, (delta, cols, cur, prev)
        prev = cur


@pytest.mark.parametrize("delta", DELTAS)
def test_theta_consistent_table_reaches_floor(delta):
    """If D is a function of B, Θ(D|B) hits its minimum (PR: -1; entropies: 0)."""
    rng = np.random.default_rng(3)
    x = rng.integers(0, 5, size=(100, 3)).astype(np.int32)
    d = ((x[:, 0] + 2 * x[:, 1]) % 3).astype(np.int32)  # D determined by B
    got = _theta_via_decomposition(delta, x, d, [0, 1])
    if delta == "PR":
        np.testing.assert_allclose(got, -1.0, atol=1e-6)
    else:
        np.testing.assert_allclose(got, 0.0, atol=1e-6)


def test_pr_theta_is_negative_dependency():
    """Θ_PR = -γ_B(D) per the paper's unified sign convention."""
    x = np.array([[0], [0], [1], [1]], np.int32)
    d = np.array([0, 0, 0, 1], np.int32)
    # class {0,0}: pure (2 objects). class {1,1}: impure. γ = 2/4.
    got = _theta_via_decomposition("PR", x, d, [0])
    np.testing.assert_allclose(got, -0.5, atol=1e-7)


def test_paper_example_table3():
    """The paper's running Example 1/3 (Table 3): B={a2}, Δ=PR → γ = 1/8·|..|.

    From Fig. 6: with B={a2} the classes are a2=0 → {Y:3,N:2} (impure) and
    a2=1 → {Y:4} pure wait — recompute from Table 3: a2=0 rows {x1,x2,x3,x7},
    decisions {Y,Y,N,N} impure; a2=1 rows {x4,x5,x6,x8} all Y → pure, 4 objs.
    γ = 4/8, Θ_PR = -0.5.
    """
    x = np.array([[0, 0], [0, 0], [0, 0], [0, 1], [0, 1], [0, 1], [1, 0], [1, 1]], np.int32)
    d = np.array([0, 0, 1, 0, 0, 0, 1, 0], np.int32)
    got = _theta_via_decomposition("PR", x, d, [1])
    np.testing.assert_allclose(got, -0.5, atol=1e-7)


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(5, 80),
    a=st.integers(1, 5),
    vmax=st.integers(2, 4),
    m=st.integers(2, 4),
    delta=st.sampled_from(DELTAS),
    seed=st.integers(0, 2**16),
)
def test_theta_property(n, a, vmax, m, delta, seed):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, vmax, size=(n, a)).astype(np.int32)
    d = rng.integers(0, m, size=(n,)).astype(np.int32)
    cols = list(rng.choice(a, size=rng.integers(1, a + 1), replace=False))
    got = _theta_via_decomposition(delta, x, d, [int(c) for c in cols])
    want = theta_oracle(delta, x, d, [int(c) for c in cols])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("delta", DELTAS)
def test_raw_rows_keepdims_same_values(delta):
    # the [..., K, 1] column form the Pallas epilogues use holds the same
    # bits as the default [..., K] rows
    rng = np.random.default_rng(3)
    cont = jnp.asarray(rng.integers(0, 5, (4, 16, 7)), jnp.float32)
    rows = measures.RAW_ROWS[delta](cont)
    col = measures.RAW_ROWS[delta](cont, keepdims=True)
    assert col.shape == rows.shape + (1,)
    np.testing.assert_array_equal(np.asarray(col[..., 0]), np.asarray(rows))
