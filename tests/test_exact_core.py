"""The exact core (at most 128 attributes): Θ(D|C\\{a}) for every a from one
prefix scan and one suffix scan, each row's class of C\\{a} the pair of its
prefix and suffix ranks, ranked by a two-key sort.

Random tables with 10-20% padding slots scattered among the live ones.  The
pair ids are bitwise the exact grouping of C\\{a}, the core's Θs are those of
the per-attribute loop it replaced (PR bitwise), and a second table of the
same shape compiles nothing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import measures, reduction
from repro.core.granularity import Granularity, exact_class_ids
from repro.core.plan import contingency_from_ids, subset_ids

CAP, N_DEC = 256, 3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CASES = [(a, v) for a in (1, 2, 5, 41) for v in (2, 10)]


def table(n_attrs, v_max, seed=0):
    """A granularity of ``CAP`` slots, 10-20% of them padding, with
    repeated prefixes and suffixes so that classes of C\\{a} merge rows."""
    rng = np.random.default_rng([seed, n_attrs, v_max])
    protos = rng.integers(0, v_max, size=(CAP // 4, n_attrs))
    x = protos[rng.integers(0, len(protos), size=CAP)]
    hit = rng.random((CAP, n_attrs)) < 0.1
    x = np.where(hit, rng.integers(0, v_max, size=x.shape), x)
    valid = rng.random(CAP) >= rng.uniform(0.1, 0.2)
    w = np.where(valid, rng.integers(1, 50, size=CAP), 0)
    return Granularity(
        x=jnp.asarray(x, jnp.int32),
        d=jnp.asarray(rng.integers(0, N_DEC, size=CAP), jnp.int32),
        w=jnp.asarray(w, jnp.int32), valid=jnp.asarray(valid),
        num=jnp.int32(valid.sum()), n_total=jnp.int32(w.sum()),
        n_attrs=n_attrs, n_dec=N_DEC, v_max=v_max)


def grouping(gran, cols):
    """Exact class ids of the columns ``cols``: the one class of every live
    slot where there are none."""
    if not cols:
        return (jnp.zeros((gran.capacity,), jnp.int32),
                jnp.any(gran.valid).astype(jnp.int32))
    return subset_ids(gran, jnp.asarray(cols, jnp.int32), exact=True)


def loop_core(gran, delta):
    """The exact core as it was: one exact grouping of C\\{a} per attribute,
    each with its contingency, Θ and read-back."""
    A = gran.n_attrs
    out = np.zeros((A,), np.float64)
    for a in range(A):
        ids, _ = grouping(gran, [j for j in range(A) if j != a])
        cont = contingency_from_ids(ids, gran.d, gran.w, gran.valid,
                                    n_bins=gran.capacity, m=gran.n_dec)
        out[a] = float(measures.evaluate(delta, cont, gran.n_total))
    return out


@pytest.mark.parametrize("n_attrs,v_max", CASES)
def test_pair_ids_are_the_exact_grouping_of_each_subset(n_attrs, v_max):
    gran = table(n_attrs, v_max)
    ids, ks = reduction._exact_core_ids(gran.x, gran.valid, v_max)
    assert ids.shape == (n_attrs, CAP) and ids.dtype == jnp.int32
    for a in range(n_attrs):
        cols = [j for j in range(n_attrs) if j != a]
        ref, k = grouping(gran, cols)
        if cols:
            ref_direct, k_direct = exact_class_ids(
                gran.x[:, np.asarray(cols)], gran.valid, radix=v_max)
            np.testing.assert_array_equal(ref, ref_direct)
            assert int(k) == int(k_direct)
        np.testing.assert_array_equal(ids[a], ref, err_msg=f"a={a}")
        assert int(ks[a]) == int(k), a
    if n_attrs > 2:
        # the table merges rows: some subset has fewer classes than slots
        assert int(ks.min()) < int(gran.valid.sum())


@pytest.mark.parametrize("delta", ["PR", "SCE", "LCE", "CCE"])
@pytest.mark.parametrize("n_attrs,v_max", CASES)
def test_core_thetas_equal_the_per_attribute_loop(n_attrs, v_max, delta):
    gran = table(n_attrs, v_max, seed=1)
    full = reduction._core_inner_thetas(gran, delta, exact=True)
    old = loop_core(gran, delta)
    assert full.dtype == np.float64 and full.shape == (n_attrs,)
    if delta == "PR":
        np.testing.assert_array_equal(full, old)
    else:
        ulp = np.spacing(np.float32(np.max(np.abs(old))))
        np.testing.assert_array_less(np.abs(full - old), 4 * ulp + 1e-30)
    # the core is the same set of attributes
    theta_c = float(measures.evaluate(delta, contingency_from_ids(
        grouping(gran, list(range(n_attrs)))[0], gran.d, gran.w, gran.valid,
        n_bins=CAP, m=N_DEC), gran.n_total))
    assert (np.flatnonzero(full > theta_c + 1e-6).tolist()
            == np.flatnonzero(old > theta_c + 1e-6).tolist())


def test_a_second_table_of_one_shape_compiles_nothing():
    first, second = table(41, 10, seed=5), table(41, 10, seed=6)
    a = reduction._core_inner_thetas(first, "SCE", exact=True)
    info = reduction._exact_core.cache_info()
    compiles = []

    def on_duration(event, duration, **_):
        if event == COMPILE_EVENT:
            compiles.append(duration)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        b = reduction._core_inner_thetas(second, "SCE", exact=True)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert compiles == []
    assert reduction._exact_core.cache_info().misses == info.misses
    assert not np.array_equal(a, b)
