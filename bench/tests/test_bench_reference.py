"""The benchmark's generator copy and its plain reference, against the
program at small sizes on the CPU."""
import dataclasses
import types

import numpy as np
import pytest

from bench import gen, reference
from bench.entries.batch import compare, granules_differ

LIMITS = {"granules_differ": 0, "core_differ": 0, "reduct_differ": 0,
          "theta_gap": 1e-5}


def small(name, n_rows, n_attrs):
    from repro.data import paper_dataset

    return dataclasses.replace(paper_dataset(name, seed=0), n_rows=n_rows,
                               n_attrs=n_attrs)


@pytest.mark.parametrize("name,n_rows,n_attrs,seed", [
    ("kdd99", 150_000, 12, 3),
    ("kdd99", 70_000, 41, 2**31 + 11),
    ("gisette", 500, 300, 7),
])
def test_generator_copy_yields_the_programs_rows(name, n_rows, n_attrs, seed):
    stream = dataclasses.replace(small(name, n_rows, n_attrs), seed=seed)
    shape = gen.Shape(n_rows=n_rows, n_attrs=n_attrs, v_max=stream.v_max,
                      n_dec=stream.n_dec,
                      distinct_fraction=stream.distinct_fraction)
    table = gen.Table(shape, seed)
    x, d = stream.table()
    assert np.array_equal(table.x, x) and np.array_equal(table.d, d)
    step = 65536 if n_rows > 65536 else 128
    for i in range(table.n_chunks(step)):
        xc, dc = stream.chunk(i, step)
        tx, td = table.chunk(i, step)
        assert np.array_equal(tx, xc) and np.array_equal(td, dc)


def test_table_seeds_differ_per_table_and_take_large_seeds():
    seeds = {gen.table_seed(2**31 + 5, t) for t in range(8)}
    assert len(seeds) == 8
    assert gen.table_seed(3, 1) == gen.table_seed(3, 1)


def test_weighted_rows_are_the_same_multiset_as_the_rows():
    shape = gen.Shape(n_rows=100_000, n_attrs=9, v_max=6, n_dec=4,
                      distinct_fraction=0.01)
    t = gen.Table(shape, 5)
    a = reference.granules(*t.weighted_rows())
    b = reference.granules(t.x, t.d)
    assert all(np.array_equal(u, v) for u, v in zip(a, b))
    assert int(a[2].sum()) == shape.n_rows


def test_distinct_rows_agree_with_build_granularity():
    import jax.numpy as jnp

    from repro.core.granularity import build_granularity

    shape = gen.Shape(n_rows=4000, n_attrs=7, v_max=3, n_dec=3,
                      distinct_fraction=0.2)
    t = gen.Table(shape, 9)
    g = build_granularity(jnp.asarray(t.x), jnp.asarray(t.d), n_dec=3,
                          v_max=3)
    valid = np.asarray(g.valid)
    got = (np.asarray(g.x)[valid], np.asarray(g.d)[valid],
           np.asarray(g.w)[valid])
    ref = reference.granules(t.x, t.d)
    assert len(ref[2]) == int(g.num)
    assert granules_differ(got, ref) == 0
    # and a changed weight is seen
    bad = (got[0], got[1], got[2] + (np.arange(len(got[2])) == 0))
    assert granules_differ(bad, ref) > 0


@pytest.fixture(scope="module")
def table():
    shape = gen.Shape(n_rows=30_000, n_attrs=12, v_max=5, n_dec=4,
                      distinct_fraction=0.05)
    t = gen.Table(shape, 21)
    return t, reference.granules(*t.weighted_rows())


@pytest.mark.parametrize("delta", ["PR", "SCE", "LCE", "CCE"])
def test_reference_agrees_with_the_program(table, delta):
    from repro.core.reduction import plar_reduce

    t, ref_g = table
    r = plar_reduce(source=t, delta=delta, chunk_rows=8192)
    nums = compare(r, ref_g, ref_g, delta, t.v_max, {})
    assert all(nums[k] <= LIMITS[k] for k in LIMITS), nums


@pytest.mark.parametrize("delta", ["PR", "SCE", "LCE", "CCE"])
def test_the_bfloat16_control_is_not_correct(table, delta):
    """The control: the reference in the program's place, computed in
    bfloat16 with float32 sums.  It has to fail a limit."""
    t, ref_g = table
    ctl_g = ref_g[:2] + (reference._Arith("bfloat16").hold(ref_g[2])
                         .astype(np.int64),)
    ctl = types.SimpleNamespace(**reference.reduce(
        *ctl_g, delta=delta, v_max=t.v_max, precision="bfloat16"))
    nums = compare(ctl, ctl_g, ref_g, delta, t.v_max, {})
    assert any(nums[k] > LIMITS[k] for k in LIMITS), nums


def test_candidate_thetas_by_hand():
    """Four granules, R = one class, candidate column (0, 0, 1, 1):
    classes {g0, g1} with decisions (0, 1) weights (1, 3), and {g2, g3}
    with decisions (1, 1) weights (2, 2).  n = 8."""
    r = np.zeros(4, np.int64)
    cand = np.array([[0], [0], [1], [1]], np.uint8)
    d = np.array([0, 1, 1, 1])
    w = np.array([1, 3, 2, 2])
    ar = reference._Arith("float64")
    got = {delta: reference.candidate_thetas(delta, r, cand, d, w, 8, 2,
                                             ar)[0]
           for delta in ("PR", "SCE", "LCE", "CCE")}
    assert got["PR"] == pytest.approx(-4 / 8)
    assert got["SCE"] == pytest.approx(-(1 * np.log(1 / 4)
                                         + 3 * np.log(3 / 4)) / 8)
    assert got["LCE"] == pytest.approx((1 * 3 + 3 * 1) / 64)
    assert got["CCE"] == pytest.approx(
        (4 * 4 * 3 - 3 * 3 * 2 + 4 * 4 * 3 - 4 * 4 * 3) / (64 * 7))


NEAR = gen.Shape(n_rows=60_000, n_attrs=14, v_max=6, n_dec=5,
                 distinct_fraction=0.05, near_duplicates=0.05,
                 near_duplicate_attrs=2)


def test_near_duplicates_give_the_table_a_core():
    t = gen.Table(NEAR, 2**31 + 7)
    plain = gen.Table(dataclasses.replace(NEAR, near_duplicates=0.0),
                      2**31 + 7)
    # the share asked for is remade, each row in one column
    changed = (t.proto_x != plain.proto_x).sum(axis=1)
    assert (changed > 0).sum() <= NEAR.near_duplicates * NEAR.n_proto
    assert (changed > 0).sum() >= 0.8 * NEAR.near_duplicates * NEAR.n_proto
    g = reference.granules(*t.weighted_rows())
    ref = reference.reduce(*g, delta="SCE", v_max=NEAR.v_max)
    assert len(ref["core"]) >= 1
    assert ref["reduct"][:len(ref["core"])] == ref["core"]


@pytest.mark.parametrize("delta", ["PR", "SCE"])
def test_a_relabelled_table_has_the_same_answer_renamed(delta):
    """Relabelling renames values and classes: the granule counts, core,
    reduct and Θ history are the same."""
    a = gen.Table(NEAR, 11)
    b = gen.Table(NEAR, 11, relabel_seed=2**40 + 3)
    assert not np.array_equal(a.x, b.x)
    assert np.array_equal(a.index, b.index)
    ga = reference.granules(*a.weighted_rows())
    gb = reference.granules(*b.weighted_rows())
    assert np.array_equal(np.sort(ga[2]), np.sort(gb[2]))
    ra = reference.reduce(*ga, delta=delta, v_max=NEAR.v_max)
    rb = reference.reduce(*gb, delta=delta, v_max=NEAR.v_max)
    np.testing.assert_allclose(ra["theta_history"], rb["theta_history"],
                               rtol=1e-12, atol=1e-15)
    assert ra["core"] == rb["core"] and ra["reduct"] == rb["reduct"]


def test_the_program_finds_the_core_the_reference_finds():
    from repro.core.reduction import plar_reduce

    t = gen.Table(NEAR, 2**31 + 7, relabel_seed=9)
    g = reference.granules(*t.weighted_rows())
    r = plar_reduce(source=t, delta="PR", chunk_rows=8192)
    ref = reference.reduce(*g, delta="PR", v_max=NEAR.v_max)
    assert r.core == ref["core"] and len(r.core) >= 1
    nums = compare(r, g, g, "PR", NEAR.v_max, {}, inner=ref["inner"])
    assert nums["core_gap"] == 0.0 and nums["reduct_differ"] == 0
