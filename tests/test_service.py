"""Online reduct service (DESIGN.md §3.7): state, repair, serving.

The acceptance contract: a dataset created from the first half of a paper
table and streamed the second half in ≥4 update batches ends with the same
reduct as a batch ``plar_reduce`` over the full table, for all four
measures — while every update costs one monoid merge plus a warm-started
repair, never a from-scratch recompute.
"""
import asyncio

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import build_granularity, plar_reduce, with_capacity
from repro.core.measures import f32_threshold
from repro.data import scaled_paper_dataset
from repro.service import (
    DatasetHandle,
    ReductServer,
    granularity_fingerprint,
    repair_reduce,
    valid_prefix_len,
)

DELTAS = ["PR", "SCE", "LCE", "CCE"]


def _table(rng, n, a, vmax=3, m=2, redundancy=0.5):
    x = rng.integers(0, vmax, size=(n, a)).astype(np.int32)
    for j in range(1, a):
        if rng.random() < redundancy:
            x[:, j] = x[:, rng.integers(0, j)]
    d = rng.integers(0, m, size=(n,)).astype(np.int32)
    return x, d


# ---------------------------------------------------------------------------
# DatasetHandle: state + updates + fingerprint
# ---------------------------------------------------------------------------


def test_handle_update_matches_batch_granularity():
    """Half + streamed updates == monolithic build (live prefix and
    fingerprint), and capacity follows the pow2 policy."""
    rng = np.random.default_rng(0)
    x, d = _table(rng, 600, 6, vmax=4, m=3)
    h = DatasetHandle.create(x[:300], d[:300], n_dec=3, v_max=4)
    for lo in range(300, 600, 100):
        h.update(x[lo:lo + 100], d[lo:lo + 100])
    mono = build_granularity(jnp.asarray(x), jnp.asarray(d), n_dec=3, v_max=4)
    num = int(mono.num)
    assert h.n_granules == num
    np.testing.assert_array_equal(np.asarray(h.gran.x)[:num],
                                  np.asarray(mono.x)[:num])
    np.testing.assert_array_equal(np.asarray(h.gran.w)[:num],
                                  np.asarray(mono.w)[:num])
    assert h.gran.capacity == (1 << (num - 1).bit_length())
    assert h.n_updates == 3 and h.rows_absorbed == 600
    assert h.fingerprint == granularity_fingerprint(mono)


def test_fingerprint_content_invariance():
    """Fingerprint is a pure function of live content: invariant to padding
    capacity and build path, sensitive to rows and to multiplicities."""
    rng = np.random.default_rng(1)
    x, d = _table(rng, 200, 5)
    g = build_granularity(jnp.asarray(x), jnp.asarray(d), n_dec=2, v_max=3)
    assert granularity_fingerprint(g) == granularity_fingerprint(
        with_capacity(g, 4 * g.capacity))
    g2 = build_granularity(jnp.asarray(x[:199]), jnp.asarray(d[:199]),
                           n_dec=2, v_max=3)
    assert granularity_fingerprint(g) != granularity_fingerprint(g2)
    # duplicating a row changes only a weight — still a different content
    xd = np.concatenate([x, x[:1]])
    dd = np.concatenate([d, d[:1]])
    g3 = build_granularity(jnp.asarray(xd), jnp.asarray(dd), n_dec=2, v_max=3)
    assert granularity_fingerprint(g) != granularity_fingerprint(g3)


def test_handle_create_and_update_validation():
    rng = np.random.default_rng(2)
    x, d = _table(rng, 100, 4)
    with pytest.raises(ValueError, match="n_dec and v_max"):
        DatasetHandle.create(x, d)
    h = DatasetHandle.create(x, d, n_dec=2, v_max=3)
    with pytest.raises(ValueError, match="attributes"):
        h.update(x[:, :3], d)
    with pytest.raises(ValueError, match="decision shape"):
        h.update(x, d[:-1])
    with pytest.raises(ValueError, match="v_max"):
        h.update(np.full((2, 4), 3, np.int32), np.zeros((2,), np.int32))
    with pytest.raises(ValueError, match="n_dec"):
        h.update(np.zeros((2, 4), np.int32), np.full((2,), 2, np.int32))
    # negative codes would scatter out of segment_sum range downstream —
    # rejected here, before they can corrupt the merged granularity
    with pytest.raises(ValueError, match="v_max"):
        h.update(np.full((2, 4), -1, np.int32), np.zeros((2,), np.int32))
    with pytest.raises(ValueError, match="n_dec"):
        h.update(np.zeros((2, 4), np.int32), np.full((2,), -1, np.int32))
    # empty batch is identity on the granularity
    before = h.fingerprint
    h.update(np.zeros((0, 4), np.int32), np.zeros((0,), np.int32))
    assert h.fingerprint == before


# ---------------------------------------------------------------------------
# repair: validate (fold) → trim → resume
# ---------------------------------------------------------------------------


def test_valid_prefix_len():
    # every fold improves, target unreached → keep all
    assert valid_prefix_len([0.5, 0.3, 0.1], theta_full=0.0) == 3
    # third fold no longer improves beyond tie_tol → trim it and the tail
    assert valid_prefix_len([0.5, 0.3, 0.3, 0.1], theta_full=0.0) == 2
    # stopping target reached mid-prefix → later attributes are redundant
    assert valid_prefix_len([0.5, 0.3, 0.1], theta_full=0.3) == 2
    assert valid_prefix_len([], theta_full=0.0) == 0


def test_repair_is_noop_on_unchanged_data():
    """Full prefix valid + target reached → the probe IS the result: zero
    greedy iterations, byte-identical Θ history."""
    rng = np.random.default_rng(3)
    x, d = _table(rng, 250, 8)
    cold = plar_reduce(x, d, delta="SCE")
    gran = build_granularity(jnp.asarray(x), jnp.asarray(d), n_dec=2, v_max=3)
    r, kept = repair_reduce(gran, cold.reduct, delta="SCE")
    assert kept == len(cold.reduct)
    assert r.reduct == cold.reduct
    assert r.theta_history == cold.theta_history
    assert r.iterations == 0


def test_repair_trims_redundant_prefix():
    """A prefix attribute that no longer improves Θ (a copy of an earlier
    one) is dropped, and the resumed greedy never re-selects it."""
    rng = np.random.default_rng(4)
    x, d = _table(rng, 250, 8, redundancy=0.0)
    x[:, 3] = x[:, 2]  # attr 3 is redundant once 2 is selected
    gran = build_granularity(jnp.asarray(x), jnp.asarray(d), n_dec=2, v_max=3)
    r, kept = repair_reduce(gran, [2, 3], delta="SCE")
    assert kept == 1
    assert r.reduct[0] == 2 and 3 not in r.reduct


@pytest.mark.parametrize("delta", DELTAS)
def test_handle_reduce_warm_matches_cold(delta):
    """After an update, the warm repair and a cold run on the same handle
    agree.  Prefix stability is a property of the data, not a theorem —
    near-ties can legitimately reorder greedy picks — so this uses a paper
    stand-in whose attribute significances are well separated (the regime
    the service targets; see DESIGN.md §3.7 repair semantics)."""
    stream = scaled_paper_dataset("breast-cancer-wisconsin", max_rows=683,
                                  max_attrs=9)
    x, d = stream.table()
    h = DatasetHandle.create(x[:500], d[:500], n_dec=stream.n_dec,
                             v_max=stream.v_max)
    h.reduce(delta)
    h.update(x[500:], d[500:])
    warm = h.reduce(delta)
    assert h.last_was_warm
    cold = h.reduce(delta, warm=False)
    assert warm.reduct == cold.reduct
    assert warm.theta_history == cold.theta_history
    # the repaired reduct is a super-reduct: it reaches the stopping target
    target = f32_threshold(warm.theta_full, 1e-6) + 1e-6
    assert warm.theta_history[-1] <= target


# ---------------------------------------------------------------------------
# end-to-end acceptance: stream a paper dataset through the server
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("delta", DELTAS)
def test_service_streaming_matches_batch(delta):
    """First half creates the dataset, second half streams in 4 update
    batches; the final reduct equals batch ``plar_reduce`` on the full
    table — for all four measures."""
    stream = scaled_paper_dataset("shuttle", max_rows=4000, max_attrs=9)
    x, d = stream.table()
    half = len(x) // 2
    rest = len(x) - half

    async def drive():
        async with ReductServer() as srv:
            await srv.submit("s", x[:half], d[:half],
                             n_dec=stream.n_dec, v_max=stream.v_max)
            r = await srv.query("s", delta=delta)
            for i in range(4):
                lo = half + i * rest // 4
                hi = half + (i + 1) * rest // 4
                await srv.update("s", x[lo:hi], d[lo:hi])
                r = await srv.query("s", delta=delta)
            return r, srv.stats.copy(), list(srv.requests)

    r, stats, reqs = asyncio.run(drive())
    full = plar_reduce(x, d, delta=delta, n_dec=stream.n_dec,
                       v_max=stream.v_max)
    assert r.reduct == full.reduct
    # equal reducts over equal content (same live granules, same pow2
    # capacity) fold the same sequence → byte-identical Θ histories
    assert r.theta_history == full.theta_history
    assert stats["cold"] == 1 and stats["warm"] == 4
    assert stats["merges"] == 4
    assert all(q.warm for q in reqs[1:])


def test_server_coalesces_pending_updates():
    """k buffered update batches drain as ONE merge at the next query."""
    rng = np.random.default_rng(6)
    x, d = _table(rng, 400, 6)

    async def drive():
        async with ReductServer() as srv:
            await srv.submit("c", x[:100], d[:100], n_dec=2, v_max=3)
            await srv.query("c", delta="SCE")
            for lo in (100, 200, 300):
                await srv.update("c", x[lo:lo + 100], d[lo:lo + 100])
            r = await srv.query("c", delta="SCE")
            return r, srv.stats.copy(), srv.handle("c")

    r, stats, handle = asyncio.run(drive())
    assert stats["updates"] == 3
    assert stats["merges"] == 1              # coalesced into one fold
    assert stats["coalesced_batches"] == 3
    assert handle.n_updates == 1             # the handle saw one batch
    assert handle.rows_absorbed == 400
    # the coalesced merge is exact: same reduct as batch over all rows
    full = plar_reduce(x, d, delta="SCE", n_dec=2, v_max=3)
    assert r.reduct == full.reduct


def test_update_is_served_from_the_next_query():
    """An update batch far smaller than the dataset's capacity, which a
    streaming fold would hold pending, is merged at once: the handle's
    granularity counts its new rows at the next query, in one merge."""
    from repro import obs
    from repro.core import Granularity

    rows = np.arange(1024 + 24, dtype=np.int32)
    x = np.stack([rows // 64, rows // 8 % 8, rows % 8], axis=1)
    d = (rows % 2).astype(np.int32)
    deferred = obs.counter("plar_fold_deferred_chunks_total")
    rebuilds = obs.counter("plar_merge_rebuilds_total")

    async def drive():
        async with ReductServer() as srv:
            await srv.submit("u", x[:1024], d[:1024], n_dec=2, v_max=17)
            await srv.query("u", delta="SCE")
            before = srv.handle("u").gran
            counts = deferred.value, rebuilds.value
            await srv.update("u", x[1024:], d[1024:])
            await srv.query("u", delta="SCE")
            return before, srv.handle("u").gran, counts, srv.stats.copy()

    before, after, counts, stats = asyncio.run(drive())
    assert before.capacity == 1024 and int(before.num) == 1024
    assert isinstance(after, Granularity)
    assert int(after.num) == 1024 + 24 and after.capacity == 2048
    assert int(after.n_total) == len(rows)
    assert stats["merges"] == 1
    assert (deferred.value, rebuilds.value) == counts


def test_server_result_cache_and_param_keys():
    """Repeat query on unchanged content is a cache hit; params and content
    changes both miss."""
    rng = np.random.default_rng(7)
    x, d = _table(rng, 300, 6)

    async def drive():
        async with ReductServer() as srv:
            await srv.submit("k", x[:200], d[:200], n_dec=2, v_max=3)
            r1 = await srv.query("k", delta="SCE")
            r2 = await srv.query("k", delta="SCE")          # hit
            r3 = await srv.query("k", delta="SCE", max_features=1)  # params miss
            await srv.update("k", x[200:], d[200:])
            r4 = await srv.query("k", delta="SCE")          # content miss
            return (r1, r2, r3, r4), srv.stats.copy(), list(srv.requests)

    (r1, r2, r3, r4), stats, reqs = asyncio.run(drive())
    assert stats["queries"] == 4 and stats["cache_hits"] == 1
    assert reqs[1].cached and r2 is r1
    assert not reqs[2].cached and r3.reduct != r1.reduct
    assert not reqs[3].cached


def test_server_validation_and_lifecycle():
    rng = np.random.default_rng(8)
    x, d = _table(rng, 100, 4)

    async def drive():
        async with ReductServer() as srv:
            await srv.submit("v", x, d, n_dec=2, v_max=3)
            with pytest.raises(ValueError, match="already exists"):
                await srv.submit("v", x, d, n_dec=2, v_max=3)
            with pytest.raises(KeyError, match="unknown dataset"):
                await srv.query("nope")
            with pytest.raises(KeyError, match="unknown dataset"):
                await srv.update("nope", x, d)
            with pytest.raises(ValueError, match="rows"):
                await srv.update("v", x, d[:-1])
            # errors inside the worker propagate to the awaiting caller
            with pytest.raises(ValueError, match="unknown mode"):
                await srv.query("v", delta="SCE", mode="sprak")
            return await srv.query("v", delta="SCE")

    r = asyncio.run(drive())
    assert r.reduct  # server still serves after a failed request

    async def no_start():
        srv = ReductServer()
        await srv.submit("w", x, d, n_dec=2, v_max=3)  # no queue needed
        with pytest.raises(RuntimeError, match="not started"):
            await srv.query("w")

    asyncio.run(no_start())


def test_server_concurrent_submit_same_name():
    """Concurrent same-name submits: exactly one wins, the other gets the
    documented ValueError (the name is reserved before the build awaits)."""
    rng = np.random.default_rng(9)
    x, d = _table(rng, 120, 4)

    async def drive():
        async with ReductServer() as srv:
            results = await asyncio.gather(
                srv.submit("dup", x[:60], d[:60], n_dec=2, v_max=3),
                srv.submit("dup", x[60:], d[60:], n_dec=2, v_max=3),
                return_exceptions=True)
            errors = [r for r in results if isinstance(r, BaseException)]
            assert len(errors) == 1 and isinstance(errors[0], ValueError)
            assert srv.handle("dup") is not None
            return await srv.query("dup", delta="SCE")

    assert asyncio.run(drive()).reduct is not None
