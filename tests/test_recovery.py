"""Resilience layer (DESIGN.md §3.10): lineage recovery, durable
checkpoints, fault injection, and the hardened serving path.

The acceptance contract: a build that loses a shard and recovers it by
re-folding ONLY that shard's lineage is **bitwise identical** to the
unfailed build — granularity arrays, fingerprint, and downstream reducts
and Θ histories across ≥3 measures; a killed-and-restarted server restores
its handles from the checkpoint and answers its first query warm.
"""
import asyncio
import os

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import (
    build_granularity,
    merge_granularity,
    next_pow2,
    plar_reduce,
    with_capacity,
)
from repro.core.recovery import (
    ChunkSlice,
    ShardLineage,
    build_sharded,
    merge_shards,
    recover,
    refold_shard,
)
from repro.data import TabularStream
from repro.service import (
    CheckpointCorrupt,
    DatasetHandle,
    FaultInjected,
    FaultPlan,
    FaultSpec,
    QueryPoisoned,
    ReductServer,
    RetryPolicy,
    ServerStopped,
    ServiceCheckpointer,
    ShardLost,
    granularity_fingerprint,
    repair_reduce,
)
from repro.train.checkpoint import CheckpointManager

PARITY_DELTAS = ["PR", "SCE", "LCE"]


def _stream(n=900, a=8, seed=0):
    return TabularStream(n_rows=n, n_attrs=a, v_max=3, n_dec=2,
                         distinct_fraction=0.3, seed=seed)


def _gran_equal(g1, g2):
    """Bitwise equality of the live prefix + static metadata."""
    n1, n2 = int(g1.num), int(g2.num)
    assert n1 == n2
    np.testing.assert_array_equal(np.asarray(g1.x)[:n1], np.asarray(g2.x)[:n1])
    np.testing.assert_array_equal(np.asarray(g1.d)[:n1], np.asarray(g2.d)[:n1])
    np.testing.assert_array_equal(np.asarray(g1.w)[:n1], np.asarray(g2.w)[:n1])
    assert int(g1.n_total) == int(g2.n_total)
    assert granularity_fingerprint(g1) == granularity_fingerprint(g2)


# ---------------------------------------------------------------------------
# shard lineage + re-fold recovery
# ---------------------------------------------------------------------------


def test_refold_shard_bitwise_identical():
    """Replaying one shard's lineage reproduces its granularity exactly."""
    src = _stream()
    build = build_sharded(src, 4, chunk_rows=256)
    assert build.n_shards == 4 and not build.lost
    for s in range(4):
        lin = build.lineages[s]
        assert lin.shard_index == s and lin.slices
        _gran_equal(refold_shard(src, lin), build.shards[s])


def _merge_every_chunk(chunks, n_dec, v_max):
    """The fold merging each chunk's table into the accumulator on arrival,
    the cadence before runs of chunk tables were merged together."""
    acc = None
    for xc, dc in chunks:
        g = build_granularity(jnp.asarray(xc), jnp.asarray(dc), n_dec=n_dec,
                              v_max=v_max, capacity=next_pow2(len(xc)))
        g = with_capacity(g, next_pow2(int(g.num)))
        if acc is not None:
            g = merge_granularity(acc, g)
            g = with_capacity(g, next_pow2(int(g.num)))
        acc = g
    return acc


def _assert_bitwise(a, b):
    assert a.capacity == b.capacity
    for field in ("x", "d", "w", "valid", "num", "n_total"):
        np.testing.assert_array_equal(np.asarray(getattr(a, field)),
                                      np.asarray(getattr(b, field)), field)


def test_shard_folds_match_a_merge_per_chunk():
    """Each shard's fold, built and re-folded from its lineage, holds runs
    of chunk slices before it merges them, and its granules are bitwise
    those of a fold that merges every slice on arrival."""
    from repro import obs

    src = TabularStream(n_rows=2000, n_attrs=6, v_max=3, n_dec=2,
                        distinct_fraction=0.3, seed=4)
    deferred = obs.counter("plar_fold_deferred_chunks_total")
    before = deferred.value
    build = build_sharded(src, 2, chunk_rows=64)
    assert deferred.value > before
    for lin, shard in zip(build.lineages, build.shards):
        slices = [src.chunk(sl.step, lin.chunk_rows) for sl in lin.slices]
        want = _merge_every_chunk(
            [(xc[sl.lo:sl.hi], dc[sl.lo:sl.hi])
             for sl, (xc, dc) in zip(lin.slices, slices)],
            src.n_dec, src.v_max)
        _assert_bitwise(shard, want)
        _assert_bitwise(refold_shard(src, lin), want)


def test_mesh_shard_fold_matches_a_merge_per_chunk():
    """The mesh path's per-shard streaming fold (one data shard here)
    is finished before it is placed: its granules are bitwise those of a
    fold that merges every chunk on arrival, at capacity next_pow2(num)."""
    import jax

    from repro import obs
    from repro.core.distributed import _granularity_from_source
    from repro.distributed.api import make_mesh

    src = TabularStream(n_rows=2000, n_attrs=6, v_max=3, n_dec=2,
                        distinct_fraction=0.3, seed=5)
    mesh = make_mesh((1, 1), ("data", "model"),
                     devices=np.array(jax.devices()[:1]))
    deferred = obs.counter("plar_fold_deferred_chunks_total")
    before = deferred.value
    gx, gd, gw, gv, n_total = _granularity_from_source(
        src, mesh, n_dec=src.n_dec, v_max=src.v_max, chunk_rows=64)
    assert deferred.value > before
    want = _merge_every_chunk(src.chunks(64), src.n_dec, src.v_max)
    want = with_capacity(want, max(want.capacity, 16))
    assert n_total == int(want.n_total)
    for got, field in zip((gx, gd, gw, gv), ("x", "d", "w", "valid")):
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(getattr(want, field)), field)


def test_recover_reproduces_unfailed_build_and_downstream():
    """Lost shard → re-fold + re-merge == the unfailed build, bitwise —
    and therefore byte-identical reducts and Θ histories across ≥3
    measures (the §3.10 parity contract)."""
    src = _stream()
    unfailed = build_sharded(src, 3, chunk_rows=256)
    failed = build_sharded(src, 3, chunk_rows=256)
    failed.drop(1)
    assert failed.lost == [1]
    assert recover(failed, src) == [1]
    _gran_equal(failed.merged, unfailed.merged)
    for delta in PARITY_DELTAS:
        a = plar_reduce(source=unfailed.merged, delta=delta)
        b = plar_reduce(source=failed.merged, delta=delta)
        assert a.reduct == b.reduct
        assert a.core == b.core
        assert a.theta_history == b.theta_history
        assert a.theta_full == b.theta_full


def test_sharded_matches_monolithic_build():
    """The sharded path itself is a parity-preserving build: merged shards
    == one-shard build == the engine's own resolve path."""
    src = _stream(n=700, a=6)
    _gran_equal(build_sharded(src, 5, chunk_rows=200).merged,
                build_sharded(src, 1, chunk_rows=200).merged)


def test_recover_with_cascading_drops_converges():
    """A shard dying *during* recovery is re-folded again — the loop
    converges once the (finite) plan is exhausted."""
    src = _stream()
    unfailed = build_sharded(src, 3, chunk_rows=256)
    plan = FaultPlan.parse("shard_drop@0:2,shard_drop@1:0")
    failed = build_sharded(src, 3, chunk_rows=256, fault_plan=plan)
    assert failed.lost == [2]  # the build-time drop
    recovered = recover(failed, src, fault_plan=plan)
    # shard 2 re-folded, then the plan killed shard 0 mid-recovery
    assert sorted(recovered) == [0, 2] and not failed.lost
    _gran_equal(failed.merged, unfailed.merged)
    assert plan.fired == [("shard_drop", 0), ("shard_drop", 1)]


def test_merge_shards_refuses_lost_shards():
    src = _stream(n=300, a=5)
    build = build_sharded(src, 2, chunk_rows=128)
    build.drop(0)
    with pytest.raises(ValueError, match="recover lost shards first"):
        merge_shards(build.shards)


def test_lineage_dict_roundtrip():
    lin = ShardLineage(shard_index=1, n_shards=4, chunk_rows=256, n_dec=2,
                       v_max=3, exact=True,
                       slices=(ChunkSlice(0, 64, 128), ChunkSlice(1, 64, 128)))
    assert ShardLineage.from_dict(lin.to_dict()) == lin


def test_handle_sharded_lifecycle():
    """DatasetHandle wraps the same machinery: drop → recover keeps the
    fingerprint; an online update retires the lineage (not replayable)."""
    src = _stream(n=600, a=6)
    h = DatasetHandle.create_sharded(src, 3, chunk_rows=200)
    fp = h.fingerprint
    r0 = h.reduce("PR")
    h.drop_shard(0)
    assert h.lost_shards == [0]
    assert h.recover_shards(src) == [0]
    assert h.fingerprint == fp
    assert h.reduce("PR").reduct == r0.reduct
    h.update(*src.chunk(0, 64))  # streamed rows: lineage no longer covers
    assert h.lineage is None
    with pytest.raises(ShardLost):
        h.drop_shard(0)


# ---------------------------------------------------------------------------
# fault plans
# ---------------------------------------------------------------------------


def test_fault_plan_parse_grammar():
    plan = FaultPlan.parse("shard_drop@0:1,dispatch@2x3,merge!@0")
    assert plan.specs[0] == FaultSpec("shard_drop", 0, arg=1)
    assert plan.specs[1] == FaultSpec("dispatch", 2, count=3)
    assert plan.specs[2] == FaultSpec("merge", 0, transient=False)
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultPlan.parse("frobnicate@0")
    with pytest.raises(ValueError, match="KIND@STEP"):
        FaultPlan.parse("dispatch")


def test_fault_plan_fires_deterministically():
    plan = FaultPlan.parse("dispatch@1x2")
    assert plan.fire("dispatch") is None            # occurrence 0
    with pytest.raises(FaultInjected) as e1:
        plan.inject("dispatch")                     # occurrence 1
    assert e1.value.transient and e1.value.step == 1
    with pytest.raises(FaultInjected):
        plan.inject("dispatch")                     # occurrence 2
    assert plan.fire("dispatch") is None            # occurrence 3: exhausted
    assert plan.fired == [("dispatch", 1), ("dispatch", 2)]
    plan.reset()
    assert plan.fire("dispatch") is None and plan.fired == []


def test_fault_plan_seeded_replayable():
    a = FaultPlan.seeded(7, horizon=16, n_faults=3)
    b = FaultPlan.seeded(7, horizon=16, n_faults=3)
    assert a.specs == b.specs
    assert a.specs != FaultPlan.seeded(8, horizon=16, n_faults=3).specs


# ---------------------------------------------------------------------------
# durable checkpoints
# ---------------------------------------------------------------------------


def _handle(seed=0, n=500, a=6):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 3, (n, a)).astype(np.int32)
    d = rng.integers(0, 2, (n,)).astype(np.int32)
    return DatasetHandle.create(x, d, n_dec=2, v_max=3), x, d


def test_service_checkpoint_roundtrip(tmp_path):
    h, _x, _d = _handle()
    r = h.reduce("PR")
    h.reduce("SCE", tol=1e-5)
    ck = ServiceCheckpointer(str(tmp_path))
    assert ck.save({"ds": h}) is not None
    step, handles = ck.restore()
    h2 = handles["ds"]
    _gran_equal(h2.gran, h.gran)
    assert h2.fingerprint == h.fingerprint
    assert set(h2._results) == set(h._results)
    got = h2._results[("PR", (("exact", True),))]
    assert got.reduct == r.reduct and got.theta_history == r.theta_history
    # restored handle answers warm, and its repair is byte-identical to the
    # live handle's repair from the same state
    live = h.reduce("PR")
    restored = h2.reduce("PR")
    assert h.last_was_warm and h2.last_was_warm
    assert restored.reduct == live.reduct
    assert restored.theta_history == live.theta_history


def test_sharded_handle_checkpoint_keeps_lineage(tmp_path):
    src = _stream(n=600, a=6)
    h = DatasetHandle.create_sharded(src, 3, chunk_rows=200)
    ck = ServiceCheckpointer(str(tmp_path))
    ck.save({"ds": h})
    _step, handles = ck.restore()
    h2 = handles["ds"]
    assert h2.lineage is not None and len(h2.lineage) == 3
    assert h2.lineage == h.lineage
    assert h2.fingerprint == h.fingerprint


def test_checkpoint_crash_leaves_previous_step_restorable(tmp_path):
    """An injected crash between staging and commit aborts the step with
    nothing committed — the previous step still restores."""
    h, _x, _d = _handle()
    h.reduce("PR")
    ck = ServiceCheckpointer(str(tmp_path),
                             fault_plan=FaultPlan.parse("checkpoint@1"))
    assert ck.save({"ds": h}) is not None          # step 1 commits
    h.update(*_handle(seed=1)[1:])                  # change content
    assert ck.save({"ds": h}) is None               # step 2: injected crash
    assert ck.failed_saves == 1
    assert isinstance(ck.last_error, FaultInjected)
    step, handles = ck.restore()
    assert step == 1                                # pre-crash state survives
    assert handles["ds"].fingerprint != h.fingerprint


def test_checkpoint_fingerprint_mismatch_is_corrupt(tmp_path):
    import json
    h, _x, _d = _handle()
    ck = ServiceCheckpointer(str(tmp_path))
    path = ck.save({"ds": h})
    mpath = os.path.join(path, "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    manifest["extra"]["datasets"]["ds"]["fingerprint"] ^= 0xDEAD
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    with pytest.raises(CheckpointCorrupt, match="fingerprint"):
        ck.restore()


def test_train_restore_skips_corrupt_step(tmp_path):
    """S1: auto-pick restore degrades to the next older committed step when
    the newest is corrupt (truncated npz), with a warning; an explicitly
    requested corrupt step still raises."""
    mgr = CheckpointManager(str(tmp_path), keep=5)
    mgr.save(1, {"w": np.arange(4)})
    mgr.save(2, {"w": np.arange(8)})
    npz = os.path.join(mgr._path(2), "arrays.npz")
    with open(npz, "wb") as f:
        f.write(b"\x00" * 16)  # committed but garbage
    with pytest.warns(UserWarning, match="skipping corrupt checkpoint"):
        step, tree, _extra = mgr.restore()
    assert step == 1 and len(tree["w"]) == 4
    with pytest.raises(Exception):
        mgr.restore(step=2)


def test_train_restore_all_corrupt_raises(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, {"w": np.arange(4)})
    npz = os.path.join(mgr._path(1), "arrays.npz")
    with open(npz, "wb") as f:
        f.write(b"junk")
    with pytest.warns(UserWarning):
        with pytest.raises(FileNotFoundError, match="all 1 committed"):
            mgr.restore()


# ---------------------------------------------------------------------------
# hardened server: restart, flush, retry, quarantine, stale
# ---------------------------------------------------------------------------


def test_server_restart_restores_and_answers_warm(tmp_path):
    """Kill + restart: the new server restores the checkpointed handle and
    serves its first query through the warm repair path."""
    rng = np.random.default_rng(3)
    x = rng.integers(0, 3, (600, 6)).astype(np.int32)
    d = rng.integers(0, 2, (600,)).astype(np.int32)
    ckdir = str(tmp_path)

    async def first_life():
        async with ReductServer(checkpoint_dir=ckdir) as srv:
            await srv.submit("ds", x, d, n_dec=2, v_max=3)
            await srv.query("ds", delta="PR")          # cold
            # run one warm repair on the handle so the checkpoint persists
            # the repair fixed point — exactly what the restarted server's
            # first (warm) query must reproduce byte-for-byte
            r = await asyncio.to_thread(srv.handle("ds").reduce, "PR")
            return r, srv.handle("ds").fingerprint

    r1, fp1 = asyncio.run(first_life())

    async def second_life():
        async with ReductServer(checkpoint_dir=ckdir) as srv:
            assert srv.stats["restored_datasets"] == 1
            assert srv.handle("ds").fingerprint == fp1
            r = await srv.query("ds", delta="PR")
            warm = srv.stats["warm"]
            # and the restored state keeps absorbing updates
            await srv.update("ds", x[:50], d[:50])
            r2 = await srv.query("ds", delta="PR")
            return r, warm, r2

    r2, warm, r3 = asyncio.run(second_life())
    assert r2.reduct == r1.reduct
    assert r2.theta_history == r1.theta_history
    assert warm == 1  # first post-restart query repaired, not recomputed
    # the restarted server's warm answer selects what a cold run selects
    assert sorted(r2.reduct) == sorted(plar_reduce(x, d, delta="PR").reduct)
    assert r3.reduct  # post-restore update still serves


def test_server_stop_flushes_pending_updates(tmp_path):
    """S2: updates buffered but never demanded by a query are merged by
    stop() — an orderly shutdown never drops accepted updates."""
    rng = np.random.default_rng(4)
    x = rng.integers(0, 3, (400, 6)).astype(np.int32)
    d = rng.integers(0, 2, (400,)).astype(np.int32)
    ckdir = str(tmp_path)

    async def drive():
        srv = ReductServer(checkpoint_dir=ckdir)
        async with srv:
            await srv.submit("ds", x[:200], d[:200], n_dec=2, v_max=3)
            await srv.update("ds", x[200:300], d[200:300])
            await srv.update("ds", x[300:], d[300:])
            # no query: the batches are still buffered at stop()
        return srv.summary(), srv._handles["ds"].fingerprint

    stats, fp = asyncio.run(drive())
    assert stats["flushed_batches"] == 2
    assert stats["merges"] == 1  # both batches in ONE coalesced merge
    full = DatasetHandle.create(x, d, n_dec=2, v_max=3)
    assert fp == full.fingerprint
    # and the final checkpoint captured the flushed state
    _step, handles = ServiceCheckpointer(ckdir).restore()
    assert handles["ds"].fingerprint == full.fingerprint


def test_transient_dispatch_fault_is_retried():
    rng = np.random.default_rng(5)
    x = rng.integers(0, 3, (300, 6)).astype(np.int32)
    d = rng.integers(0, 2, (300,)).astype(np.int32)

    async def drive():
        async with ReductServer(
                fault_plan=FaultPlan.parse("dispatch@0"),
                retry=RetryPolicy(base_delay_s=0.001)) as srv:
            await srv.submit("ds", x, d, n_dec=2, v_max=3)
            r = await srv.query("ds", delta="PR")
            return r, dict(srv.stats)

    r, stats = asyncio.run(drive())
    assert r.reduct and not r.stale
    assert stats["retries"] == 1
    assert stats["quarantined"] == 0


def test_fatal_faults_quarantine_then_content_change_clears():
    """A config failing `quarantine_after` times is poisoned: followers get
    QueryPoisoned without re-running the dispatch; a content change (merge)
    clears the quarantine."""
    rng = np.random.default_rng(6)
    x = rng.integers(0, 3, (300, 6)).astype(np.int32)
    d = rng.integers(0, 2, (300,)).astype(np.int32)

    async def drive():
        async with ReductServer(
                fault_plan=FaultPlan.parse("dispatch!@0x2"),
                retry=RetryPolicy(base_delay_s=0.001,
                                  quarantine_after=2)) as srv:
            await srv.submit("ds", x[:250], d[:250], n_dec=2, v_max=3)
            with pytest.raises(FaultInjected):   # fatal: not retried
                await srv.query("ds", delta="PR")
            with pytest.raises(FaultInjected):
                await srv.query("ds", delta="PR")
            assert srv.stats["quarantined"] == 1
            with pytest.raises(QueryPoisoned, match="quarantined"):
                await srv.query("ds", delta="PR")
            runs_before = srv.stats["engine_runs"]
            # content change clears the slate; plan is exhausted → success
            await srv.update("ds", x[250:], d[250:])
            r = await srv.query("ds", delta="PR")
            return r, runs_before, dict(srv.stats)

    r, runs_before, stats = asyncio.run(drive())
    assert r.reduct
    assert runs_before == 0          # poisoned follower never hit the engine
    assert stats["retries"] == 0     # fatal faults are not retried


def test_serve_stale_degrades_to_last_good():
    """serve_stale=True: a failed dispatch serves the last known-good
    result flagged stale=True instead of erroring."""
    rng = np.random.default_rng(7)
    x = rng.integers(0, 3, (300, 6)).astype(np.int32)
    d = rng.integers(0, 2, (300,)).astype(np.int32)

    async def drive():
        async with ReductServer(
                fault_plan=FaultPlan.parse("dispatch!@1x3"),
                retry=RetryPolicy(base_delay_s=0.001),
                serve_stale=True) as srv:
            await srv.submit("ds", x[:250], d[:250], n_dec=2, v_max=3)
            good = await srv.query("ds", delta="PR")   # occurrence 0: fine
            await srv.update("ds", x[250:], d[250:])   # cache now misses
            degraded = await srv.query("ds", delta="PR")
            return good, degraded, dict(srv.stats)

    good, degraded, stats = asyncio.run(drive())
    assert not good.stale
    assert degraded.stale
    assert degraded.reduct == good.reduct
    assert stats["stale_served"] == 1


def test_fault_storm_is_absorbed(tmp_path):
    """Transient dispatch faults, a merge fault and a checkpoint-write
    crash across a stream of updates: every query is answered, no fault
    reaches a client, and the retries and failed save are counted."""
    rng = np.random.default_rng(17)
    x = rng.integers(0, 3, (1200, 8)).astype(np.int32)
    d = rng.integers(0, 2, (1200,)).astype(np.int32)
    plan = FaultPlan.parse("dispatch@1,dispatch@3,merge@1,checkpoint@1")

    async def drive():
        async with ReductServer(
                checkpoint_dir=str(tmp_path), fault_plan=plan,
                retry=RetryPolicy(base_delay_s=0.001),
                serve_stale=True) as srv:
            await srv.submit("ds", x[:600], d[:600], n_dec=2, v_max=3)
            answered = 0
            for i in range(4):
                lo = 600 + i * 150
                await srv.update("ds", x[lo:lo + 150], d[lo:lo + 150])
                r = await srv.query("ds", delta="PR")
                answered += bool(r.reduct)
            return answered, dict(srv.stats)

    answered, stats = asyncio.run(drive())
    assert answered == 4, "a fault leaked to a client"
    assert len(plan.fired) >= 3, plan.fired
    assert stats["retries"] >= 2


def test_stopped_server_raises_typed_error():
    async def drive():
        srv = ReductServer()
        async with srv:
            await srv.submit("ds", np.zeros((4, 2), np.int32),
                             np.zeros((4,), np.int32), n_dec=2, v_max=2)
        from repro.service import ServiceError
        with pytest.raises(ServiceError, match="not started"):
            srv._ensure_running()  # fully stopped == not started
        srv._stopping = True
        with pytest.raises(ServerStopped, match="server stopped"):
            srv._ensure_running()  # mid-shutdown: the typed stop error
        srv._stopping = False
        # the hierarchy: every typed error is still a RuntimeError
        assert issubclass(ServerStopped, RuntimeError)
        assert issubclass(QueryPoisoned, RuntimeError)

    asyncio.run(drive())


# ---------------------------------------------------------------------------
# S4: repair_reduce under adversarial inputs
# ---------------------------------------------------------------------------


def test_repair_empty_previous_is_cold_run():
    h, x, d = _handle(seed=8)
    cold = plar_reduce(x, d, delta="PR", n_dec=2, v_max=3)
    r, kept = repair_reduce(h.gran, [], delta="PR")
    assert kept == 0
    assert r.reduct == cold.reduct and r.theta_history == cold.theta_history


def test_repair_out_of_range_previous_is_sanitized():
    """A reduct referencing attributes beyond the table (a checkpoint from
    a wider schema) must not crash or corrupt the result: bad attributes
    are dropped from the warm hint, the answer matches the cold run."""
    h, x, d = _handle(seed=9)
    cold = plar_reduce(x, d, delta="PR", n_dec=2, v_max=3)
    bad = list(cold.reduct) + [h.gran.n_attrs + 3, -1, cold.reduct[0]]
    r, _kept = repair_reduce(h.gran, bad, delta="PR")
    assert r.reduct == cold.reduct
    assert r.theta_history == cold.theta_history
    # entirely-garbage previous degrades to a cold run
    r2, kept2 = repair_reduce(h.gran, [99, 99, -5], delta="PR")
    assert kept2 == 0 and r2.reduct == cold.reduct


def test_noop_update_racing_checkpoint_restore(tmp_path):
    """S4: a fingerprint-unchanged no-op update between checkpoint and
    restore must leave the restored handle fully consistent — same
    fingerprint, warm repair still valid."""
    h, _x, _d = _handle(seed=10)
    r = h.reduce("PR")
    ck = ServiceCheckpointer(str(tmp_path))
    ck.save({"ds": h})
    # empty batch: counted, but content (and fingerprint) unchanged
    h.update(np.zeros((0, h.gran.n_attrs), np.int32), np.zeros((0,), np.int32))
    assert h.n_updates == 1
    _step, handles = ck.restore()
    h2 = handles["ds"]
    assert h2.fingerprint == h.fingerprint
    # both warm-repair from the same persisted state → identical answers
    live, restored = h.reduce("PR"), h2.reduce("PR")
    assert h.last_was_warm and h2.last_was_warm
    assert restored.reduct == live.reduct
    assert restored.theta_history == live.theta_history
