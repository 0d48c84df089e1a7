"""Analytic kernel-cost model + selector/autotune tests (DESIGN.md §5.2).

Three concerns:

* the closed-form FLOPs/bytes of :mod:`repro.kernels.contingency.model`
  against XLA's own ``compiled.cost_analysis()`` — on *single-grid-step*
  shapes, because XLA counts a ``while`` body once (the roofline.py caveat),
  so multi-step grids under-report by the step count;
* the selector seam: byte-identical reducts and Θ histories across every
  selector mode × Θ backend (tiles and ladder rungs must never change bits,
  only speed);
* the autotune caches: platform-scoped keys, bounded LRU, disk round-trip,
  and the top-k pruned (opt-in) timing refinement.
"""
import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.plan import ladder_rungs
from repro.core.reduction import plar_reduce
from repro.kernels.contingency import autotune
from repro.kernels.contingency.autotune import (
    SELECTOR_MODES,
    autotune_block_sizes,
    autotune_cache_clear,
    autotune_cache_info,
    resolve_tiles,
    shape_bucket,
)
from repro.kernels.contingency.fused import fused_theta_pallas
from repro.kernels.contingency.kernel import contingency_pallas
from repro.kernels.contingency.model import (
    KernelCost,
    VMEM_BUDGET_BYTES,
    contingency_cost,
    feasible_tiles,
    fused_cost,
    modeled_time_s,
    prune_ladder_rungs,
    rank_tiles,
    rung_eval_cost_bytes,
    select_tiles,
    sweep_cost,
    sweep_working_set_bytes,
    working_set_bytes,
)
from repro.kernels.contingency.sweep import sweep_theta_pallas


def _xla_cost(lowered):
    """(flops, bytes accessed) from XLA's analysis of a lowered computation."""
    ca = lowered.compile().cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


def _operands(nc, g, n_bins, m, v_max=1, seed=0):
    rng = np.random.default_rng(seed)
    packed = jnp.asarray(rng.integers(0, n_bins, (nc, g)), jnp.int32)
    x_t = jnp.asarray(rng.integers(0, v_max, (nc, g)), jnp.int32)
    r_ids = jnp.asarray(rng.integers(0, max(n_bins // v_max, 1), (g,)),
                        jnp.int32)
    wd = jnp.zeros((g, m), jnp.float32).at[
        jnp.arange(g), jnp.asarray(rng.integers(0, m, (g,)))].set(1.0)
    return packed, x_t, r_ids, wd


# ---------------------------------------------------------------------------
# model vs compiled.cost_analysis()
# ---------------------------------------------------------------------------


def test_contingency_cost_vs_xla():
    # single grid step: nc=1, K̂/bk = 1, Ĝ/bg = 1 — XLA's while-once count
    # is then exact, so FLOPs must match tightly and bytes within 2×.
    nc, g, nb, m, bk, bg = 1, 1024, 8, 128, 8, 1024
    packed, _, _, wd = _operands(nc, g, nb, m)
    low = contingency_pallas.lower(packed, wd, n_bins=nb, bk=bk, bg=bg,
                                   interpret=True)
    flops_x, bytes_x = _xla_cost(low)
    cost = contingency_cost(nc, g, nb, m, bk, bg)
    assert 0.9 <= cost.flops / flops_x <= 1.1
    assert 0.5 <= cost.hbm_bytes / bytes_x <= 2.0
    assert cost.grid_steps == 1
    assert cost.transcendentals == 0.0


def test_fused_cost_vs_xla():
    nc, g, nb, m, bk, bg = 1, 1024, 8, 128, 8, 1024
    packed, _, _, wd = _operands(nc, g, nb, m)
    low = fused_theta_pallas.lower(packed, wd, n_bins=nb, delta="SCE",
                                   bk=bk, bg=bg, interpret=True)
    flops_x, bytes_x = _xla_cost(low)
    cost = fused_cost(nc, g, nb, m, bk, bg, delta="SCE")
    assert 0.9 <= cost.flops / flops_x <= 1.2   # epilogue ≈8 flops/cell
    assert 0.5 <= cost.hbm_bytes / bytes_x <= 2.0
    assert cost.transcendentals > 0           # SCE logs
    assert fused_cost(nc, g, nb, m, bk, bg, delta="PR").transcendentals == 0


@pytest.mark.parametrize("nb,v_max", [(8, 2), (16, 4)])
def test_sweep_cost_vs_xla(nb, v_max):
    # bc=1 keeps XLA's per-op operand counting aligned with the stream model
    # (at bc>1 XLA charges the reused wd tile once per candidate, which the
    # read-once schedule does not pay — the reuse test below covers that).
    nc, g, m, bc, bk, bg = 1, 1024, 128, 1, nb, 1024
    _, x_t, r_ids, wd = _operands(nc, g, nb, m, v_max=v_max)
    low = sweep_theta_pallas.lower(x_t, r_ids, wd, v_max=v_max, n_bins=nb,
                                   delta="SCE", bc=bc, bk=bk, bg=bg,
                                   interpret=True)
    flops_x, bytes_x = _xla_cost(low)
    cost = sweep_cost(nc, g, nb, m, bc, bk, bg, v_max=v_max, delta="SCE")
    assert 0.9 <= cost.flops / flops_x <= 1.2
    assert 0.5 <= cost.hbm_bytes / bytes_x <= 2.0


def test_sweep_bc_reuse_model_property():
    # The whole point of the sweep kernel: shared r_ids/wd traffic carries a
    # 1/BC factor.  Larger candidate blocks must strictly cut modeled HBM.
    nc, g, nb, m = 64, 4096, 1024, 128
    b1 = sweep_cost(nc, g, nb, m, 1, 128, 256).hbm_bytes
    b8 = sweep_cost(nc, g, nb, m, 8, 128, 256).hbm_bytes
    assert b8 < b1
    # and the saving is the shared-stream term, ≈ (1 - 1/8) of it
    shared1 = 4.0 * 4096 * (1 + m) * nc * (1024 // 128)
    assert b1 - b8 == pytest.approx(shared1 * (1 - 1 / 8), rel=1e-6)


def test_feasible_tiles_respect_budget_and_alignment():
    for kernel in ("contingency", "fused", "sweep"):
        cands = feasible_tiles(kernel, 64, 3000, 1024, 128)
        assert cands
        for tiles in cands:
            if kernel == "sweep":
                bc, bk, bg = tiles
                assert sweep_working_set_bytes(bc, bk, bg, 128) <= VMEM_BUDGET_BYTES
            else:
                bk, bg = tiles
                assert working_set_bytes(bk, bg, 128) <= VMEM_BUDGET_BYTES
            assert bk % 8 == 0 and bg % 128 == 0
    # tiny table: no tile more than one step beyond the padded shape
    for bk, bg in feasible_tiles("contingency", 2, 300, 40, 128):
        assert bk // 2 < 40 + 7 and bg // 2 < 384


def test_rank_is_deterministic_and_sorted():
    r1 = rank_tiles("fused", 64, 3000, 1024, 128)
    r2 = rank_tiles("fused", 64, 3000, 1024, 128)
    assert r1 == r2
    times = [t for _, _, t in r1]
    assert times == sorted(times)
    assert select_tiles("fused", 64, 3000, 1024, 128) == r1[0][0]
    assert all(isinstance(c, KernelCost) and modeled_time_s(c) == t
               for _, c, t in r1[:3])


# ---------------------------------------------------------------------------
# analytic ladder-rung pruning
# ---------------------------------------------------------------------------


def test_prune_ladder_rungs_invariants():
    rungs = ladder_rungs(4096)                      # (256, 512, ..., 4096)
    pruned = prune_ladder_rungs(rungs, 4096, 8)
    assert set(pruned) <= set(rungs)                # subset of the pow2 family
    assert pruned[-1] == rungs[-1]                  # exact top always kept
    assert list(pruned) == sorted(pruned)
    # bin-dominated regime (tiny fixed term): every halving saves ~50% > 15%
    assert prune_ladder_rungs((256, 512, 1024), 256, 23) == (256, 512, 1024)


def test_prune_ladder_dispatch_bound_collapse():
    # granule-dominated regime: the fixed G·m term dwarfs the per-bin term,
    # so small rungs save nothing and collapse away.
    pruned = prune_ladder_rungs((256, 512), 4096, 128)
    assert pruned == (512,)
    # monotonicity of the underlying cost
    assert rung_eval_cost_bytes(256, 4096, 128) < rung_eval_cost_bytes(512, 4096, 128)


def test_ladder_rungs_selector_modes():
    # default (heuristic) is the unchanged pow2 ladder — pinned by test_sweep
    assert ladder_rungs(1024) == (256, 512, 1024)
    pruned = ladder_rungs(4096, selector="analytic", g=4096, m=128)
    full = ladder_rungs(4096)
    assert set(pruned) <= set(full) and pruned[-1] == full[-1]
    # without shape context the analytic mode degrades to the full ladder
    assert ladder_rungs(4096, selector="analytic") == full


# ---------------------------------------------------------------------------
# selector parity: tiles/rungs must never change bits
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["segment", "fused_xla", "sweep_xla"])
def test_selector_parity_matrix(backend):
    rng = np.random.default_rng(7)
    x = rng.integers(0, 3, (300, 10)).astype(np.int32)
    d = rng.integers(0, 3, (300,)).astype(np.int32)
    ref = None
    for sel in SELECTOR_MODES:
        r = plar_reduce(x, d, delta="SCE", backend=backend, ladder=True,
                        selector=sel)
        key = (tuple(r.reduct),
               tuple(np.float32(t).tobytes() for t in r.theta_history))
        if ref is None:
            ref = key
        assert key == ref, f"selector={sel} backend={backend} changed bits"


def test_ops_default_tiles_match_analytic():
    # bk/bg=None routes through the analytic selector; explicit tiles with
    # the same values must agree exactly.
    from repro.kernels.contingency.ops import contingency

    nc, g, nb, m = 4, 600, 32, 3
    rng = np.random.default_rng(1)
    packed = jnp.asarray(rng.integers(0, nb, (nc, g)), jnp.int32)
    d = jnp.asarray(rng.integers(0, m, (g,)), jnp.int32)
    w = jnp.ones((g,), jnp.float32)
    auto = contingency(packed, d, w, n_bins=nb, n_dec=m)
    bk, bg = resolve_tiles("contingency", nc=nc, g=g, n_bins=nb, m=128,
                           selector="analytic")
    manual = contingency(packed, d, w, n_bins=nb, n_dec=m, bk=bk, bg=bg)
    assert jnp.array_equal(auto, manual)


def test_resolve_tiles_modes():
    kw = dict(nc=8, g=3000, n_bins=1024, m=128)
    assert resolve_tiles("contingency", **kw, selector="pinned") == (128, 512)
    assert resolve_tiles("sweep", **kw, selector="pinned") == (8, 128, 256)
    heur = resolve_tiles("fused", **kw, selector="heuristic")
    assert heur == autotune.select_block_sizes(1024, 3000, 128)
    ana = resolve_tiles("fused", **kw)     # None → analytic default
    assert ana == select_tiles("fused", 8, 3000, 1024, 128)
    with pytest.raises(ValueError, match="unknown tile selector"):
        resolve_tiles("fused", **kw, selector="nope")


# ---------------------------------------------------------------------------
# caches: platform key, LRU bound, disk round-trip
# ---------------------------------------------------------------------------


@pytest.fixture()
def tmp_disk(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune_cache_clear(disk=True)
    yield path
    autotune_cache_clear(disk=True)


def test_cache_key_includes_platform(tmp_disk):
    shape = dict(nc=2, g=256, n_bins=64, m=8)
    autotune_block_sizes(**shape)                       # default platform
    info0 = autotune_cache_info()
    autotune_block_sizes(**shape, platform="tpu")       # distinct key
    info1 = autotune_cache_info()
    assert info1["misses"] == info0["misses"] + 1
    autotune_block_sizes(**shape, platform="tpu")       # now a hit
    assert autotune_cache_info()["hits"] == info1["hits"] + 1


def test_cache_clear_and_info(tmp_disk):
    autotune_block_sizes(2, 256, 64, 8)
    info = autotune_cache_info()
    assert info["size"] >= 1 and info["disk_entries"] >= 1
    assert info["disk_path"] == str(tmp_disk)
    autotune_cache_clear(disk=True)
    info = autotune_cache_info()
    assert info["size"] == 0 and info["hits"] == 0 and info["misses"] == 0
    assert info["disk_entries"] == 0 and not tmp_disk.exists()


def test_cache_lru_bounded(tmp_disk, monkeypatch):
    monkeypatch.setattr(autotune, "_CACHE_MAXSIZE", 4)
    autotune_cache_clear()
    for i in range(8):
        autotune_block_sizes(2, 256 + 128 * i, 64, 8)
    assert autotune_cache_info()["size"] <= 4


def test_disk_cache_roundtrip(tmp_disk):
    pick = autotune_block_sizes(2, 300, 40, 3)
    assert tmp_disk.exists()
    data = json.loads(tmp_disk.read_text())
    key = autotune._disk_key(jax.default_backend(), "contingency",
                             shape_bucket(2, 300, 40, 3))
    assert tuple(data[key]) == pick
    # a fresh "process" (memory cleared) resolves the persisted tuning
    autotune_cache_clear()
    assert resolve_tiles("contingency", nc=2, g=300, n_bins=40, m=3) == pick


def test_disk_tuned_overrides_model(tmp_disk):
    kw = dict(nc=8, g=3000, n_bins=1024, m=128)
    model_pick = select_tiles("fused", 8, 3000, 1024, 128)
    custom = (8, 128)
    assert custom != model_pick
    key = autotune._disk_key(jax.default_backend(), "fused",
                             shape_bucket(8, 3000, 1024, 128))
    tmp_disk.write_text(json.dumps({key: list(custom)}))
    autotune._disk_state["data"] = None                 # force reload
    assert resolve_tiles("fused", **kw) == custom
    # other modes ignore the disk cache
    assert resolve_tiles("fused", **kw, selector="heuristic") != custom


def test_no_disk_cache_unless_named(monkeypatch, tmp_path):
    # tiles come from the model alone unless REPRO_AUTOTUNE_CACHE names a
    # file: no default location is read or written
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    monkeypatch.setenv("HOME", str(tmp_path))
    monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path))
    autotune_cache_clear()
    pick = autotune_block_sizes(2, 300, 40, 3)
    assert autotune_cache_info()["disk_path"] is None
    assert list(tmp_path.iterdir()) == []
    assert resolve_tiles("contingency", nc=2, g=300, n_bins=40, m=3) == pick


def test_restricted_candidates_not_persisted(tmp_disk):
    # a rank over a caller-pinned candidate list is not a shape tuning and
    # must not shadow the model for the whole bucket
    autotune_block_sizes(2, 300, 40, 3, delta="SCE",
                         candidates=((8, 128), (16, 256)))
    assert not tmp_disk.exists()


# ---------------------------------------------------------------------------
# timing refinement: top-k pruning + failed-compile skip
# ---------------------------------------------------------------------------


def test_refine_compiles_at_most_topk(tmp_disk, monkeypatch):
    built = []

    def fake_build(kernel, tiles, *a, **kw):
        built.append(tiles)
        return lambda: jnp.zeros(())

    monkeypatch.setattr(autotune, "_build_candidate_fn", fake_build)
    pick = autotune_block_sizes(4, 2000, 512, 16, delta="SCE", refine=True,
                                reps=1, top_k=3)
    assert len(built) <= 3                       # analytic pruning before timing
    assert pick in built                          # winner came from the timed set
    assert len(feasible_tiles("fused", 4, 2000, 512, 128)) > 3  # pruning real


def test_refine_default_is_zero_compiles(tmp_disk, monkeypatch):
    def boom(*a, **kw):  # pragma: no cover - must not be reached
        raise AssertionError("refine=False must never build a candidate")

    monkeypatch.setattr(autotune, "_build_candidate_fn", boom)
    pick = autotune_block_sizes(4, 2000, 512, 16, delta="SCE")
    assert pick == select_tiles("fused", 4, 2000, 512, 128)


def test_refine_skips_failed_compile(tmp_disk, monkeypatch, caplog):
    calls = []

    def flaky_build(kernel, tiles, *a, **kw):
        calls.append(tiles)
        if len(calls) == 1:
            def dead():
                raise RuntimeError("XLA compile exploded")
            return dead
        return lambda: jnp.zeros(())

    monkeypatch.setattr(autotune, "_build_candidate_fn", flaky_build)
    with caplog.at_level(logging.WARNING,
                         logger="repro.kernels.contingency.autotune"):
        pick = autotune_block_sizes(4, 2000, 512, 16, delta="SCE",
                                    refine=True, reps=1, top_k=2)
    assert pick == calls[1]                      # survivor wins
    assert any("failed to compile" in r.message for r in caplog.records)
