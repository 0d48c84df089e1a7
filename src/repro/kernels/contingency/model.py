"""Analytic roofline cost model for the contingency kernels (DESIGN.md §5.2).

The timing autotuner is meaningless on this host (interpret-mode Pallas) and
expensive on real hardware (a compile per candidate tiling).  This module is
the alternative production kernel libraries converge on: a *closed-form* cost
model per kernel — FLOPs, HBM bytes moved, and the per-grid-step VMEM working
set as a function of the problem shape ``(nc, G, K, V, m)`` and the tiling
``(BC, BK, BG)`` — ranked on the roofline of :mod:`repro.launch.roofline`.
The analytic rank is the default tile selector (``ops.py``) and prunes the
timing autotuner's candidate grid to a top-k (``autotune.py``), so timing
becomes an opt-in refinement instead of the default 9-compile sweep.

Model shapes (validated against ``compiled.cost_analysis()`` by
tests/test_kernel_cost_model.py; grid = one step keeps the count exact —
XLA's analysis counts a ``while`` body once, the roofline.py caveat):

* **contingency** (``kernel.py``, grid ``(nc, K̂/BK, Ĝ/BG)``): per step one
  ``[BK, BG]`` compare + a ``[BK, BG] @ [BG, m]`` MXU dot → total
  ``nc·K̂·Ĝ·(1+2m)`` FLOPs.  HBM: ``packed``/``wd`` are re-streamed once per
  bin tile (the G walk restarts for every k), the ``[nc, K̂, m]`` counts
  tensor is written once.
* **fused** (``fused.py``): same accumulation + the θ' epilogue
  (≈ 8 FLOPs/cell on the finished ``[BK, m]`` tile) and an O(nc) output —
  the ``[nc, K̂, m]`` HBM write disappears.
* **sweep** (``sweep.py``, grid ``(Ĉ/BC, K̂/BK, Ĝ/BG)``): per candidate the
  fused kernel's work plus the in-register pack (2 FLOPs/granule per bin
  tile); the shared ``r_ids``/``wd`` granule tiles are loaded **once per
  candidate block** — their stream traffic carries a 1/BC factor, the reuse
  ``autotune.working_set_bytes`` alone cannot express.

A modeled execution time adds a per-grid-step dispatch overhead to the
roofline bound, so tiny-granule tables rank as dispatch-bound (many grid
steps, little traffic) — the regime the ``autotune`` benchmark preset
measures end-to-end.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Optional, Sequence, Tuple

from repro.launch.roofline import roofline_terms

__all__ = [
    "KernelCost",
    "contingency_cost",
    "fused_cost",
    "sweep_cost",
    "kernel_cost",
    "working_set_bytes",
    "sweep_working_set_bytes",
    "modeled_time_s",
    "feasible_tiles",
    "rank_tiles",
    "select_tiles",
    "prune_ladder_rungs",
    "rung_eval_cost_bytes",
    "LANE",
    "SUBLANE",
    "VMEM_BUDGET_BYTES",
    "GRID_STEP_OVERHEAD_S",
    "LADDER_MIN_SAVING",
]

LANE = 128
SUBLANE = 8
VMEM_BUDGET_BYTES = 4 * 1024 * 1024   # per-step working set cap (¼ of VMEM)

# Fixed cost of one grid step beyond its data movement (DMA issue, loop
# bookkeeping).  Small enough to be invisible on streaming shapes, large
# enough that tiny-granule tables (many steps, tiny tiles) rank as
# dispatch-bound and the selector prefers fewer/larger tiles on ties.
GRID_STEP_OVERHEAD_S = 1e-7

# Candidate tile axes the analytic selector searches (supersets of the old
# timing grid: the model is free, so smaller-than-MXU tiles for small tables
# cost nothing to consider).
CANDIDATE_BK = (8, 16, 32, 64, 128, 256, 512)
CANDIDATE_BG = (128, 256, 512, 1024)
# BC is the sublane axis of the sweep kernel's candidate block, so Mosaic
# takes only multiples of 8 (padded candidate rows are sliced off).
CANDIDATE_BC = (8, 16)

_EPILOGUE_FLOPS_PER_CELL = 8   # θ' row math: where/log/mul/add per count cell


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


@dataclasses.dataclass(frozen=True)
class KernelCost:
    """Closed-form cost of one kernel launch.

    ``hbm_bytes`` is the modeled HBM stream traffic; ``vmem_bytes`` the
    per-grid-step resident working set (the feasibility constraint);
    ``grid_steps`` the grid size (the dispatch-overhead multiplier).
    ``transcendentals`` counts the log evaluations of the θ' epilogue
    (0 for the unfused kernel and for Θ_PR).
    """

    flops: float
    hbm_bytes: float
    vmem_bytes: int
    grid_steps: int
    transcendentals: float = 0.0


def working_set_bytes(bk: int, bg: int, m: int) -> int:
    """f32/int32 bytes resident per grid step (contingency/fused kernels).

    packed tile + double-buffered wd stream + output/accumulator tile + the
    [BK, BG] one-hot intermediate (the largest term for big tiles).
    """
    packed = 4 * bg
    wd = 2 * 4 * bg * m          # double-buffered stream
    acc = 4 * bk * m             # output/accumulator tile
    onehot = 4 * bk * bg         # materialized before the dot
    return packed + wd + acc + onehot


def sweep_working_set_bytes(bc: int, bk: int, bg: int, m: int) -> int:
    """Per-step VMEM bytes of the sweep kernel: the fused kernel's tiles with
    a BC-row candidate slab and a ``[BC, BK, m]`` accumulator."""
    xt = 4 * bc * bg
    r = 4 * bg
    wd = 2 * 4 * bg * m
    acc = 4 * bc * bk * m
    onehot = 4 * bk * bg
    return xt + r + wd + acc + onehot


def contingency_cost(nc: int, g: int, n_bins: int, m: int,
                     bk: int, bg: int) -> KernelCost:
    """Cost of one unfused contingency launch (``kernel.py``)."""
    k_hat = _round_up(n_bins, bk)
    g_hat = _round_up(g, bg)
    k_tiles = k_hat // bk
    steps = nc * k_tiles * (g_hat // bg)
    flops = float(nc) * k_hat * g_hat * (1 + 2 * m)
    # packed and wd are re-streamed once per (candidate, bin-tile) pair —
    # the G-axis walk restarts for every k — and the counts tensor lands once.
    hbm = (4.0 * g_hat * nc * k_tiles            # packed
           + 4.0 * g_hat * m * nc * k_tiles      # wd
           + 4.0 * nc * k_hat * m)               # counts out
    return KernelCost(flops, hbm, working_set_bytes(bk, bg, m), steps)


def fused_cost(nc: int, g: int, n_bins: int, m: int, bk: int, bg: int,
               delta: str = "SCE") -> KernelCost:
    """Cost of one fused contingency→Θ launch (``fused.py``)."""
    k_hat = _round_up(n_bins, bk)
    g_hat = _round_up(g, bg)
    k_tiles = k_hat // bk
    steps = nc * k_tiles * (g_hat // bg)
    flops = (float(nc) * k_hat * g_hat * (1 + 2 * m)
             + float(_EPILOGUE_FLOPS_PER_CELL) * nc * k_hat * m)
    hbm = (4.0 * g_hat * nc * k_tiles            # packed
           + 4.0 * g_hat * m * nc * k_tiles      # wd
           + 4.0 * nc)                           # θ' scalars out
    trans = 0.0 if delta == "PR" else float(nc) * k_hat * m
    return KernelCost(flops, hbm, working_set_bytes(bk, bg, m), steps, trans)


def sweep_cost(nc: int, g: int, n_bins: int, m: int,
               bc: int, bk: int, bg: int, v_max: int = 1,
               delta: str = "SCE") -> KernelCost:
    """Cost of one multi-candidate sweep launch (``sweep.py``).

    The load-bearing term: the shared ``r_ids``/``wd`` tiles are DMA'd once
    per candidate *block*, so their stream traffic is ``Ĉ/BC`` × per-bin-tile
    — the BC× reuse ``working_set_bytes`` (a pure capacity model) ignores.
    """
    del v_max  # shape-independent: the pack is 2 flops/granule regardless
    c_hat = _round_up(nc, bc)
    k_hat = _round_up(n_bins, bk)
    g_hat = _round_up(g, bg)
    c_blocks = c_hat // bc
    k_tiles = k_hat // bk
    steps = c_blocks * k_tiles * (g_hat // bg)
    flops = (float(c_hat) * k_hat * g_hat * (1 + 2 * m)
             + 2.0 * c_hat * g_hat * k_tiles                 # in-register pack
             + float(_EPILOGUE_FLOPS_PER_CELL) * c_hat * k_hat * m)
    hbm = (4.0 * g_hat * c_hat * k_tiles                     # x_t slab rows
           + 4.0 * g_hat * (1 + m) * c_blocks * k_tiles      # shared r_ids+wd, ÷BC
           + 4.0 * c_hat)                                    # θ' scalars out
    trans = 0.0 if delta == "PR" else float(c_hat) * k_hat * m
    return KernelCost(flops, hbm, sweep_working_set_bytes(bc, bk, bg, m),
                      steps, trans)


def kernel_cost(kernel: str, nc: int, g: int, n_bins: int, m: int,
                tiles: Sequence[int], *, v_max: int = 1,
                delta: str = "SCE") -> KernelCost:
    """Dispatch by kernel name; ``tiles`` is (bk, bg) or (bc, bk, bg)."""
    if kernel == "contingency":
        return contingency_cost(nc, g, n_bins, m, *tiles)
    if kernel == "fused":
        return fused_cost(nc, g, n_bins, m, *tiles, delta=delta)
    if kernel == "sweep":
        return sweep_cost(nc, g, n_bins, m, *tiles, v_max=v_max, delta=delta)
    raise ValueError(
        f"unknown kernel: {kernel!r} (one of: contingency, fused, sweep)")


def modeled_time_s(cost: KernelCost) -> float:
    """Roofline execution-time estimate: max(compute, memory) + dispatch.

    Reuses :func:`repro.launch.roofline.roofline_terms` (the project's one
    home for hardware constants); the added per-grid-step overhead makes the
    dispatch-bound regime — many tiny steps — visible to the ranking.
    """
    terms = roofline_terms(cost.flops, cost.hbm_bytes, 0.0)
    return terms["bound_s"] + cost.grid_steps * GRID_STEP_OVERHEAD_S


def feasible_tiles(kernel: str, nc: int, g: int, n_bins: int, m: int,
                   *, vmem_budget: int = VMEM_BUDGET_BYTES
                   ) -> Tuple[Tuple[int, ...], ...]:
    """Aligned candidate tilings under the VMEM budget.

    BK stays sublane-aligned and never exceeds the padded bin count by more
    than one tile (no all-padding bin tiles); BG is lane-aligned and capped
    one tile above the granule count; sweep BC is a sublane multiple capped
    one block above nc.
    """
    k_cap = _round_up(max(n_bins, 1), SUBLANE)
    g_cap = _round_up(max(g, 1), LANE)
    bks = [bk for bk in CANDIDATE_BK if bk // 2 < k_cap] or [SUBLANE]
    bgs = [bg for bg in CANDIDATE_BG if bg // 2 < g_cap] or [LANE]
    out = []
    if kernel == "sweep":
        bcs = [bc for bc in CANDIDATE_BC if bc // 2 < max(nc, 1)] or [SUBLANE]
        for bc in bcs:
            for bk in bks:
                for bg in bgs:
                    if sweep_working_set_bytes(bc, bk, bg, m) <= vmem_budget:
                        out.append((bc, bk, bg))
    else:
        for bk in bks:
            for bg in bgs:
                if working_set_bytes(bk, bg, m) <= vmem_budget:
                    out.append((bk, bg))
    # Never empty: the smallest aligned tile is the floor.
    if not out:
        out = [(SUBLANE, SUBLANE, LANE)] if kernel == "sweep" else [(SUBLANE, LANE)]
    return tuple(out)


def rank_tiles(kernel: str, nc: int, g: int, n_bins: int, m: int, *,
               v_max: int = 1, delta: str = "SCE",
               candidates: Optional[Iterable[Sequence[int]]] = None
               ) -> Tuple[Tuple[Tuple[int, ...], KernelCost, float], ...]:
    """Candidates sorted by modeled time (deterministic tie-break).

    Returns ``((tiles, cost, time_s), ...)`` ascending; ties prefer the
    larger tile area (fewer grid steps on hardware), then the lexicographic
    tiling — so the rank is a pure function of the shape.
    """
    if candidates is None:
        cands = feasible_tiles(kernel, nc, g, n_bins, m)
    else:
        cands = tuple(tuple(int(t) for t in c) for c in candidates)
    scored = []
    for tiles in cands:
        cost = kernel_cost(kernel, nc, g, n_bins, m, tiles,
                           v_max=v_max, delta=delta)
        scored.append((tiles, cost, modeled_time_s(cost)))
    area = lambda t: t[0][-1] * t[0][-2]   # bk·bg (bc excluded: pure reuse)
    scored.sort(key=lambda s: (s[2], -area(s), s[0]))
    return tuple(scored)


def select_tiles(kernel: str, nc: int, g: int, n_bins: int, m: int, *,
                 v_max: int = 1, delta: str = "SCE") -> Tuple[int, ...]:
    """The analytic selector: best modeled tiling for the shape."""
    return rank_tiles(kernel, nc, g, n_bins, m, v_max=v_max, delta=delta)[0][0]


# ---------------------------------------------------------------------------
# ladder-rung pruning (plan.ladder_rungs selector="analytic", DESIGN.md §5.3)
# ---------------------------------------------------------------------------

# Keep a smaller rung only if it saves at least this fraction of the modeled
# per-iteration eval traffic vs the next kept rung above it.
LADDER_MIN_SAVING = 0.15


def rung_eval_cost_bytes(rung: int, g: int, m: int) -> float:
    """Modeled per-candidate eval traffic at bin bound ``rung``.

    Granule-proportional fixed term (read the candidate slab row + shared
    r_ids + wd stream — paid at every rung) plus the bin-proportional term
    (zero-init and θ'-read of the ``[rung, m]`` counts) — the
    padding-vs-traffic tradeoff the ladder exists to manage.  Deliberately a
    function of (G, m) only: nc scales both terms linearly and cancels, so
    host loop, device engine and mesh driver — whose mp_chunks differ —
    derive the *same* rung set from the same granularity (the cross-driver
    parity contract of §5.3).
    """
    fixed = 4.0 * g * (2 + m)
    per_bin = 2.0 * 4.0 * rung * m
    return fixed + per_bin


def prune_ladder_rungs(rungs: Sequence[int], g: int, m: int, *,
                       min_saving: float = LADDER_MIN_SAVING
                       ) -> Tuple[int, ...]:
    """Drop rungs whose modeled eval saving is below ``min_saving``.

    Walks down from the (always kept) exact top rung and keeps a rung only
    if it cuts the modeled eval cost of the last kept rung by at least
    ``min_saving``.  The result is a subset of the input closed over the top
    rung, so every §5.3 structural invariant — pow2-multiple-of-tile rungs,
    exact top, prefix property, shard-count divisibility — is inherited, and
    the rung-invariance lemma makes results byte-identical to the unpruned
    ladder.  Dispatch-bound tables (G·m ≫ K·V·m) collapse to few rungs —
    fewer ``lax.switch`` branches to trace; bin-dominated tables keep the
    full pow2 ladder (each halving saves ~50%).
    """
    rungs = tuple(rungs)
    if len(rungs) <= 1:
        return rungs
    kept = [rungs[-1]]
    for r in reversed(rungs[:-1]):
        if (rung_eval_cost_bytes(r, g, m)
                <= (1.0 - min_saving) * rung_eval_cost_bytes(kept[-1], g, m)):
            kept.append(r)
    return tuple(sorted(kept))
