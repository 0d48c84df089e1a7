"""Tile selection for the contingency kernels (DESIGN.md §5.2).

Three selector modes, shared by every kernel entry point
(:func:`resolve_tiles` is the one seam ``ops.py`` calls):

* ``analytic`` — **the default**: the closed-form roofline model of
  :mod:`repro.kernels.contingency.model` ranks every feasible aligned tiling
  and picks the best modeled time.  Free (no compiles), shape-exact, and
  consistent across processes.  Tuned picks persisted by
  :func:`autotune_block_sizes` (keyed by platform × kernel × shape bucket)
  override the model only when ``REPRO_AUTOTUNE_CACHE`` names the file that
  holds them; without it, tiles are a pure function of the shape.
* ``heuristic`` — the PR-1 zero-cost shape rule (:func:`select_block_sizes`):
  largest MXU-aligned tile under the VMEM budget.  Kept as the legacy
  fallback and as a parity baseline for the selector tests.
* ``pinned`` — the kernels' module defaults (``DEFAULT_BK``/``BG``/``BC``),
  for pinning a known tiling in benchmarks and bisections.

:func:`autotune_block_sizes` is the measured refinement: the analytic rank
prunes the candidate grid to a top-k (default 3) **before any timing**, and
timing itself is opt-in (``refine=True``) — in interpret mode timings are
meaningless, and on a TPU each timed candidate costs a compile.  Winners
land in a bounded in-memory LRU, and in the on-disk cache when
``REPRO_AUTOTUNE_CACHE`` names one.
"""
from __future__ import annotations

import json
import logging
import os
import time
from collections import OrderedDict
from pathlib import Path
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from .model import (  # noqa: F401  (public re-exports: the constants' one home)
    LANE,
    SUBLANE,
    VMEM_BUDGET_BYTES,
    feasible_tiles,
    rank_tiles,
    select_tiles,
    sweep_working_set_bytes,
    working_set_bytes,
)

logger = logging.getLogger(__name__)

SELECTOR_MODES = ("heuristic", "analytic", "pinned")
DEFAULT_SELECTOR = "analytic"

# Candidate grid of the *measured* hook when the caller pins one explicitly;
# the default candidate set is the model's feasible enumeration.
CANDIDATE_BK = (128, 256, 512)
CANDIDATE_BG = (256, 512, 1024)

# In-memory tuning cache: bounded LRU (a long-lived service process sweeps
# many (K, G) regimes; the cache must not grow with them unboundedly).
_CACHE_MAXSIZE = 256
_CACHE: "OrderedDict[Tuple, Tuple[int, ...]]" = OrderedDict()
_CACHE_STATS = {"hits": 0, "misses": 0}

# On-disk tuning cache, shared across processes — only at the file that
# ``REPRO_AUTOTUNE_CACHE`` names; there is no default location.
_DISK_ENV = "REPRO_AUTOTUNE_CACHE"
_disk_state: Dict[str, object] = {"path": None, "data": None}


def _round_up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def select_block_sizes(
    n_bins: int,
    g: int,
    m: int,
    *,
    vmem_budget: int = VMEM_BUDGET_BYTES,
) -> Tuple[int, int]:
    """Shape heuristic: largest aligned (BK, BG) fitting the VMEM budget.

    BK never exceeds the padded bin count (no all-padding bin tiles) and BG
    never exceeds the padded granule count; both stay hardware-aligned
    (sublane/lane multiples) so the one-hot matmul runs at full MXU occupancy.
    """
    bk = min(max(_round_up(n_bins, SUBLANE), SUBLANE), 512)
    # Prefer a full 128-row MXU tile when there are enough bins to fill it.
    if n_bins >= LANE:
        bk = max(bk, LANE)
        bk = min(bk, _round_up(n_bins, LANE))
    bg = min(max(_round_up(g, LANE), LANE), 1024)
    while bg > LANE and working_set_bytes(bk, bg, m) > vmem_budget:
        bg //= 2
    while bk > SUBLANE and working_set_bytes(bk, bg, m) > vmem_budget:
        bk = max(_round_up(bk // 2, SUBLANE), SUBLANE)  # halve, stay aligned
    return bk, bg


def _pinned_tiles(kernel: str) -> Tuple[int, ...]:
    if kernel == "sweep":
        from .sweep import DEFAULT_BC, DEFAULT_BG, DEFAULT_BK

        return (DEFAULT_BC, DEFAULT_BK, DEFAULT_BG)
    from .kernel import DEFAULT_BG, DEFAULT_BK

    return (DEFAULT_BK, DEFAULT_BG)


def resolve_tiles(
    kernel: str,
    *,
    nc: int,
    g: int,
    n_bins: int,
    m: int,
    v_max: int = 1,
    delta: str = "SCE",
    selector: Optional[str] = None,
) -> Tuple[int, ...]:
    """The shared tile selector: ``(bk, bg)``, or ``(bc, bk, bg)`` for sweep.

    ``selector=None`` means the default mode (``analytic``).  Resolution is
    pure host Python over concrete ints — the ``ops.py`` wrappers call it
    *outside* (or at trace time of) their jitted bodies, so the chosen tiles
    are ordinary static arguments and no selector state is baked into a
    compiled executable.
    """
    mode = DEFAULT_SELECTOR if selector is None else selector
    if mode not in SELECTOR_MODES:
        raise ValueError(
            f"unknown tile selector: {mode!r} "
            f"(one of: {', '.join(SELECTOR_MODES)})")
    if mode == "pinned":
        tiles = _pinned_tiles(kernel)
        obs.event("autotune.resolve", kernel=kernel, mode=mode,
                  tiles=repr(tiles))
        return tiles
    if mode == "heuristic":
        bk, bg = select_block_sizes(n_bins, g, m)
        if kernel == "sweep":
            from .sweep import DEFAULT_BC

            tiles = (DEFAULT_BC, bk, bg)
        else:
            tiles = (bk, bg)
        obs.event("autotune.resolve", kernel=kernel, mode=mode,
                  tiles=repr(tiles))
        return tiles
    # analytic: a persisted tuning for this (platform, kernel, shape bucket)
    # wins over the model — measured beats modeled when a cache is named.
    tuned = _disk_get(_disk_key(jax.default_backend(), kernel,
                                shape_bucket(nc, g, n_bins, m)))
    if tuned is not None:
        obs.counter("plar_autotune_disk_hits_total",
                    "tile resolutions served from the persisted tuning"
                    ).inc()
        obs.event("autotune.resolve", kernel=kernel, mode="analytic",
                  source="disk", tiles=repr(tuned))
        return tuned
    obs.counter("plar_autotune_disk_misses_total",
                "tile resolutions that fell through to the analytic model"
                ).inc()
    tiles = select_tiles(kernel, nc, g, n_bins, m, v_max=v_max, delta=delta)
    obs.event("autotune.resolve", kernel=kernel, mode="analytic",
              source="model", tiles=repr(tiles))
    return tiles


# ---------------------------------------------------------------------------
# persistent tuning cache: (platform, kernel, shape-bucket) → tiles
# ---------------------------------------------------------------------------


def shape_bucket(nc: int, g: int, n_bins: int, m: int) -> Tuple[int, int, int, int]:
    """Pow2 shape bucket: one tuning covers a ×2 band per axis, so the greedy
    loop's drifting (K, G) regimes hit a handful of entries, not thousands."""

    def p2(v: int) -> int:
        b = 1
        while b < max(v, 1):
            b *= 2
        return b

    return (p2(nc), p2(g), p2(n_bins), p2(m))


def _disk_path() -> Optional[Path]:
    env = os.environ.get(_DISK_ENV)
    return Path(env) if env else None


def _disk_key(platform: str, kernel: str, bucket: Tuple[int, ...]) -> str:
    return f"{platform}|{kernel}|" + "x".join(str(b) for b in bucket)


def _disk_data() -> Dict[str, list]:
    """Lazily loaded disk cache, reloaded when the path changes (tests point
    ``REPRO_AUTOTUNE_CACHE`` at tmp files)."""
    path = _disk_path()
    if _disk_state["path"] != path or _disk_state["data"] is None:
        data: Dict[str, list] = {}
        try:
            if path is not None:
                with open(path) as f:
                    raw = json.load(f)
                if isinstance(raw, dict):
                    data = {str(k): list(v) for k, v in raw.items()
                            if isinstance(v, (list, tuple))}
        except (OSError, ValueError):
            data = {}
        _disk_state["path"] = path
        _disk_state["data"] = data
    return _disk_state["data"]  # type: ignore[return-value]


def _disk_get(key: str) -> Optional[Tuple[int, ...]]:
    val = _disk_data().get(key)
    return tuple(int(v) for v in val) if val else None


def _disk_put(key: str, tiles: Sequence[int]) -> None:
    path = _disk_path()
    if path is None:
        return
    data = _disk_data()
    data[key] = [int(t) for t in tiles]
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_suffix(".tmp")
        with open(tmp, "w") as f:
            json.dump(data, f, indent=0, sort_keys=True)
        os.replace(tmp, path)
    except OSError as e:  # read-only FS etc. — tuning still served from memory
        logger.warning("autotune: could not persist tuning cache to %s: %s",
                       path, e)


# ---------------------------------------------------------------------------
# measured refinement
# ---------------------------------------------------------------------------


def _cache_put(key: Tuple, tiles: Tuple[int, ...]) -> None:
    _CACHE[key] = tiles
    _CACHE.move_to_end(key)
    while len(_CACHE) > _CACHE_MAXSIZE:
        _CACHE.popitem(last=False)


def autotune_cache_clear(disk: bool = False) -> None:
    """Drop all in-memory tunings (and the on-disk cache with ``disk=True``)."""
    _CACHE.clear()
    _CACHE_STATS["hits"] = 0
    _CACHE_STATS["misses"] = 0
    path = _disk_path()
    if disk and path is not None:
        _disk_state["data"] = {}
        try:
            path.unlink(missing_ok=True)
        except OSError:
            pass


def autotune_cache_info() -> Dict[str, object]:
    """Cache observability: sizes, hit/miss counters, disk location."""
    path = _disk_path()
    return {
        "size": len(_CACHE),
        "maxsize": _CACHE_MAXSIZE,
        "hits": _CACHE_STATS["hits"],
        "misses": _CACHE_STATS["misses"],
        "disk_path": None if path is None else str(path),
        "disk_entries": len(_disk_data()),
    }


def _build_candidate_fn(kernel, tiles, packed, wd, x_t, r_ids, *, n_bins,
                        delta, v_max):
    """Zero-arg launcher for one candidate tiling (monkeypatch seam for the
    compile-count tests)."""
    if kernel == "contingency":
        from .kernel import contingency_pallas

        bk, bg = tiles
        return lambda: contingency_pallas(
            packed, wd, n_bins=n_bins, bk=bk, bg=bg)
    if kernel == "fused":
        from .fused import fused_theta_pallas

        bk, bg = tiles
        return lambda: fused_theta_pallas(
            packed, wd, n_bins=n_bins, delta=delta, bk=bk, bg=bg)
    from .sweep import sweep_theta_pallas

    bc, bk, bg = tiles
    return lambda: sweep_theta_pallas(
        x_t, r_ids, wd, v_max=v_max, n_bins=n_bins, delta=delta, bc=bc,
        bk=bk, bg=bg)


def _time_candidate(fn, reps: int) -> float:
    """Best-of-reps wall time; every rep blocks on its own output so async
    dispatch cannot fold rep k's device time into rep k+1's measurement."""
    best = float("inf")
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def autotune_block_sizes(
    nc: int,
    g: int,
    n_bins: int,
    m: int,
    *,
    delta: Optional[str] = None,
    kernel: Optional[str] = None,
    v_max: int = 1,
    reps: int = 3,
    candidates: Optional[Sequence[Sequence[int]]] = None,
    refine: bool = False,
    top_k: int = 3,
    platform: Optional[str] = None,
) -> Tuple[int, ...]:
    """Analytically rank candidate tilings; optionally time the top-k.

    ``delta=None`` tunes the unfused contingency kernel; a measure name tunes
    the fused Θ kernel; ``kernel="sweep"`` (with ``v_max``) tunes the
    multi-candidate sweep kernel — candidates are then ``(bc, bk, bg)``.

    By default (``refine=False``) the pick is the analytic rank's best: zero
    compiles.  ``refine=True`` times the ``top_k`` (default 3) analytically
    best candidates — each rep blocked on its own output — and candidates
    whose compile fails are skipped with a logged warning, never silently.
    Winners are memoized in the bounded in-memory LRU (keyed *including the
    JAX platform* — a CPU tuning must not leak onto TPU) and persisted to the
    on-disk cache that ``REPRO_AUTOTUNE_CACHE`` names, if any, so other
    processes' ``analytic`` selector reuses them.
    """
    if kernel is None:
        kernel = "contingency" if delta is None else "fused"
    if kernel not in ("contingency", "fused", "sweep"):
        raise ValueError(
            f"unknown kernel: {kernel!r} (one of: contingency, fused, sweep)")
    delta_eff = delta or "SCE"
    if platform is None:
        platform = jax.default_backend()
    if candidates is not None:
        candidates = tuple(tuple(int(t) for t in c) for c in candidates)
    key = (platform, kernel, nc, g, n_bins, m, delta, v_max, reps,
           candidates, refine, top_k)
    if key in _CACHE:
        _CACHE_STATS["hits"] += 1
        obs.counter("plar_autotune_cache_hits_total",
                    "autotune LRU hits").inc()
        _CACHE.move_to_end(key)
        return _CACHE[key]
    _CACHE_STATS["misses"] += 1
    obs.counter("plar_autotune_cache_misses_total",
                "autotune LRU misses (re-ranked/timed)").inc()

    m_pad = _round_up(max(m, 1), LANE)
    ranked = rank_tiles(kernel, nc, g, n_bins, m_pad, v_max=v_max,
                        delta=delta_eff, candidates=candidates)
    best = ranked[0][0]

    if refine and len(ranked) > 1:
        rng = np.random.default_rng(0)
        x_host = rng.integers(0, max(n_bins // max(v_max, 1), 1), (nc, g))
        packed = jnp.asarray(rng.integers(0, n_bins, (nc, g)), jnp.int32)
        x_t = jnp.asarray(x_host, jnp.int32)
        r_ids = jnp.zeros((g,), jnp.int32)
        wd = jnp.zeros((g, m_pad), jnp.float32).at[
            jnp.arange(g), jnp.asarray(rng.integers(0, max(m, 1), (g,)))
        ].set(1.0)

        best_dt = float("inf")
        timed_best = None
        for tiles, _cost, _t in ranked[:top_k]:
            fn = _build_candidate_fn(
                kernel, tiles, packed, wd, x_t, r_ids, n_bins=n_bins,
                delta=delta_eff, v_max=v_max)
            try:
                jax.block_until_ready(fn())            # compile + warm
            except Exception as e:
                logger.warning(
                    "autotune: %s candidate %s failed to compile on %s "
                    "(skipped): %s", kernel, tiles, platform, e)
                continue
            dt = _time_candidate(fn, reps)
            if dt < best_dt:
                timed_best, best_dt = tiles, dt
        if timed_best is not None:
            best = timed_best

    _cache_put(key, best)
    # Persist full-grid ranks and every measured refinement; a rank over a
    # caller-restricted candidate list is not a shape tuning — don't let it
    # shadow the model for the whole bucket.
    if candidates is None or refine:
        _disk_put(_disk_key(platform, kernel, shape_bucket(nc, g, n_bins, m)),
                  best)
    return best
