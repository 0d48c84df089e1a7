"""Evaluation planning: how Θ(D|B) gets computed for batches of candidates.

Two modes (kept separate so §Perf can report the paper-faithful baseline and
the beyond-paper optimized version independently):

* ``spark`` — the direct transliteration of PLAR Algorithm 2: each candidate
  re-keys every granule from scratch (``map``) and groups by sorted key
  (``reduceByKey``).  Cost per candidate per iteration: O(G log G) sort.
* ``incremental`` — beyond-paper: exact class ids of ``U/R`` are maintained
  across iterations, so evaluating ``R ∪ {a}`` is a *pack* (``r·V + v``, O(G))
  followed by a contingency reduction into ``K·V`` exact bins — no sort in the
  loop, and the reduction is a one-hot contraction the MXU executes natively.

Contingency backends (all bit-equivalent, asserted by tests):

* ``segment`` — ``jax.ops.segment_sum`` (best on CPU; XLA scatter-add on TPU).
* ``onehot``  — chunked one-hot matmul (the MXU strategy expressed in XLA).
* ``pallas``  — the Pallas contingency kernel (``repro.kernels.contingency``).

Θ backends (:func:`candidate_theta`, DESIGN.md §5.2) additionally fold the
measure's θ row-reduction into the contingency accumulation so the
``[nc, K, M]`` tensor is never materialized in HBM:

* ``fused``     — the fused contingency→Θ Pallas kernel.
* ``fused_xla`` — the same schedule expressed in XLA: scan over bin tiles,
  θ per finished tile, scalar accumulation (rows = bins, so every tile holds
  complete rows — the property that makes the fusion exact).

Sweep backends (DESIGN.md §5.3) take the *read-once slab* operand form —
a pre-transposed candidate slab ``x_t [nc, G]`` plus the shared class ids
``r_ids [G]`` — and fold the id-packing ``p = r·V + v`` into the reduction,
so ``packed [nc, G]`` never exists as its own buffer:

* ``sweep``     — the multi-candidate Pallas kernel
  (``kernels/contingency/sweep.py``): each granule tile is loaded once and
  reused across a block of candidates.
* ``sweep_xla`` — the host/XLA twin: fused-pack segment contingency + the
  kernel's tile-ordered θ epilogue (:func:`_theta_tiled_raw`), whose
  sequential per-tile accumulation is what gives the §5.3 bin ladder its
  bitwise ladder-on == ladder-off guarantee.

The **bin ladder** (:func:`ladder_rungs`) supplies the static bucket sizes
the drivers select from per iteration: pow2 multiples of the 256-bin tile up
to the run's static bound ``cap·v_max`` (itself always the top rung).
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from . import measures
from .granularity import (
    Granularity,
    exact_class_ids,
    ids_by_sort,
    row_fingerprints,
)

__all__ = [
    "ids_by_sort",
    "subset_ids",
    "candidate_contingency",
    "candidate_theta",
    "contingency_from_ids",
    "theta_for_ids",
    "ladder_rungs",
    "rung_for",
    "theta_tiled_raw",
    "LADDER_TILE",
    "SWEEP_BACKENDS",
]

# Bin-tile width of the ladder/sweep schedules (DESIGN.md §5.3): matches the
# fused kernels' 256-bin tile so every rung is a whole number of θ tiles.
LADDER_TILE = 256


def ladder_rungs(n_bins: int, tile: int = LADDER_TILE, *,
                 selector: str = "heuristic",
                 g: Optional[int] = None,
                 m: Optional[int] = None) -> Tuple[int, ...]:
    """Static bin-bucket ladder for K-adaptive evaluation (DESIGN.md §5.3).

    Ascending pow2 multiples of ``tile`` strictly below ``n_bins``, closed by
    ``n_bins`` itself (the run's exact static bound ``cap·v_max``).  Rung
    properties the drivers rely on:

    * every rung below the top is ``tile·2^i`` — a power-of-two multiple of
      the 256-bin θ tile, so it is divisible by any pow2 data-shard count
      ≤ 256; the top rung ``cap·v_max`` is divisible by the data-shard
      count on the mesh because ``cap = nd·cap_per_shard`` there
      (``reduce_scatter`` keeps tiling at every rung);
    * the top rung is the exact full bound, so selecting "first rung
      ≥ K·v_max" always succeeds (K ≤ cap);
    * a smaller rung's θ tiles are a *prefix* of a larger rung's: rungs
      below the top are whole tile counts, and a top rung that is not
      (non-pow2 ``cap``, or ``cap < tile``) gets its trailing partial tile
      zero-padded by :func:`_theta_tiled_raw` — all-zero rows with θ' = 0,
      so the prefix/bit-parity argument is unaffected.

    ``selector="analytic"`` (with the granule count ``g`` and decision width
    ``m``) additionally prunes the pow2 set by the modeled padding-vs-traffic
    tradeoff (``kernels/contingency/model.prune_ladder_rungs``): a rung
    survives only if it saves a meaningful fraction of the per-iteration eval
    cost — dispatch-bound tables (G ≫ K·V) collapse to few rungs, fewer
    ``lax.switch`` branches.  The pruned set is a subset of the pow2 set
    closed over the exact top rung, so every invariant above is inherited and
    results stay byte-identical (the §5.3 rung-invariance lemma).  Other
    selector values (``heuristic``/``pinned``) keep the full pow2 ladder.
    """
    rungs = []
    b = tile
    while b < n_bins:
        rungs.append(b)
        b *= 2
    rungs.append(n_bins)
    if selector == "analytic" and g is not None and m is not None:
        from repro.kernels.contingency.model import prune_ladder_rungs

        return prune_ladder_rungs(rungs, int(g), int(m))
    return tuple(rungs)


def rung_for(k: int, v_max: int, rungs: Sequence[int]) -> int:
    """Host-side rung selection: smallest rung ≥ K·v_max.

    The host twin of the device engine's ``_rung_index`` — load-bearing for
    host/device ladder parity, so both drivers share this one definition.
    """
    need = max(k, 1) * v_max
    return next(r for r in rungs if r >= need)


def subset_ids(gran: Granularity, cols: jnp.ndarray, *, exact: bool, seed: int = 0):
    """Class ids of ``U/B`` for the column subset B (dynamic index array).

    ``exact`` ids are sort-free (:func:`~repro.core.granularity.
    exact_class_ids`) and number the classes as a lexsort on B's columns
    would; otherwise the classes are those of B's fingerprint pair.
    """
    x_sub = gran.x[:, cols]
    if exact:
        return exact_class_ids(x_sub, gran.valid, radix=gran.v_max)
    keys = [row_fingerprints(x_sub, seed + 7919), row_fingerprints(x_sub, seed)]
    return ids_by_sort(keys, gran.valid)


# ---------------------------------------------------------------------------
# Contingency backends: packed ids [nc, G] → counts [nc, n_bins, m]
# ---------------------------------------------------------------------------


def _cont_segment(packed, d, w, valid, n_bins, m):
    w_ = jnp.where(valid, w, 0).astype(jnp.float32)

    def one(p):
        seg = jnp.where(valid, p * m + d, n_bins * m)  # padding → dropped bin
        return jax.ops.segment_sum(w_, seg, num_segments=n_bins * m + 1)[:-1].reshape(n_bins, m)

    return jax.vmap(one)(packed)


def _cont_onehot(packed, d, w, valid, n_bins, m, *, bin_chunk: int = 512):
    """One-hot contraction, chunked over bins — mirrors the TPU MXU strategy."""
    w_ = jnp.where(valid, w, 0).astype(jnp.float32)
    wd = w_[:, None] * jax.nn.one_hot(d, m, dtype=jnp.float32)  # [G, m]
    n_chunks = -(-n_bins // bin_chunk)
    pad_bins = n_chunks * bin_chunk

    def chunk(c, _):
        base = c * bin_chunk
        bins = base + jnp.arange(bin_chunk)
        onehot = (packed[:, :, None] == bins[None, None, :]).astype(jnp.float32)  # [nc, G, BK]
        return c + 1, jnp.einsum("cgk,gm->ckm", onehot, wd)

    _, chunks = jax.lax.scan(chunk, 0, None, length=n_chunks)  # [n_chunks, nc, BK, m]
    cont = jnp.moveaxis(chunks, 0, 1).reshape(packed.shape[0], pad_bins, m)
    return cont[:, :n_bins, :]


def _cont_pallas(packed, d, w, valid, n_bins, m, *, selector=None):
    from repro.kernels.contingency.ops import contingency as _kernel

    w_ = jnp.where(valid, w, 0).astype(jnp.float32)
    return _kernel(packed, d, w_, n_bins=n_bins, n_dec=m, selector=selector)


@partial(jax.jit, static_argnames=("n_bins", "m", "backend", "selector"))
def candidate_contingency(
    packed: jnp.ndarray,
    d: jnp.ndarray,
    w: jnp.ndarray,
    valid: jnp.ndarray,
    *,
    n_bins: int,
    m: int,
    backend: str = "segment",
    selector: Optional[str] = None,
) -> jnp.ndarray:
    """counts[c, k, j] = Σ_g w_g · 1[packed[c,g] = k] · 1[d_g = j].

    The paper's REDUCE phase for a *batch* of candidates at once (MP × DP).
    ``selector`` picks the Pallas tile-selection mode (None = analytic
    default); the XLA backends have no tiles and ignore it.
    """
    if backend == "segment":
        return _cont_segment(packed, d, w, valid, n_bins, m)
    if backend == "onehot":
        return _cont_onehot(packed, d, w, valid, n_bins, m)
    if backend == "pallas":
        return _cont_pallas(packed, d, w, valid, n_bins, m,
                            selector=selector)
    raise ValueError(f"unknown contingency backend: {backend}")


def _theta_fused_xla_raw(delta, packed, d, w, valid, *, n_bins, m, bin_chunk: int = 256):
    """XLA rendition of the fused kernel's schedule (DESIGN.md §5.2).

    Rows of the contingency table are bins, so a bin tile always holds
    *complete* rows — the unnormalized θ' can be applied per tile and the
    [nc, K, M] tensor is reduced to a scalar per candidate inside the scan
    carry.  This is what the Pallas kernel does on TPU, expressed for
    backends without Pallas support.

    Returns *raw* partials: like the Pallas kernel, normalization stays with
    the caller so raw sums/psums happen first and the measure's division
    happens exactly once — keeping Θ_PR integer-exact across tilings and
    shards (the determinism note in ``measures.evaluate``).
    """
    w_ = jnp.where(valid, w, 0).astype(jnp.float32)
    wd = w_[:, None] * jax.nn.one_hot(d, m, dtype=jnp.float32)  # [G, m]
    n_chunks = -(-n_bins // bin_chunk)

    def chunk(carry, c):
        bins = c * bin_chunk + jnp.arange(bin_chunk)
        onehot = (packed[:, :, None] == bins[None, None, :]).astype(jnp.float32)
        tile = jnp.einsum("cgk,gm->ckm", onehot, wd)          # [nc, BK, m]
        return carry + measures.RAW_ROWS[delta](tile).sum(-1), None

    # Bins ≥ n_bins never occur in `packed`, so overhang tiles hold all-zero
    # rows with θ' = 0 — no unpadding needed.
    raw, _ = jax.lax.scan(
        chunk, jnp.zeros((packed.shape[0],), jnp.float32),
        jnp.arange(n_chunks))
    return raw


def _theta_fused_xla(delta, packed, d, w, valid, n, *, n_bins, m, bin_chunk: int = 256):
    """Normalized Θ via the fused XLA schedule (single-process path)."""
    raw = _theta_fused_xla_raw(
        delta, packed, d, w, valid, n_bins=n_bins, m=m, bin_chunk=bin_chunk)
    return measures.theta_scale(delta, raw, n)


# ---------------------------------------------------------------------------
# sweep backend: read-once candidate slab + tile-ordered θ (DESIGN.md §5.3)
# ---------------------------------------------------------------------------


def sweep_contingency(x_t, r_ids, d, w, valid, *, v_max: int, n_bins: int, m: int):
    """Fused-pack contingency: slab ``x_t [nc, G]`` + shared ``r_ids [G]``.

    The id-packing ``p = r·V + v`` folds into the per-candidate segment
    expression, so ``packed [nc, G]`` is never staged as its own buffer —
    the XLA twin of the sweep kernel's in-register pack.  Counts are
    scatter-adds of integer-valued f32 weights: exact and order-independent
    below 2²⁴, so the result is bit-identical to the ``segment`` backend's
    pack-then-reduce for every bin bound ≥ K·V.
    """
    w_ = jnp.where(valid, w, 0).astype(jnp.float32)
    d32 = d.astype(jnp.int32)

    def one(x_row):
        seg = jnp.where(valid, (r_ids * v_max + x_row) * m + d32, n_bins * m)
        return jax.ops.segment_sum(
            w_, seg, num_segments=n_bins * m + 1)[:-1].reshape(n_bins, m)

    return jax.vmap(one)(x_t)


def _theta_tiled_raw(delta, cont, *, tile: int = LADDER_TILE):
    """Sequential per-tile θ' accumulation over bin tiles — the sweep
    kernel's epilogue order expressed on a materialized contingency.

    ``cont [nc, nb, m]`` is split into ``ceil(nb/tile)`` bin tiles (trailing
    tile zero-padded) and θ' is accumulated tile-by-tile in ascending order
    via a scan carry: a fixed-length within-tile reduction plus a sequential
    chain of f32 scalar adds.  This is the load-bearing structure of the bin
    ladder's bit-parity guarantee (DESIGN.md §5.3): a smaller rung's tiles
    are a prefix of a larger rung's, and every dropped trailing tile holds
    only all-zero rows whose θ' is exactly 0 — adding exact zeros in the
    same order cannot change the f32 value.
    """
    nc, nb, m = cont.shape
    n_tiles = -(-nb // tile)
    if n_tiles * tile != nb:
        cont = jnp.pad(cont, ((0, 0), (0, n_tiles * tile - nb), (0, 0)))
    tiles = jnp.moveaxis(cont.reshape(nc, n_tiles, tile, m), 1, 0)

    def step(carry, tile_cont):
        return carry + measures.RAW_ROWS[delta](tile_cont).sum(-1), None

    raw, _ = jax.lax.scan(step, jnp.zeros((nc,), jnp.float32), tiles)
    return raw


# Public alias: the ensemble engine (core/engine.py) composes the sweep
# epilogue with a per-config measure switch, so the tile-ordered accumulation
# — the structure the §5.3 bitwise rung-invariance rests on — is part of the
# module's contract, not an implementation detail.
theta_tiled_raw = _theta_tiled_raw


def _theta_sweep_xla(delta, x_t, r_ids, d, w, valid, n, *, v_max, n_bins, m):
    """Normalized Θ via the sweep schedule: fused-pack contingency +
    tile-ordered θ epilogue (single-process / per-shard-local path)."""
    cont = sweep_contingency(
        x_t, r_ids, d, w, valid, v_max=v_max, n_bins=n_bins, m=m)
    return measures.theta_scale(delta, _theta_tiled_raw(delta, cont), n)


SWEEP_BACKENDS = ("sweep", "sweep_xla")


@partial(jax.jit, static_argnames=("delta", "n_bins", "m", "backend",
                                   "v_max", "selector"))
def candidate_theta(
    delta: str,
    packed: jnp.ndarray,
    d: jnp.ndarray,
    w: jnp.ndarray,
    valid: jnp.ndarray,
    n,
    *,
    n_bins: int,
    m: int,
    backend: str = "segment",
    x_t: Optional[jnp.ndarray] = None,
    r_ids: Optional[jnp.ndarray] = None,
    v_max: Optional[int] = None,
    selector: Optional[str] = None,
) -> jnp.ndarray:
    """Θ(D|B∪{a})[c] for a batch of candidates — the full MAP+REDUCE+sum.

    ``segment``/``onehot``/``pallas`` materialize the contingency and reduce
    it with :func:`repro.core.measures.evaluate`; ``fused``/``fused_xla`` fold
    the θ epilogue into the accumulation (DESIGN.md §5.2) and never build the
    [nc, K, M] tensor.

    The sweep backends (DESIGN.md §5.3) take the read-once slab operands
    ``x_t [nc, G]`` + ``r_ids [G]`` + static ``v_max`` instead of ``packed``
    (pass ``packed=None``): the pack is fused into the reduction and θ runs
    as the tile-ordered epilogue, so ``n_bins`` may be any §5.3 ladder rung
    ≥ K·V with bitwise-identical results across rungs.
    """
    if backend in SWEEP_BACKENDS:
        if x_t is None or r_ids is None or v_max is None:
            raise ValueError(
                f"backend={backend!r} takes the slab operand form: pass "
                "x_t=, r_ids=, v_max= (and packed=None)")
        if backend == "sweep":
            from repro.kernels.contingency.ops import sweep_theta

            w_ = jnp.where(valid, w, 0).astype(jnp.float32)
            return sweep_theta(
                x_t, r_ids, d, w_, n, delta=delta, v_max=v_max,
                n_bins=n_bins, n_dec=m, selector=selector)
        return _theta_sweep_xla(
            delta, x_t, r_ids, d, w, valid, n, v_max=v_max, n_bins=n_bins,
            m=m)
    if backend == "fused":
        from repro.kernels.contingency.ops import fused_theta

        w_ = jnp.where(valid, w, 0).astype(jnp.float32)
        return fused_theta(
            packed, d, w_, n, delta=delta, n_bins=n_bins, n_dec=m,
            selector=selector)
    if backend == "fused_xla":
        return _theta_fused_xla(delta, packed, d, w, valid, n, n_bins=n_bins, m=m)
    if backend not in ("segment", "onehot", "pallas"):
        raise ValueError(
            f"unknown Θ backend: {backend!r} "
            "(one of: segment, onehot, pallas, fused, fused_xla, sweep, "
            "sweep_xla)")
    cont = candidate_contingency(
        packed, d, w, valid, n_bins=n_bins, m=m, backend=backend,
        selector=selector)
    return measures.evaluate(delta, cont, n)


def contingency_from_ids(ids, d, w, valid, *, n_bins: int, m: int) -> jnp.ndarray:
    """Single-subset contingency [n_bins, m] (used for Θ(D|R), Θ(D|C), core)."""
    return candidate_contingency(ids[None, :], d, w, valid, n_bins=n_bins, m=m)[0]


def theta_for_ids(delta: str, ids, gran: Granularity, *, n_bins: int):
    """Θ(D|B) given exact class ids of U/B."""
    cont = contingency_from_ids(ids, gran.d, gran.w, gran.valid, n_bins=n_bins, m=gran.n_dec)
    return measures.evaluate(delta, cont, gran.n_total)
