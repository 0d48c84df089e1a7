"""Distributed PLAR: the paper's MDP (model + data parallelism) on a mesh.

Mapping (DESIGN.md §2) — this *is* the paper's architecture, re-expressed:

    Spark construct                     mesh construct
    -----------------------------------------------------------------
    RDD granule partitions, .cache()    granule arrays sharded over ('pod','data'), HBM-resident
    MP process pool over candidates     candidate axis sharded over 'model'
    map (re-key onto B∪D)               packed ids  p = r·V + x[:,a]   (local)
    reduceByKey                         per-shard contingency + psum over data axes
    driver sum()                        θ rows summed on-shard (redundantly, post-psum)
    driver argmax                       host argmin over the gathered [A] thetas

Three collective schedules for the contingency merge (the §Perf knob):

* ``all_reduce``      — paper-faithful DP: every data shard psums the full
  ``[nc_loc, K·V, m]`` contingency, then reduces θ locally.
* ``reduce_scatter``  — beyond-paper: each shard reduces θ over its *slice*
  of contingency rows (θ is row-separable, Eq. 8!) and a scalar psum merges.
  Halves collective bytes and distributes the θ flops; exact because
  Θ(D|B) = Σ_i θ(S_i) commutes with row partitioning.
* ``fused``           — beyond-paper (DESIGN.md §5.2): the driver re-shards
  granules between iterations so every *current class* lives on one data
  shard.  Then every packed key ``p = r·V + v`` — for every candidate — is
  shard-local, each contingency row is complete on exactly one shard, and a
  shard's fused contingency→Θ partial (θ of a row absent from the shard is
  exactly 0) psums to the exact Θ[c]: cross-device payload O(nc·K·m) → O(nc).
  Iterations whose class sizes don't pack into the per-shard capacity (e.g.
  the first ones, where few large classes exist but K — and so the payload —
  is still small) fall back to ``all_reduce`` transparently.

Correctness notes:
* Per-shard granularity tables may hold duplicate keys across shards — the
  contingency sum is key-additive, so dedup is an optional memory
  optimization (``dedup_granules``), never a correctness requirement.  The
  same property licenses *streaming* ingestion (``source=``): each shard
  folds its slice of every chunk through the granularity monoid merge
  (DESIGN.md §3.6) instead of staging the sharded full table.
* Id compaction uses the presence-bitmap/psum construction whose
  shard-consistency is proven by ``test_compact_ids_commute_with_merge``.
* The attribute core (one-time, paper lines 3–8) is computed on gathered
  granule tables — G ≪ N after GrC init; the greedy hot loop is fully
  distributed.
"""
from __future__ import annotations

import dataclasses
import time
from functools import lru_cache, partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import measures
from ..distributed.api import shard_map
from .engine import (
    SelectionState,
    _Cfg,
    _MeshColl,
    _advance,
    _eval_mesh,
    _make_cond_body,
    _mesh_cand_slab,
    init_state,
    merge_candidate_cont,
    run_engine,
)
from .granularity import (
    Granularity,
    build_granularity,
    finish_fold,
    fold_chunk,
    next_pow2,
    with_capacity,
)
from .plan import contingency_from_ids, ladder_rungs, rung_for
from .reduction import (
    ReductionResult,
    _check_source_args,
    _core_inner_thetas,
    _materialize,
    _next_pow2,
)


def _data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _n_data_shards(mesh: Mesh) -> int:
    n = 1
    for a in _data_axes(mesh):
        n *= mesh.shape[a]
    return n


def _n_model_shards(mesh: Mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1


# ---------------------------------------------------------------------------
# sharded evaluation / advance steps
# ---------------------------------------------------------------------------


def _eval_step(mesh: Mesh, delta: str, n_bins: int, m: int, v_max: int,
               collective: str, *, table_dtype: str = "int32",
               fused_pack: bool = False, backend: str = "segment"):
    """shard_map: candidates over 'model' × granules over data → thetas [A].

    §Perf knobs: ``table_dtype="int8"`` stores the granule table x/d in one
    byte per cell (v_max < 128), quartering the dominant column-read traffic;
    ``fused_pack`` folds the id-packing arithmetic into the per-candidate
    segment expression instead of materializing ``packed [A_loc, G_loc]``;
    ``backend="sweep_xla"`` (DESIGN.md §5.3) is that same fused-pack
    formulation — in this host-dispatched step the candidate set changes
    every iteration, so there is no loop-invariant slab to hoist and the
    column-wise pack is the read-once form.  ``n_bins`` may be any §5.3
    ladder rung ≥ K·V.
    """
    # thin wrapper: defaulted and keyword calls must share one lru entry
    # (the single-compile contract — same normalization as make_engine_run)
    return _eval_step_cached(mesh, delta, n_bins, m, v_max, collective,
                             table_dtype, fused_pack or backend == "sweep_xla")


@lru_cache(maxsize=None)
def _eval_step_cached(mesh, delta, n_bins, m, v_max, collective, table_dtype,
                      fused_pack):
    daxes = _data_axes(mesh)
    nd = _n_data_shards(mesh)

    def local(cand_cols, r_ids, x, d, w, valid, n):
        # cand_cols [A_loc]; r_ids/d/w/valid [G_loc]; x [G_loc, A]
        d32 = d.astype(jnp.int32)
        w_ = jnp.where(valid, w, 0).astype(jnp.float32)

        if collective == "fused":
            # Per-shard fused contingency→Θ partial + scalar psum.  Exact only
            # under the driver's class-grouped placement (module docstring):
            # rows this shard doesn't own are all-zero and contribute θ' = 0.
            # Raw partials are psum'd *before* the single normalization so
            # Θ_PR stays integer-exact across shard counts (tie-breaking
            # determinism, see measures.evaluate).
            from .plan import _theta_fused_xla_raw

            x_cand = jnp.take(x, cand_cols, axis=1).T.astype(jnp.int32)
            packed = r_ids[None, :] * v_max + x_cand          # [A_loc, G_loc]
            raw = _theta_fused_xla_raw(
                delta, packed, d32, w, valid, n_bins=n_bins, m=m)
            return measures.theta_scale(delta, jax.lax.psum(raw, daxes), n)

        if fused_pack:
            def one(col):
                x_col = jnp.take(x, col, axis=1).astype(jnp.int32)
                seg = jnp.where(valid, (r_ids * v_max + x_col) * m + d32,
                                n_bins * m)
                return jax.ops.segment_sum(w_, seg, num_segments=n_bins * m + 1)[:-1]

            cont = jax.vmap(one)(cand_cols).reshape(-1, n_bins, m)
        else:
            x_cand = jnp.take(x, cand_cols, axis=1).T.astype(jnp.int32)
            packed = r_ids[None, :] * v_max + x_cand              # [A_loc, G_loc]

            def one(p):
                seg = jnp.where(valid, p * m + d32, n_bins * m)
                return jax.ops.segment_sum(w_, seg, num_segments=n_bins * m + 1)[:-1]

            cont = jax.vmap(one)(packed).reshape(-1, n_bins, m)   # [A_loc, nb, m]
        # all_reduce / reduce_scatter schedules shared with the device engine
        return merge_candidate_cont(
            delta, cont, n, _MeshColl(daxes, nd, False), collective)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P("model"), P(daxes), P(daxes, None), P(daxes), P(daxes),
                  P(daxes), P()),
        out_specs=P("model"),
        check_vma=False,
    )
    return jax.jit(fn)


@lru_cache(maxsize=None)
def _advance_step(mesh: Mesh, delta: str, n_bins: int, m: int, v_max: int):
    """shard_map: fold the winning attribute into the shared reduction state.

    The pack → presence-psum → rank → contingency body is the engine's
    ``_advance`` with a mesh collective adapter — one copy of the
    shard-consistent compaction logic (DESIGN.md §3.1) for both drivers.
    """
    daxes = _data_axes(mesh)
    nd = _n_data_shards(mesh)
    # only delta/m/v_max and the bin bound matter to _advance; n_bins here is
    # the caller's (possibly bins_for-laddered) bound, always a v_max multiple
    cfg = _Cfg(delta, "incremental", "segment", 0, n_bins // v_max, m, v_max,
               0.0, 0.0, False, 0, 1)

    def local(a_col, r_ids, d, w, valid, n):
        coll = _MeshColl(daxes, nd, False)
        new_ids, k_new, theta, _g_pure = _advance(
            cfg, coll, r_ids, a_col, d, w, valid, n)
        return new_ids, k_new, theta

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(daxes), P(daxes), P(daxes), P(daxes), P(daxes), P()),
        out_specs=(P(daxes), P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


@lru_cache(maxsize=None)
def _engine_run_mesh(mesh: Mesh, delta: str, n_attrs: int, cap: int, m: int,
                     v_max: int, tol: float, tie_tol: float, collective: str,
                     max_sel: int, backend: str = "segment",
                     ladder: bool = False, selector: str = "analytic"):
    """The device-resident greedy core (engine.py) wrapped in ``shard_map``.

    One jitted while_loop runs the entire reduction: granules stay sharded
    over the data axes, candidates over 'model', and the per-iteration
    contingency merge uses the ``all_reduce``/``reduce_scatter`` collectives
    of :func:`_eval_step` — but with zero host round-trips between
    iterations.  The loop's cond/body are *the same code* the single-process
    driver runs (engine._make_cond_body); only the collective adapter
    differs.  ``n_bins = cap·v_max`` bounds the global packed-id range for
    every iteration, so the loop compiles exactly once.

    The ``fused`` collective is excluded: its class regrouping stages granule
    tables through the host between iterations (module docstring), which is
    fundamentally a host-loop schedule.
    """
    daxes = _data_axes(mesh)
    nd = _n_data_shards(mesh)
    nm = _n_model_shards(mesh)
    has_model = "model" in mesh.axis_names
    # cfg.cap is the *global* capacity: r_ids are globally-dense, so the
    # packed-id bound K·V ≤ cap·V must cover all shards together.  The MP
    # level on the mesh is the 'model' axis itself, so mp_chunk is inert.
    cfg = _Cfg(delta, "incremental", backend, n_attrs, cap, m, v_max,
               tol, tie_tol, False, max_sel, n_attrs, ladder, selector)

    def local(st, x, d, w, n, theta_full, core_attrs, core_count):
        coll = _MeshColl(daxes, nd, has_model)
        # this shard's candidate slab, gathered+transposed once per run —
        # not per iteration (the §5.3 hoist, same as the local engine's x.T)
        x_tl = _mesh_cand_slab(cfg, coll, nm, x)
        cond, body = _make_cond_body(
            cfg, coll,
            lambda s: _eval_mesh(cfg, coll, collective, s, x_tl, d, w, n),
            x, d, w, n, theta_full, core_attrs, core_count)
        return jax.lax.while_loop(cond, body, st)

    state_specs = SelectionState(
        r_ids=P(daxes), h1=P(daxes), h2=P(daxes), active=P(daxes),
        remaining=P(), theta_history=P(), order=P(), k=P(), theta_r=P(),
        pr_correction=P(), n_selected=P())
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(state_specs, P(daxes, None), P(daxes), P(daxes), P(), P(),
                  P(), P()),
        out_specs=state_specs,
        check_vma=False,
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# distributed GrC build
# ---------------------------------------------------------------------------


def shard_decision_table(x: np.ndarray, d: np.ndarray, mesh: Mesh):
    """Place the raw table row-sharded over the data axes (the HDFS load)."""
    nd = _n_data_shards(mesh)
    n, a = x.shape
    n_pad = -(-n // nd) * nd
    xp = np.zeros((n_pad, a), np.int32)
    dp = np.zeros((n_pad,), np.int32)
    vp = np.zeros((n_pad,), bool)
    xp[:n], dp[:n], vp[:n] = x, d, True
    daxes = _data_axes(mesh)
    sh = lambda *spec: NamedSharding(mesh, P(*spec))
    return (
        jax.device_put(xp, sh(daxes, None)),
        jax.device_put(dp, sh(daxes)),
        jax.device_put(vp, sh(daxes)),
    )


def _data_shard_devices(mesh: Mesh) -> List:
    """One device per data shard: the first device of the mesh that holds
    row block ``s`` of an array sharded over the data axes."""
    nd = _n_data_shards(mesh)
    sharding = NamedSharding(mesh, P(_data_axes(mesh)))
    devs = [None] * nd
    for dev, idx in sharding.devices_indices_map((nd,)).items():
        s = idx[0].start or 0
        if devs[s] is None:
            devs[s] = dev
    return devs


def _shard_granularities_to_mesh(shard_grans, mesh: Mesh):
    """Place per-shard granule tables (equal capacities) row-sharded on the
    mesh: shard ``s``'s table goes to the devices that hold row block ``s``
    (a device-to-device copy where it already lives on one of them), and
    the global array is assembled from those pieces — no host round trip,
    and no shard staged through one device."""
    daxes = _data_axes(mesh)
    cap_ps = shard_grans[0].capacity

    def assemble(field, spec):
        parts = [getattr(g, field) for g in shard_grans]
        shape = (cap_ps * len(parts),) + tuple(parts[0].shape[1:])
        sharding = NamedSharding(mesh, P(*spec))
        pieces = [jax.device_put(parts[(idx[0].start or 0) // cap_ps], dev)
                  for dev, idx
                  in sharding.addressable_devices_indices_map(shape).items()]
        return jax.make_array_from_single_device_arrays(shape, sharding, pieces)

    return (assemble("x", (daxes, None)), assemble("d", (daxes,)),
            assemble("w", (daxes,)), assemble("valid", (daxes,)))


def _granularity_from_source(source, mesh: Mesh, *, n_dec: int, v_max: int,
                             chunk_rows: int):
    """Distributed GrC init without the sharded full table resident.

    Each data shard folds its slice of every chunk through the streaming
    monoid merge, so peak host memory is O(chunk + Σ per-shard granularity
    capacity) instead of the full ``(n_rows, n_attrs)`` array that
    ``shard_decision_table`` + ``_grc_build_step`` stage.  Chunks iterate
    on the *outside* — each is materialized exactly once and sliced per
    shard (the ``TokenStream.shard`` partition), not re-generated per shard
    (for a real out-of-core reader that would multiply IO by the shard
    count).  Cross-shard duplicate keys are allowed — the contingency sum
    is key-additive (module docstring) — so shards granulate independently,
    exactly like the per-partition combiner of a Spark reduceByKey.  Each
    shard's fold runs on a device that holds that shard.
    """
    nd = _n_data_shards(mesh)
    devs = _data_shard_devices(mesh)
    accs = [None] * nd
    for i in range(source.n_chunks(chunk_rows)):
        xc, dc = source.chunk(i, chunk_rows)
        n = xc.shape[0]
        for s in range(nd):
            lo, hi = s * n // nd, (s + 1) * n // nd
            # each shard folds on its own device (committed inputs pin the
            # jitted build there), not on the default device
            accs[s] = fold_chunk(
                accs[s], jax.device_put(xc[lo:hi], devs[s]),
                jax.device_put(dc[lo:hi], devs[s]), n_dec=n_dec, v_max=v_max)
    accs = [finish_fold(g) for g in accs]
    if any(g is None for g in accs):
        raise ValueError("source yielded no rows for at least one data shard")
    cap_ps = max(next_pow2(max(int(g.num), 16)) for g in accs)
    accs = [with_capacity(g, cap_ps) for g in accs]
    gx, gd, gw, gv = _shard_granularities_to_mesh(accs, mesh)
    n_total = sum(int(g.n_total) for g in accs)
    return gx, gd, gw, gv, n_total


def _granularity_to_mesh(gran: Granularity, mesh: Mesh):
    """Split a prebuilt (host) granularity contiguously over the data shards.

    Live granules are distinct keys, so any row partition of them is a valid
    per-shard granularity table; capacities pad to a common power of two.
    """
    nd = _n_data_shards(mesh)
    live = int(gran.num)
    cap_ps = next_pow2(max(-(-max(live, 1) // nd), 16))
    x, d = np.asarray(gran.x)[:live], np.asarray(gran.d)[:live]
    w, v = np.asarray(gran.w)[:live], np.asarray(gran.valid)[:live]
    shard_grans = []
    for s in range(nd):
        lo, hi = s * live // nd, (s + 1) * live // nd
        g = Granularity(
            x=jnp.asarray(x[lo:hi]), d=jnp.asarray(d[lo:hi]),
            w=jnp.asarray(w[lo:hi]), valid=jnp.asarray(v[lo:hi]),
            num=jnp.int32(hi - lo), n_total=jnp.int32(int(w[lo:hi].sum())),
            n_attrs=gran.n_attrs, n_dec=gran.n_dec, v_max=gran.v_max,
        )
        shard_grans.append(with_capacity(g, cap_ps))
    gx, gd, gw, gv = _shard_granularities_to_mesh(shard_grans, mesh)
    return gx, gd, gw, gv, int(gran.n_total)


@lru_cache(maxsize=None)
def _grc_build_step(mesh: Mesh, n_dec: int, v_max: int, capacity: int):
    """Per-shard GrC initialization (paper lines 1–2).  No cross-shard dedup:
    duplicate keys across shards are weight-additive (module docstring)."""
    daxes = _data_axes(mesh)

    def local(x, d, valid):
        g = build_granularity(
            x, d, n_dec=n_dec, v_max=v_max,
            valid=valid, exact=True, capacity=capacity,
        )
        return g.x, g.d, g.w, g.valid, jax.lax.psum(g.num, daxes), jax.lax.psum(
            g.n_total, daxes)

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(daxes, None), P(daxes), P(daxes)),
        out_specs=(P(daxes, None), P(daxes), P(daxes), P(daxes), P(), P()),
        check_vma=False,
    )
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# class-grouped placement for the fused schedule
# ---------------------------------------------------------------------------


def _regroup_by_class(gx, gd, gw, gvalid, r_ids, mesh):
    """Re-shard granules so each current class id lives on one data shard.

    The precondition of the ``fused`` collective (module docstring).  Classes
    are packed onto shards least-loaded-first (LPT); returns the re-placed
    arrays, or ``None`` when some shard would overflow its static capacity —
    the caller then falls back to ``all_reduce`` for that iteration.
    Feasibility is decided from ``r_ids``/``valid`` alone (O(G) gather); the
    full O(G·A) granule table is pulled to the host only when packing
    succeeds.  The Spark analogue is a ``partitionBy`` on the cached RDD, and
    G ≪ N after GrC init.  (A production mesh implementation would use a
    ragged all-to-all keyed on the class id instead of staging through the
    host.)
    """
    nd = _n_data_shards(mesh)
    if nd == 1:
        # One data shard: class grouping holds trivially, nothing to move.
        return gx, gd, gw, gvalid, r_ids
    daxes = _data_axes(mesh)
    cap = gx.shape[0]
    cps = cap // nd
    vh, rh = np.asarray(gvalid), np.asarray(r_ids)
    live = np.nonzero(vh)[0]
    classes, inverse, counts = np.unique(
        rh[live], return_inverse=True, return_counts=True)

    loads = np.zeros(nd, np.int64)
    assign = np.empty(len(classes), np.int64)
    for ci in np.argsort(-counts):
        s = int(np.argmin(loads))
        if loads[s] + counts[ci] > cps:
            return None
        assign[ci] = s
        loads[s] += counts[ci]

    xh, dh, wh = np.asarray(gx), np.asarray(gd), np.asarray(gw)
    nx = np.zeros_like(xh)
    nd_ = np.zeros_like(dh)
    nw = np.zeros_like(wh)
    nv = np.zeros_like(vh)
    nr = np.zeros_like(rh)
    offsets = np.arange(nd) * cps
    for s in range(nd):
        rows = live[assign[inverse] == s]
        sl = slice(offsets[s], offsets[s] + len(rows))
        nx[sl], nd_[sl], nw[sl], nr[sl] = xh[rows], dh[rows], wh[rows], rh[rows]
        nv[sl] = True

    sh = lambda *spec: NamedSharding(mesh, P(*spec))
    return (
        jax.device_put(nx, sh(daxes, None)),
        jax.device_put(nd_, sh(daxes)),
        jax.device_put(nw, sh(daxes)),
        jax.device_put(nv, sh(daxes)),
        jax.device_put(nr, sh(daxes)),
    )


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def plar_reduce_distributed(
    x=None,
    d=None,
    mesh: Optional[Mesh] = None,
    *,
    source=None,                        # Granularity | GranuleSource (alt. to x, d)
    chunk_rows: int = 65536,            # streaming-ingestion chunk size
    delta: str = "PR",
    n_dec: Optional[int] = None,
    v_max: Optional[int] = None,
    eps: float = 0.0,
    tol: float = 1e-6,
    tie_tol: float = 1e-5,
    max_features: Optional[int] = None,
    collective: str = "all_reduce",     # | "reduce_scatter" | "fused" (§Perf)
    backend: str = "segment",           # | "sweep_xla" (read-once slab, §5.3)
    ladder: bool = False,               # K-adaptive bin ladder (§5.3)
    selector: str = "analytic",         # tile/rung selection mode
    compute_core: bool = True,
    grc_init: bool = True,
    engine: str = "auto",               # "device" while_loop | "host" legacy loop
) -> ReductionResult:
    """PLAR Algorithm 2 on a ('pod','data','model') mesh.  See module doc."""
    t0 = time.perf_counter()
    if collective not in ("all_reduce", "reduce_scatter", "fused"):
        raise ValueError(
            f"unknown collective: {collective!r} "
            "(one of: all_reduce, reduce_scatter, fused)")
    if backend not in ("segment", "sweep_xla"):
        raise ValueError(
            f"unknown mesh Θ backend: {backend!r} (one of: segment, "
            "sweep_xla — the Pallas/interpret backends are single-process)")
    if engine not in ("auto", "host", "device"):
        raise ValueError(
            f"unknown engine: {engine!r} (one of: auto, host, device)")
    from repro.kernels.contingency.autotune import SELECTOR_MODES
    if selector not in SELECTOR_MODES:
        raise ValueError(
            f"unknown selector: {selector!r} "
            f"(one of: {', '.join(SELECTOR_MODES)})")
    if engine == "device" and collective == "fused":
        raise ValueError(
            "engine='device' cannot run the 'fused' collective: its class "
            "regrouping stages granules through the host between iterations; "
            "use engine='host'")
    if collective == "fused" and backend != "segment":
        raise ValueError(
            "collective='fused' has its own fused contingency→Θ schedule; "
            "backend must stay 'segment'")
    if engine == "auto":
        engine = "host" if collective == "fused" else "device"
    if mesh is None:
        raise ValueError("mesh is required")
    _check_source_args(x, d, source)
    nd = _n_data_shards(mesh)
    nm = _n_model_shards(mesh)

    if source is not None:
        # declared source metadata is authoritative on every ingestion path
        # (never re-inferred from whichever classes/values happened to
        # realize) — both Granularity and GranuleSource carry these fields
        n_dec = source.n_dec if n_dec is None else n_dec
        v_max = source.v_max if v_max is None else v_max

    if source is not None and not isinstance(source, Granularity) and not grc_init:
        # HAR cost model (every raw row a granule) has nothing to stream
        # into — materialize, same thin adapter as resolve_granularity.
        x, d = _materialize(source, chunk_rows)
        source = None

    # --- GrC initialization (distributed, cached in device memory) ---
    if isinstance(source, Granularity):
        A = source.n_attrs
        gx, gd, gw, gvalid, n_rows = _granularity_to_mesh(source, mesh)
    elif source is not None:
        A, n_rows = source.n_attrs, source.n_rows
        gx, gd, gw, gvalid, _ = _granularity_from_source(
            source, mesh, n_dec=n_dec, v_max=v_max, chunk_rows=chunk_rows)
    else:
        x = np.asarray(x, np.int32)
        d = np.asarray(d, np.int32)
        if n_dec is None:
            n_dec = int(d.max()) + 1
        if v_max is None:
            v_max = int(x.max()) + 1
        n_rows, A = x.shape
        xs, ds, vs = shard_decision_table(x, d, mesh)
        cap_per_shard = xs.shape[0] // nd
        if grc_init:
            build = _grc_build_step(mesh, n_dec, v_max, cap_per_shard)
            gx, gd, gw, gvalid, _g_num, _n_total = build(xs, ds, vs)
        else:
            gx, gd = xs, ds
            gw = jax.device_put(
                np.ones((xs.shape[0],), np.int32),
                NamedSharding(mesh, P(_data_axes(mesh))))
            gvalid = vs
    n = jnp.float32(n_rows)

    cap = gx.shape[0]
    daxes = _data_axes(mesh)
    sh = lambda *spec: NamedSharding(mesh, P(*spec))

    # --- Θ(D|C) (stop target) + core, on gathered granules (one-time) ---
    gx_h = np.asarray(gx)
    gd_h = np.asarray(gd)
    gw_h = np.asarray(gw)
    gv_h = np.asarray(gvalid)
    gran_h = Granularity(
        x=jnp.asarray(gx_h), d=jnp.asarray(gd_h), w=jnp.asarray(gw_h),
        valid=jnp.asarray(gv_h), num=jnp.int32(int(gv_h.sum())),
        n_total=jnp.int32(n_rows), n_attrs=A, n_dec=n_dec, v_max=v_max,
    )
    from .plan import subset_ids
    ids_c, _ = subset_ids(gran_h, jnp.arange(A, dtype=jnp.int32), exact=True)
    cont_c = contingency_from_ids(ids_c, gran_h.d, gran_h.w, gran_h.valid,
                                  n_bins=cap, m=n_dec)
    theta_full = float(measures.evaluate(delta, cont_c, n))

    core: List[int] = []
    n_evals = 0
    if compute_core:
        inner = _core_inner_thetas(gran_h, delta, exact=True)
        core = [int(a) for a in range(A) if inner[a] - theta_full > eps + tie_tol]
        n_evals += A

    if engine == "device":
        # One shard_map(while_loop) call runs the whole reduction on device;
        # jit places the replicated state leaves per the in_specs.
        max_sel = int(max_features) if max_features is not None else A
        runner = _engine_run_mesh(
            mesh, delta, A, cap, n_dec, v_max, float(tol), float(tie_tol),
            collective, max_sel, backend, bool(ladder), str(selector))
        reduct, theta_hist, iterations, ev, per_iter = run_engine(
            runner, cap, A, gvalid, gx, gd, gw, n, theta_full, core)
        return ReductionResult(
            reduct=reduct,
            core=core,
            theta_full=theta_full,
            theta_history=theta_hist,
            iterations=iterations,
            n_evaluations=n_evals + ev,
            elapsed_s=time.perf_counter() - t0,
            per_iteration_s=per_iter,
        )

    # --- distributed greedy loop state (engine == "host") ---
    r_ids = jax.device_put(np.zeros((cap,), np.int32), sh(daxes))
    k = 1
    reduct: List[int] = []
    theta_hist: List[float] = []
    per_iter_s: List[float] = []

    # Same (cap, m)-only pruning as the single-process drivers — the mesh
    # host loop lands on the identical rung set (§5.3 byte parity).
    rungs = ladder_rungs(cap * v_max, selector=selector, g=cap, m=n_dec)

    def adv_bins_for(k_):
        # The advance bound is ladder-independent (the §5.3 ladder shrinks
        # only the candidate evaluation), so theta histories are identical
        # with the ladder on or off.
        return _next_pow2(max(k_, 1)) * v_max

    def bins_for(k_):
        # Candidate-eval bound.  Ladder on: snap to the §5.3 rungs — every
        # rung is divisible by the (pow2) data-shard count, so
        # reduce_scatter keeps tiling at every K.  Ladder off: the legacy
        # pow2(k)·V bound.
        if ladder:
            return rung_for(k_, v_max, rungs)
        return adv_bins_for(k_)

    for a in core:
        adv = _advance_step(mesh, delta, adv_bins_for(k), n_dec, v_max)
        a_col = jnp.take(gx, a, axis=1)
        r_ids, k_new, theta_r = adv(a_col, r_ids, gd, gw, gvalid, n)
        k = int(k_new)
        reduct.append(a)
        theta_hist.append(float(theta_r))

    theta_r = theta_hist[-1] if theta_hist else float("inf")
    remaining = [a for a in range(A) if a not in reduct]
    iterations = 0
    # f32-mirrored stop threshold: same iteration count as the device cond
    stop_thresh = measures.f32_threshold(theta_full, tol)

    while remaining and theta_r > stop_thresh:
        if max_features is not None and len(reduct) >= max_features:
            break
        it0 = time.perf_counter()
        n_bins = bins_for(k)
        # candidate axis padded to the model-shard multiple (the MP level)
        a_pad = -(-len(remaining) // nm) * nm
        cand = np.full((a_pad,), remaining[-1], np.int32)
        cand[: len(remaining)] = remaining
        cand_dev = jax.device_put(cand, sh("model"))

        iter_collective = collective
        if collective == "fused":
            regrouped = _regroup_by_class(gx, gd, gw, gvalid, r_ids, mesh)
            if regrouped is None:
                # Classes too large to pack (early iterations) — K is small
                # then, so the all_reduce payload O(nc·K·m) is still cheap.
                iter_collective = "all_reduce"
            else:
                gx, gd, gw, gvalid, r_ids = regrouped

        ev = _eval_step(mesh, delta, n_bins, n_dec, v_max, iter_collective,
                        backend=backend)
        thetas = np.asarray(ev(cand_dev, r_ids, gx, gd, gw, gvalid, n), np.float64)
        thetas = thetas[: len(remaining)]
        n_evals += len(remaining)

        best = measures.argmin_with_ties(thetas, tie_tol)
        a_opt = remaining[best]

        adv = _advance_step(mesh, delta, adv_bins_for(k), n_dec, v_max)
        a_col = jnp.take(gx, a_opt, axis=1)
        r_ids, k_new, theta_new = adv(a_col, r_ids, gd, gw, gvalid, n)
        k = int(k_new)
        theta_r = float(theta_new)
        reduct.append(a_opt)
        remaining.remove(a_opt)
        theta_hist.append(theta_r)
        iterations += 1
        per_iter_s.append(time.perf_counter() - it0)

    return ReductionResult(
        reduct=reduct,
        core=core,
        theta_full=theta_full,
        theta_history=theta_hist,
        iterations=iterations,
        n_evaluations=n_evals,
        elapsed_s=time.perf_counter() - t0,
        per_iteration_s=per_iter_s,
    )
