"""Device-resident greedy selection engine (DESIGN.md §3.5).

The paper's PLAR loop (Algorithm 2) is "cache once, iterate on device", but
the original drivers here were host-driven Python loops: every iteration
synced ``int(k_new)``, gathered thetas to numpy for the argmin, mutated a
Python ``remaining`` list, and re-jitted whenever ``bins_for(k)`` crossed a
power of two.  That is exactly the per-iteration driver round-trip the paper
fights in Spark, reintroduced at small scale.

This module keeps the *whole* reduction on device:

* :class:`SelectionState` — a pytree carrying everything the loop mutates:
  current class ids ``r_ids``, the FSPA shrink mask ``active`` + PR
  correction scalar, the remaining-attribute mask ``[A]``, a fixed
  ``[A]``-slot ``theta_history`` buffer, the selection ``order`` buffer, and
  the class count ``k``.
* ``engine_step`` — one jitted greedy iteration: evaluate **all** candidates,
  masked argmin-with-ties, fold the winner (presence-bitmap id compaction),
  update history/shrink state.  All shapes are static: the packed-id range is
  bounded by ``capacity · v_max`` for *every* iteration (ids are dense in
  ``[0, K)`` with ``K ≤ capacity``), so one compile covers the whole run —
  the host loop's ``bins_for(k)`` ladder trades per-iteration FLOPs for a
  recompile per power of two; the engine trades padding FLOPs for zero
  recompiles and zero host transfers.
* ``engine_run`` — the full reduction (core folding + greedy loop + stopping
  rule) as a single ``lax.while_loop``.  Core attributes are *forced*
  selections for the first ``core_count`` iterations of the same loop, so
  the core-fold/greedy/stopping/result-assembly logic exists exactly once.
* ``init_state_from_reduct`` / ``engine_resume`` — the warm-start seam for
  the online reduct service (DESIGN.md §3.7): seeding folds a previously
  selected prefix through the same compiled loop with the greedy phase
  disabled (``theta_full = +inf``), resuming continues greedy from the
  seeded state.  A warm reduction is two dispatches of the one trace.

The same ``cond``/``body`` serve the mesh driver: collectives are injected
via a tiny adapter (:class:`_LocalColl` is the identity; :class:`_MeshColl`
psums contingencies over the data axes and all-gathers per-model-shard
thetas), and :mod:`repro.core.distributed` wraps the loop in ``shard_map``.
The ``fused`` collective schedule is the one consumer that *must* return to
the host between iterations (its class re-grouping stages granule tables
through the driver), so it stays on the legacy host loop — see
``plar_reduce_distributed``.

The candidate evaluation is K-adaptive when ``ladder=True`` (DESIGN.md
§5.3): a ``lax.switch`` on the device-resident ``st.k`` picks the smallest
static bin rung covering ``K·v_max``, every rung branch living inside the
one while_loop compile, and the candidate slab ``x.T`` is hoisted out of
the loop.  The advance keeps the full static bound, so theta histories are
byte-identical with the ladder on or off.

Where the host loop is still required (the ``engine="host"`` escape hatch):

* ``backend="pallas"`` / ``"fused"`` / ``"sweep"`` — the interpret-mode
  Pallas kernels are not exercised inside ``while_loop`` bodies;
* ``collective="fused"`` — host-staged class regrouping (above);
* per-iteration wall-clock introspection (the host loop times each iteration
  individually; the engine reports the loop-average).
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from . import measures
from .granularity import dyn_column_terms, ids_from_presence, presence_bitmap
from .plan import (
    candidate_contingency,
    candidate_theta,
    contingency_from_ids,
    ids_by_sort,
    ladder_rungs,
    sweep_contingency,
    theta_tiled_raw,
)

__all__ = [
    "SelectionState",
    "init_state",
    "init_state_from_reduct",
    "engine_resume",
    "make_engine_step",
    "make_engine_run",
    "unpack_result",
    "DEVICE_BACKENDS",
    "EnsembleOperands",
    "init_ensemble_state",
    "make_ensemble_run",
    "run_ensemble",
    "unpack_ensemble_result",
    "ENSEMBLE_DELTAS",
    "ENSEMBLE_BACKENDS",
]

# Θ backends that may run inside the while_loop body (DESIGN.md §3.5).
# ``sweep_xla`` is the read-once slab backend of DESIGN.md §5.3; the Pallas
# kernels (``pallas``/``fused``/``sweep``) stay on the host loop.
DEVICE_BACKENDS = ("segment", "onehot", "fused_xla", "sweep_xla")

# The static measure branch set of the ensemble engine's per-config
# lax.switch: every config's delta is a traced *index* into this tuple, so
# the compiled executable is independent of which measures a grid uses.
ENSEMBLE_DELTAS = tuple(measures.RAW_ROWS)  # ("PR", "SCE", "LCE", "CCE")

# Θ backends the stacked engine supports (DESIGN.md §3.8).  ``fused_xla`` is
# excluded: its measure is fused into the contingency accumulation itself, so
# it cannot split into a shared contingency + per-config measure epilogue.
ENSEMBLE_BACKENDS = ("segment", "onehot", "sweep_xla")


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SelectionState:
    """Everything the greedy loop mutates, as one device-resident pytree.

    Shapes (``cap`` = granule capacity, ``A`` = number of attributes):

      r_ids          [cap] int32   dense class ids of U/R (K ≤ cap)
      h1, h2         [cap] uint32  linear-sketch fingerprints of R's columns
                                   (spark mode only; zeros otherwise)
      active         [cap] bool    live-granule mask (FSPA shrink)
      remaining      [A]   bool    attributes not yet selected
      theta_history  [A]   f32     Θ(D|R) after each selection (+inf unused)
      order          [A]   i32     attribute selected at each iteration (-1)
      k              []    i32     current class count K
      theta_r        []    f32     Θ(D|R) incl. PR correction (+inf initial)
      pr_correction  []    f32     FSPA PR-correction scalar
      n_selected     []    i32     |R| = iteration counter
    """

    r_ids: jnp.ndarray
    h1: jnp.ndarray
    h2: jnp.ndarray
    active: jnp.ndarray
    remaining: jnp.ndarray
    theta_history: jnp.ndarray
    order: jnp.ndarray
    k: jnp.ndarray
    theta_r: jnp.ndarray
    pr_correction: jnp.ndarray
    n_selected: jnp.ndarray

    def tree_flatten(self):
        return (
            self.r_ids, self.h1, self.h2, self.active, self.remaining,
            self.theta_history, self.order, self.k, self.theta_r,
            self.pr_correction, self.n_selected,
        ), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


def init_state(cap: int, n_attrs: int, valid) -> SelectionState:
    """Fresh state: one class (the whole universe), nothing selected."""
    return SelectionState(
        r_ids=jnp.zeros((cap,), jnp.int32),
        h1=jnp.zeros((cap,), jnp.uint32),
        h2=jnp.zeros((cap,), jnp.uint32),
        active=jnp.asarray(valid, bool),
        remaining=jnp.ones((n_attrs,), bool),
        theta_history=jnp.full((n_attrs,), jnp.inf, jnp.float32),
        order=jnp.full((n_attrs,), -1, jnp.int32),
        k=jnp.int32(1),
        theta_r=jnp.float32(jnp.inf),
        pr_correction=jnp.float32(0.0),
        n_selected=jnp.int32(0),
    )


@dataclasses.dataclass(frozen=True)
class _Cfg:
    """Static trace-time configuration (hashable → one compile per value)."""

    delta: str
    mode: str            # "incremental" | "spark"
    backend: str         # DEVICE_BACKENDS
    n_attrs: int
    cap: int
    m: int
    v_max: int
    tol: float
    tie_tol: float
    shrink: bool
    max_sel: int         # max_features, or n_attrs when unbounded
    mp_chunk: int        # candidates evaluated per inner step (memory bound)
    ladder: bool = False  # K-adaptive bin ladder for the eval sweep (§5.3)
    selector: str = "heuristic"  # ladder-rung choice: heuristic|analytic|pinned

    @property
    def n_bins(self) -> int:
        # Static for the whole run: packed ids p = r·V + v live in [0, K·V)
        # and K ≤ cap always, so cap·V bounds every iteration.  Padding rows
        # are all-zero and contribute exactly 0 to every measure.
        return self.cap * self.v_max

    @property
    def rungs(self):
        # The static bucket set the eval sweep selects from per iteration
        # when ``ladder`` is on; the top rung is the full n_bins bound, so
        # the ladder-off path is exactly the degenerate one-rung ladder.
        # ``selector="analytic"`` prunes the pow2 set by the modeled
        # padding-vs-traffic tradeoff — a function of (cap, m) only, so the
        # host loop and mesh driver derive the identical set (§5.3 parity).
        return ladder_rungs(self.n_bins, selector=self.selector,
                            g=self.cap, m=self.m)


# ---------------------------------------------------------------------------
# collective adapters — the one seam between the two drivers
# ---------------------------------------------------------------------------


class _LocalColl:
    """Single-process: every collective is the identity."""

    n_data = 1
    daxes = ()

    def psum_data(self, x):
        return x

    def gather_model(self, thetas_local, n_attrs):
        return thetas_local[:n_attrs]


class _MeshColl:
    """Inside ``shard_map``: granules sharded over the data axes, candidates
    over 'model'.  Construct only inside the shard_map-traced function."""

    def __init__(self, daxes, nd: int, has_model: bool):
        self.daxes = daxes
        self.n_data = nd
        self.has_model = has_model

    def psum_data(self, x):
        return jax.lax.psum(x, self.daxes) if self.daxes else x

    def gather_model(self, thetas_local, n_attrs):
        if self.has_model:
            thetas_local = jax.lax.all_gather(
                thetas_local, "model", tiled=True)
        return thetas_local[:n_attrs]


# ---------------------------------------------------------------------------
# the shared step pieces
# ---------------------------------------------------------------------------


@jax.named_scope("advance")
def _advance(cfg, coll, r_ids, x_col, d, w, active, n, eval_theta=None):
    """Fold one attribute into the class ids: pack → compact → Θ → purity.

    The presence bitmap psums over data shards before ranking, so every shard
    agrees on the global dense numbering (DESIGN.md §3.1) — with
    :class:`_LocalColl` this is exactly ``granularity.compact_ids``.

    ``eval_theta(cont, n)`` overrides the measure evaluation: the ensemble
    engine passes a ``lax.switch`` over the measures so ``delta`` can be a
    traced per-config operand instead of the static ``cfg.delta`` (the
    default, bit-identical for all existing callers).
    """
    nb = cfg.n_bins
    packed = r_ids * cfg.v_max + x_col
    presence = coll.psum_data(presence_bitmap(packed, active, nb))
    new_ids, k_new = ids_from_presence(presence, packed, active)

    w_ = jnp.where(active, w, 0).astype(jnp.float32)
    seg = jnp.where(active, new_ids * cfg.m + d, nb * cfg.m)
    cont = jax.ops.segment_sum(w_, seg, num_segments=nb * cfg.m + 1)[:-1]
    cont = coll.psum_data(cont.reshape(nb, cfg.m))
    theta = (measures.evaluate(cfg.delta, cont, n) if eval_theta is None
             else eval_theta(cont, n))

    e = cont.sum(-1)
    pure_row = (cont.max(-1) == e) & (e > 0)
    g_pure = pure_row[new_ids] & active
    return new_ids, k_new.astype(jnp.int32), theta, g_pure


def _rung_index(cfg, k):
    """Device-side ladder rung selection: first rung ≥ K·V (DESIGN.md §5.3).

    ``cfg`` is any config carrying ``v_max``/``rungs`` (``_Cfg`` or the
    ensemble ``_EnsCfg``).

    ``k`` is the device-resident class count (``st.k``): packed ids live in
    ``[0, K·V)``, rungs are ascending, and the top rung is the exact full
    bound, so the index is always in range — no host sync, no clamp.
    """
    need = k.astype(jnp.int32) * cfg.v_max
    return jnp.sum(need > jnp.asarray(cfg.rungs, jnp.int32)).astype(jnp.int32)


@jax.named_scope("eval_candidates")
def _eval_local(cfg: _Cfg, st: SelectionState, x, x_t, d, w, n):
    """Single-process candidate evaluation: Θ(D|R∪{a}) for every a, [A].

    ``x_t`` is the pre-transposed ``[A, cap]`` candidate slab, hoisted out of
    the loop by the callers: candidate rows are contiguous slices instead of
    a per-iteration gather+transpose of ``x``.
    """
    cols = jnp.arange(cfg.n_attrs, dtype=jnp.int32)
    if cfg.mode == "spark":
        # Paper-faithful cost shape: re-key every granule from scratch per
        # candidate (fingerprint sort), exactly `_eval_chunk_spark` but with
        # the R-fingerprints maintained incrementally in the state (the
        # linear-sketch property: h(R∪{a}) = h(R) + term_a, uint32-exact).
        # The bin ladder does not apply: sort-ranked ids are bounded by the
        # live-granule count, not K·V.
        def one(col):
            t1 = dyn_column_terms(x, col, 0)
            t2 = dyn_column_terms(x, col, 7919)
            ids, _k = ids_by_sort([st.h2 + t2, st.h1 + t1], st.active)
            cont = contingency_from_ids(
                ids, d, w, st.active, n_bins=cfg.cap, m=cfg.m)
            return measures.evaluate(cfg.delta, cont, n)

        return jax.lax.map(one, cols) + st.pr_correction

    def eval_all(nb):
        def chunk(cc):
            x_cand = jnp.take(x_t, cc, axis=0)                 # [nc, cap]
            if cfg.backend == "sweep_xla":
                return candidate_theta(
                    cfg.delta, None, d, w, st.active, n,
                    n_bins=nb, m=cfg.m, backend=cfg.backend,
                    x_t=x_cand, r_ids=st.r_ids, v_max=cfg.v_max)
            packed = st.r_ids[None, :] * cfg.v_max + x_cand
            return candidate_theta(
                cfg.delta, packed, d, w, st.active, n,
                n_bins=nb, m=cfg.m, backend=cfg.backend)

        # mp_chunk (the paper's MP level) bounds peak memory to
        # [mp_chunk, nb, m] per inner step, exactly like the host loop's
        # chunked dispatch; per-candidate values are independent, so chunking
        # never changes bits.
        nc = min(cfg.mp_chunk, cfg.n_attrs)
        a_pad = -(-cfg.n_attrs // nc) * nc
        if a_pad == nc:
            return chunk(cols)
        grid = (jnp.arange(a_pad, dtype=jnp.int32) % cfg.n_attrs).reshape(-1, nc)
        return jax.lax.map(chunk, grid).reshape(-1)[: cfg.n_attrs]

    if not cfg.ladder or len(cfg.rungs) == 1:
        return eval_all(cfg.n_bins) + st.pr_correction

    # K-adaptive bin ladder (§5.3): all rung branches trace into the one
    # while_loop compile; per iteration a lax.switch on the device-resident
    # st.k picks the smallest rung covering K·V — early iterations pay
    # K-proportional work with zero recompiles and zero host transfers.
    thetas = jax.lax.switch(
        _rung_index(cfg, st.k), [partial(eval_all, nb) for nb in cfg.rungs])
    return thetas + st.pr_correction


def merge_candidate_cont(delta, cont, n, coll, collective: str):
    """Per-shard candidate contingency ``[nc, nb, m]`` → merged thetas [nc].

    The §3.2 collective schedules, shared by both mesh step implementations
    (this engine's ``_eval_mesh`` and the legacy ``distributed._eval_step``):
    ``all_reduce`` psums the full contingency (paper-faithful DP);
    ``reduce_scatter`` scatters contingency *rows* over the data shards,
    reduces θ locally (row-separability, Eq. 8) and psums the scalar.
    """
    nb = cont.shape[1]
    if collective == "reduce_scatter" and coll.n_data > 1 and nb % coll.n_data == 0:
        cont_slice = jax.lax.psum_scatter(
            cont, coll.daxes, scatter_dimension=1, tiled=True)
        return jax.lax.psum(
            measures.theta_rows(delta, cont_slice, n).sum(-1), coll.daxes)
    return measures.evaluate(delta, coll.psum_data(cont), n)


def _mesh_cand_slab(cfg: _Cfg, coll: _MeshColl, n_model, x):
    """This model shard's candidate slice + pre-transposed slab [A_loc, G_loc].

    Hoisted out of the while_loop by ``_engine_run_mesh``: the gather and
    transpose of the granule table happen once per run, not per iteration.
    """
    a_pad = -(-cfg.n_attrs // n_model) * n_model
    a_loc = a_pad // n_model
    midx = jax.lax.axis_index("model") if coll.has_model else 0
    cand = jnp.minimum(midx * a_loc + jnp.arange(a_loc, dtype=jnp.int32),
                       cfg.n_attrs - 1)
    return jnp.take(x, cand, axis=1).T.astype(jnp.int32)


def _eval_mesh(cfg: _Cfg, coll: _MeshColl, collective, st, x_tl, d, w, n):
    """Mesh candidate evaluation: this shard's candidate slab → gather [A].

    ``x_tl [A_loc, G_loc]`` is this shard's pre-transposed candidate slab
    (:func:`_mesh_cand_slab`).  Contingencies merge via
    :func:`merge_candidate_cont`; every §5.3 ladder rung stays divisible by
    the data-shard count (rungs below the top are pow2 multiples of the
    256-bin tile; the top rung ``cap·V`` has ``cap = nd · cap_per_shard``),
    so ``reduce_scatter`` keeps tiling at every rung.
    """
    w_ = jnp.where(st.active, w, 0).astype(jnp.float32)
    d32 = d.astype(jnp.int32)

    def eval_all(nb):
        if cfg.backend == "sweep_xla":
            # fused-pack contingency (packed [A_loc, G_loc] never staged)
            cont = sweep_contingency(
                x_tl, st.r_ids, d32, w_, st.active, v_max=cfg.v_max,
                n_bins=nb, m=cfg.m)
        else:
            packed = st.r_ids[None, :] * cfg.v_max + x_tl

            def one(p):
                seg = jnp.where(st.active, p * cfg.m + d32, nb * cfg.m)
                return jax.ops.segment_sum(
                    w_, seg, num_segments=nb * cfg.m + 1)[:-1]

            cont = jax.vmap(one)(packed).reshape(-1, nb, cfg.m)
        return merge_candidate_cont(cfg.delta, cont, n, coll, collective)

    if not cfg.ladder or len(cfg.rungs) == 1:
        th_loc = eval_all(cfg.n_bins)
    else:
        # K·V is globally consistent (st.k is replicated by the presence-psum
        # compaction), so every shard switches to the same rung and the
        # collectives inside each branch stay congruent across the mesh.
        th_loc = jax.lax.switch(
            _rung_index(cfg, st.k), [partial(eval_all, nb) for nb in cfg.rungs])
    return coll.gather_model(th_loc, cfg.n_attrs) + st.pr_correction


def _make_cond_body(cfg: _Cfg, coll, eval_thetas, x, d, w, n, theta_full,
                    core_attrs, core_count):
    """The one greedy core: cond/body shared by both drivers.

    ``eval_thetas(state) -> [A]`` is the injected evaluation strategy (local
    or mesh-collective); everything else — forced core folds, masked
    argmin-with-ties, advance, shrink, history — is identical code.
    """

    def cond(st: SelectionState):
        in_core = st.n_selected < core_count
        greedy = (
            (st.n_selected < cfg.n_attrs)
            & (st.theta_r > theta_full + cfg.tol)
            & (st.n_selected < cfg.max_sel)
        )
        return in_core | greedy

    def body(st: SelectionState):
        forced = st.n_selected < core_count

        def pick_core(st):
            return core_attrs[jnp.minimum(st.n_selected, cfg.n_attrs - 1)]

        def pick_greedy(st):
            thetas = eval_thetas(st)
            # lowest index within tie_tol of the minimum — the device twin of
            # measures.argmin_with_ties (remaining is index-ordered, so the
            # first in-band slot is the same attribute the host loop picks).
            with jax.named_scope("select"):
                thetas = jnp.where(st.remaining, thetas, jnp.inf)
                return jnp.argmax(
                    thetas <= thetas.min() + cfg.tie_tol).astype(jnp.int32)

        best = jax.lax.cond(forced, pick_core, pick_greedy, st)
        x_col = jnp.take(x, best, axis=1)
        new_ids, k_new, theta, g_pure = _advance(
            cfg, coll, st.r_ids, x_col, d, w, st.active, n)
        theta_rec = theta + st.pr_correction   # correction *before* this fold

        if cfg.mode == "spark":
            h1 = st.h1 + dyn_column_terms(x, best, 0)
            h2 = st.h2 + dyn_column_terms(x, best, 7919)
        else:
            h1, h2 = st.h1, st.h2

        if cfg.shrink:
            active = st.active & ~g_pure
            if cfg.delta == "PR":
                shed = jnp.sum(jnp.where(g_pure, w, 0)).astype(jnp.float32)
                pr_corr = st.pr_correction - shed / jnp.asarray(n, jnp.float32)
            else:
                pr_corr = st.pr_correction
        else:
            active, pr_corr = st.active, st.pr_correction

        return SelectionState(
            r_ids=new_ids,
            h1=h1,
            h2=h2,
            active=active,
            remaining=st.remaining.at[best].set(False),
            theta_history=st.theta_history.at[st.n_selected].set(theta_rec),
            order=st.order.at[st.n_selected].set(best),
            k=k_new,
            theta_r=theta_rec,
            pr_correction=pr_corr,
            n_selected=st.n_selected + 1,
        )

    return cond, body


# ---------------------------------------------------------------------------
# public entry points (cached per static config → one compile each)
# ---------------------------------------------------------------------------


def make_engine_step(delta: str, mode: str, backend: str, n_attrs: int,
                     cap: int, m: int, v_max: int, tol: float, tie_tol: float,
                     shrink: bool, max_sel: int, mp_chunk: int = 64,
                     ladder: bool = False, selector: str = "analytic"):
    """One jitted greedy iteration (evaluate → argmin → advance).

    Exposed for inspection/benchmarks; ``make_engine_run`` inlines the same
    body into its while_loop, so a full reduction costs one compile, not two.
    """
    # thin wrapper so defaulted, keyword, and explicit positional calls all
    # share one lru entry, and numpy scalar arguments (np.int32 dims from a
    # Granularity, np.bool_ flags) key identically to their Python values —
    # the single-compile contract (asserted by test_engine_factory_cache_key)
    return _make_engine_step(str(delta), str(mode), str(backend),
                             int(n_attrs), int(cap), int(m), int(v_max),
                             float(tol), float(tie_tol), bool(shrink),
                             int(max_sel), int(mp_chunk), bool(ladder),
                             str(selector))


@lru_cache(maxsize=None)
def _make_engine_step(delta, mode, backend, n_attrs, cap, m, v_max, tol,
                      tie_tol, shrink, max_sel, mp_chunk, ladder, selector):
    # an lru miss here IS a new trace → a new XLA compile at first dispatch
    obs.counter("plar_engine_step_factories_total",
                "distinct single-step engine configs traced").inc()
    cfg = _Cfg(delta, mode, backend, n_attrs, cap, m, v_max, tol, tie_tol,
               shrink, max_sel, mp_chunk, ladder, selector)

    @jax.jit
    def step(st: SelectionState, x, d, w, n, theta_full, core_attrs,
             core_count) -> SelectionState:
        x_t = x.T
        coll = _LocalColl()
        _, body = _make_cond_body(
            cfg, coll, lambda s: _eval_local(cfg, s, x, x_t, d, w, n),
            x, d, w, n, theta_full, core_attrs, core_count)
        return body(st)

    return step


def make_engine_run(delta: str, mode: str, backend: str, n_attrs: int,
                    cap: int, m: int, v_max: int, tol: float, tie_tol: float,
                    shrink: bool, max_sel: int, mp_chunk: int = 64,
                    ladder: bool = False, selector: str = "analytic"):
    """The full reduction as one ``lax.while_loop`` (single-process)."""
    # same key normalization as make_engine_step (one lru entry per logical
    # config regardless of call style or numpy scalar types)
    return _make_engine_run(str(delta), str(mode), str(backend),
                            int(n_attrs), int(cap), int(m), int(v_max),
                            float(tol), float(tie_tol), bool(shrink),
                            int(max_sel), int(mp_chunk), bool(ladder),
                            str(selector))


@lru_cache(maxsize=None)
def _make_engine_run(delta, mode, backend, n_attrs, cap, m, v_max, tol,
                     tie_tol, shrink, max_sel, mp_chunk, ladder, selector):
    # an lru miss here IS a new trace → a new XLA compile at first dispatch
    obs.counter("plar_engine_run_factories_total",
                "distinct while_loop engine configs traced").inc()
    cfg = _Cfg(delta, mode, backend, n_attrs, cap, m, v_max, tol, tie_tol,
               shrink, max_sel, mp_chunk, ladder, selector)

    @jax.jit
    def run(st: SelectionState, x, d, w, n, theta_full, core_attrs,
            core_count) -> SelectionState:
        # The candidate slab transpose is hoisted out of the while_loop: one
        # [A, cap] materialization per run instead of a gather+transpose per
        # iteration (per mp_chunk, per rung branch).
        x_t = x.T
        coll = _LocalColl()
        cond, body = _make_cond_body(
            cfg, coll, lambda s: _eval_local(cfg, s, x, x_t, d, w, n),
            x, d, w, n, theta_full, core_attrs, core_count)
        return jax.lax.while_loop(cond, body, st)

    return run


def _forced_attrs(n_attrs: int, forced) -> jnp.ndarray:
    """The padded ``[max(A,1)]`` forced-selection buffer both entry points
    feed the loop (core attributes and warm-start prefixes alike)."""
    arr = np.zeros((max(n_attrs, 1),), np.int32)
    arr[: len(forced)] = forced
    return jnp.asarray(arr)


def init_state_from_reduct(runner, cap: int, n_attrs: int, valid, x, d, w, n,
                           prefix) -> SelectionState:
    """Seed a :class:`SelectionState` by folding ``prefix`` into fresh state.

    The online-service repair primitive (DESIGN.md §3.7): runs the *same*
    compiled while_loop as the full reduction with the greedy phase disabled
    (``theta_full = +inf`` makes the greedy condition vacuously false), so
    the loop executes exactly ``len(prefix)`` forced folds and exits.  The
    returned state carries the refined ``r_ids``/``k``, the recomputed
    Θ-history prefix (the *validation* signal — each entry is Θ(D|prefix[:i])
    on the current granularity), and ``remaining`` with the prefix cleared —
    ready for :func:`engine_resume`.  ``theta_full`` is a traced operand, so
    seeding adds zero compiles beyond the runner's single trace.
    """
    st = init_state(cap, n_attrs, valid)
    return runner(st, x, d, w, n, jnp.float32(jnp.inf),
                  _forced_attrs(n_attrs, prefix), jnp.int32(len(prefix)))


def engine_resume(runner, st: SelectionState, x, d, w, n, theta_full):
    """Resume the greedy loop from a seeded state (no forced selections).

    The warm-start twin of a cold ``runner`` call: with ``core_count = 0``
    the loop goes straight to greedy iterations from wherever ``st`` left
    off.  Same compiled executable as the cold run and the seed — a warm
    reduction is two dispatches of one trace.
    """
    n_attrs = st.remaining.shape[0]
    return runner(st, x, d, w, n, jnp.float32(theta_full),
                  _forced_attrs(n_attrs, ()), jnp.int32(0))


def run_engine(runner, cap: int, n_attrs: int, valid, x, d, w, n,
               theta_full: float, core, warm_start=None):
    """Init-state → jitted loop → unpack: the device path shared verbatim by
    both drivers (``plar_reduce`` and ``plar_reduce_distributed``).

    With ``warm_start`` (a previously selected prefix; ``core`` must be
    empty) the loop is seeded by :func:`init_state_from_reduct` and resumed
    by :func:`engine_resume` — two dispatches of the same single compile,
    re-folding the prefix as forced selections and running greedy only for
    the remainder.

    Returns ``(reduct, theta_history, iterations, n_evals, per_iteration_s)``
    where ``per_iteration_s`` holds one entry per *executed loop body* —
    ``len(reduct)`` entries, core/warm folds included — each the loop average
    (the engine is a single dispatch, so individual bodies cannot be timed;
    the list sums to the measured loop wall-clock exactly).
    """
    import time

    traces_before = _jit_cache_size(runner)
    t_loop = time.perf_counter()
    with obs.span("engine.dispatch", n_attrs=n_attrs, cap=cap,
                  warm=warm_start is not None) as sp:
        if warm_start is not None:
            assert not core, "warm_start replaces the core prefix"
            forced = list(warm_start)
            st = init_state_from_reduct(
                runner, cap, n_attrs, valid, x, d, w, n, forced)
            fin = jax.block_until_ready(
                engine_resume(runner, st, x, d, w, n, theta_full))
        else:
            forced = list(core)
            st = init_state(cap, n_attrs, valid)
            fin = jax.block_until_ready(
                runner(st, x, d, w, n, jnp.float32(theta_full),
                       _forced_attrs(n_attrs, forced), jnp.int32(len(forced))))
        loop_s = time.perf_counter() - t_loop
        reduct, hist, iters, n_evals = unpack_result(fin, len(forced))
        traces_after = _jit_cache_size(runner)
        compiled = traces_after > traces_before
        sp.set(k=len(reduct), iterations=iters, compiled=compiled)
    obs.counter("plar_engine_runs_total",
                "engine while_loop dispatch sequences completed").inc()
    if compiled:
        obs.counter("plar_engine_compiles_total",
                    "engine dispatches that paid a fresh trace/compile").inc()
    obs.gauge("plar_engine_last_k",
              "reduct size of the most recent engine run").set(len(reduct))
    obs.gauge("plar_engine_last_iterations",
              "greedy iterations of the most recent engine run").set(iters)
    n_bodies = len(reduct)
    per_body = loop_s / n_bodies if n_bodies else 0.0
    if n_bodies:
        obs.histogram("plar_engine_iteration_seconds",
                      "loop-average seconds per executed engine loop body"
                      ).observe(per_body)
    return reduct, hist, iters, n_evals, [per_body] * n_bodies


def _jit_cache_size(runner) -> int:
    """Traced-executable count of a jitted callable — lets the dispatch
    span tell a compile from a cache hit."""
    return int(runner._cache_size())


def unpack_result(fin: SelectionState, core_count: int):
    """Host-side unpack: (reduct, theta_history, greedy_iterations, n_evals).

    The single device→host transfer of the whole greedy phase.
    """
    nsel = int(fin.n_selected)
    order = np.asarray(fin.order)[:nsel]
    reduct = [int(a) for a in order]
    hist = [float(t) for t in np.asarray(fin.theta_history)[:nsel]]
    iters = nsel - core_count
    n_attrs = fin.remaining.shape[0]
    # the engine evaluates ALL A candidates each greedy iteration (already-
    # selected ones are masked after the fact — static shapes); report that
    # true count, which is ≥ the host loop's shrinking len(remaining)
    n_evals = iters * n_attrs
    return reduct, hist, iters, n_evals


# ---------------------------------------------------------------------------
# stacked multi-config engine (DESIGN.md §3.8)
# ---------------------------------------------------------------------------
#
# One ``lax.while_loop`` dispatch advances a whole grid of reduction configs
# — (measure, tol, tie_tol, max_features, shrink, forced core, bagged row
# weights) — over ONE shared granularity: the config axis is a leading [C]
# axis on :class:`SelectionState` and the per-config parameters ride along as
# *traced* operands (:class:`EnsembleOperands`), so the whole grid costs one
# compile and every granule/candidate tile is read once per iteration instead
# of once per config.  Per-config measures dispatch through a ``lax.switch``
# over :data:`ENSEMBLE_DELTAS` whose branches run exactly the sequential
# engine's evaluation ops — the byte-identical-per-config contract (asserted
# by tests/test_ensemble.py) rests on that switch executing one branch, not a
# blend.


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EnsembleOperands:
    """Per-config traced parameters of the stacked engine, leading axis [C].

    Everything the sequential engine bakes into its static ``_Cfg`` that can
    instead be a traced operand lives here — which is exactly what collapses
    a C-config grid from C compiles to one:

      delta_idx   [C]          i32   index into ENSEMBLE_DELTAS
      tol         [C]          f32   stopping tolerance
      tie_tol     [C]          f32   argmin tie band
      max_sel     [C]          i32   max_features (n_attrs when unbounded)
      shrink      [C]          bool  FSPA universe shrinking
      theta_full  [C]          f32   Θ(D|C) stopping target (per-config w!)
      n           [C]          i32   total row weight |U|
      w           [C, cap]     i32   granule weights (bagged resample seam)
      core_attrs  [C, max(A,1)] i32  forced-selection prefix, padded
      core_count  [C]          i32   number of forced selections
    """

    delta_idx: jnp.ndarray
    tol: jnp.ndarray
    tie_tol: jnp.ndarray
    max_sel: jnp.ndarray
    shrink: jnp.ndarray
    theta_full: jnp.ndarray
    n: jnp.ndarray
    w: jnp.ndarray
    core_attrs: jnp.ndarray
    core_count: jnp.ndarray

    def tree_flatten(self):
        return (
            self.delta_idx, self.tol, self.tie_tol, self.max_sel, self.shrink,
            self.theta_full, self.n, self.w, self.core_attrs, self.core_count,
        ), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def n_cfgs(self) -> int:
        return self.delta_idx.shape[0]


@dataclasses.dataclass(frozen=True)
class _EnsCfg:
    """Static trace-time configuration of the stacked engine.

    Deliberately *smaller* than ``_Cfg``: everything per-config moved into
    :class:`EnsembleOperands`, so the compile cache keys only on shapes and
    the shared evaluation strategy.
    """

    mode: str            # "incremental" | "spark"
    backend: str         # ENSEMBLE_BACKENDS
    n_cfgs: int
    n_attrs: int
    cap: int
    m: int
    v_max: int
    mp_chunk: int
    ladder: bool = False
    selector: str = "heuristic"

    @property
    def n_bins(self) -> int:
        return self.cap * self.v_max

    @property
    def rungs(self):
        return ladder_rungs(self.n_bins, selector=self.selector,
                            g=self.cap, m=self.m)


def _theta_switch(delta_idx, cont, n):
    """Θ(cont) under a *traced* measure index: one-branch lax.switch whose
    branches are exactly ``measures.evaluate`` per measure — the selected
    branch runs the same ops as the sequential engine, so bits match."""
    return jax.lax.switch(
        delta_idx,
        [partial(measures.evaluate, dd) for dd in ENSEMBLE_DELTAS], cont, n)


def _sweep_theta_switch(delta_idx, cont, n):
    """The sweep epilogue under a traced measure index: tile-ordered θ'
    accumulation (plan.theta_tiled_raw) + scale, per branch — the §5.3
    structure whose bitwise rung invariance lets the stacked ladder share
    one rung across configs."""

    def mk(dd):
        def branch(cont, n):
            return measures.theta_scale(dd, theta_tiled_raw(dd, cont), n)

        return branch

    return jax.lax.switch(
        delta_idx, [mk(dd) for dd in ENSEMBLE_DELTAS], cont, n)


def _eval_ensemble_one(cfg: _EnsCfg, x, x_t, d, nb, st_c, w_c, n_c, delta_idx):
    """One config's candidate evaluation Θ(D|R∪{a}) for every a — the
    ensemble twin of :func:`_eval_local`, vmapped over the config axis by
    the runner.  Mirrors the sequential evaluation op-for-op (same
    contingency path, same chunking) with the measure dispatched through
    the one-branch switch."""
    cols = jnp.arange(cfg.n_attrs, dtype=jnp.int32)
    if cfg.mode == "spark":
        # paper-faithful re-key per candidate; the ladder does not apply
        # (sort-ranked ids are bounded by the live-granule count, not K·V)
        def one(col):
            t1 = dyn_column_terms(x, col, 0)
            t2 = dyn_column_terms(x, col, 7919)
            ids, _k = ids_by_sort([st_c.h2 + t2, st_c.h1 + t1], st_c.active)
            cont = contingency_from_ids(
                ids, d, w_c, st_c.active, n_bins=cfg.cap, m=cfg.m)
            return _theta_switch(delta_idx, cont, n_c)

        return jax.lax.map(one, cols) + st_c.pr_correction

    def chunk(cc):
        x_cand = jnp.take(x_t, cc, axis=0)                     # [nc, cap]
        if cfg.backend == "sweep_xla":
            cont = sweep_contingency(
                x_cand, st_c.r_ids, d, w_c, st_c.active, v_max=cfg.v_max,
                n_bins=nb, m=cfg.m)
            return _sweep_theta_switch(delta_idx, cont, n_c)
        packed = st_c.r_ids[None, :] * cfg.v_max + x_cand
        cont = candidate_contingency(
            packed, d, w_c, st_c.active, n_bins=nb, m=cfg.m,
            backend=cfg.backend)
        return _theta_switch(delta_idx, cont, n_c)

    # same mp_chunk grid as _eval_local: per-candidate values are
    # independent, so chunking never changes bits
    nc = min(cfg.mp_chunk, cfg.n_attrs)
    a_pad = -(-cfg.n_attrs // nc) * nc
    if a_pad == nc:
        return chunk(cols) + st_c.pr_correction
    grid = (jnp.arange(a_pad, dtype=jnp.int32) % cfg.n_attrs).reshape(-1, nc)
    return (jax.lax.map(chunk, grid).reshape(-1)[: cfg.n_attrs]
            + st_c.pr_correction)


def make_ensemble_run(mode: str, backend: str, n_cfgs: int, n_attrs: int,
                      cap: int, m: int, v_max: int, mp_chunk: int = 64,
                      ladder: bool = False, selector: str = "analytic"):
    """The whole config grid as one ``lax.while_loop`` (single compile).

    Returns ``run(st_stack, x, d, ops) -> st_stack`` where every
    :class:`SelectionState` leaf carries a leading ``[n_cfgs]`` axis and
    ``ops`` is the :class:`EnsembleOperands` stack.  Same key normalization
    as :func:`make_engine_run` (one lru entry per logical config).
    """
    if backend not in ENSEMBLE_BACKENDS:
        raise ValueError(
            f"ensemble engine does not support backend={backend!r} "
            f"(one of: {', '.join(ENSEMBLE_BACKENDS)})")
    if ladder and backend != "sweep_xla":
        raise ValueError(
            "ensemble ladder requires backend='sweep_xla': the stacked loop "
            "shares one rung (max K across configs) per iteration, which is "
            "only bit-safe under the §5.3 sweep rung invariance")
    return _make_ensemble_run(str(mode), str(backend), int(n_cfgs),
                              int(n_attrs), int(cap), int(m), int(v_max),
                              int(mp_chunk), bool(ladder), str(selector))


@lru_cache(maxsize=None)
def _make_ensemble_run(mode, backend, n_cfgs, n_attrs, cap, m, v_max,
                       mp_chunk, ladder, selector):
    # an lru miss here IS a new trace → a new XLA compile at first dispatch
    obs.counter("plar_engine_ensemble_factories_total",
                "distinct stacked-engine configs traced").inc()
    cfg = _EnsCfg(mode, backend, n_cfgs, n_attrs, cap, m, v_max, mp_chunk,
                  ladder, selector)
    coll = _LocalColl()
    pr_idx = ENSEMBLE_DELTAS.index("PR")

    @jax.jit
    def run(st: SelectionState, x, d, ops: EnsembleOperands) -> SelectionState:
        # shared candidate slab, hoisted out of the loop exactly like the
        # sequential runner — and read ONCE per iteration for all configs
        x_t = x.T

        def cond_one(st_c, ops_c):
            # the sequential cond with tol/max_sel as traced operands; the
            # f32 arithmetic theta_full + tol matches the static-Python
            # version bit-for-bit (both are f32 + f32)
            in_core = st_c.n_selected < ops_c.core_count
            greedy = (
                (st_c.n_selected < cfg.n_attrs)
                & (st_c.theta_r > ops_c.theta_full + ops_c.tol)
                & (st_c.n_selected < ops_c.max_sel)
            )
            return in_core | greedy

        def eval_rung(nb, st):
            def one(st_c, w_c, n_c, di):
                return _eval_ensemble_one(
                    cfg, x, x_t, d, nb, st_c, w_c, n_c, di)

            return jax.vmap(one)(st, ops.w, ops.n, ops.delta_idx)  # [C, A]

        def body_one(st_c, ops_c, thetas_c):
            forced = st_c.n_selected < ops_c.core_count

            # sequential pick_core / pick_greedy as a select on precomputed
            # thetas (the grid shares the evaluation, so the lax.cond that
            # skips evaluation during forced folds has nothing left to skip)
            core_pick = ops_c.core_attrs[
                jnp.minimum(st_c.n_selected, cfg.n_attrs - 1)]
            masked = jnp.where(st_c.remaining, thetas_c, jnp.inf)
            greedy_pick = jnp.argmax(
                masked <= masked.min() + ops_c.tie_tol).astype(jnp.int32)
            best = jnp.where(forced, core_pick, greedy_pick)

            x_col = jnp.take(x, best, axis=1)
            new_ids, k_new, theta, g_pure = _advance(
                cfg, coll, st_c.r_ids, x_col, d, ops_c.w, st_c.active,
                ops_c.n, eval_theta=partial(_theta_switch, ops_c.delta_idx))
            theta_rec = theta + st_c.pr_correction

            if cfg.mode == "spark":
                h1 = st_c.h1 + dyn_column_terms(x, best, 0)
                h2 = st_c.h2 + dyn_column_terms(x, best, 7919)
            else:
                h1, h2 = st_c.h1, st_c.h2

            # traced-shrink: a select per config instead of _Cfg branching;
            # shrink=False leaves active/pr_correction exactly unchanged
            active = st_c.active & ~(g_pure & ops_c.shrink)
            shed = jnp.sum(jnp.where(g_pure, ops_c.w, 0)).astype(jnp.float32)
            pr_corr = jnp.where(
                ops_c.shrink & (ops_c.delta_idx == pr_idx),
                st_c.pr_correction - shed / jnp.asarray(ops_c.n, jnp.float32),
                st_c.pr_correction)

            return SelectionState(
                r_ids=new_ids,
                h1=h1,
                h2=h2,
                active=active,
                remaining=st_c.remaining.at[best].set(False),
                theta_history=st_c.theta_history.at[st_c.n_selected].set(
                    theta_rec),
                order=st_c.order.at[st_c.n_selected].set(best),
                k=k_new,
                theta_r=theta_rec,
                pr_correction=pr_corr,
                n_selected=st_c.n_selected + 1,
            )

        def cond(st):
            return jnp.any(jax.vmap(cond_one)(st, ops))

        def body(st):
            go = jax.vmap(cond_one)(st, ops)                    # [C]
            if cfg.mode == "spark" or not cfg.ladder or len(cfg.rungs) == 1:
                thetas = eval_rung(cfg.n_bins, st)
            else:
                # shared rung across the grid: smallest rung covering
                # max_c(K_c)·V, picked OUTSIDE the vmap so the switch stays
                # a one-branch switch (a vmapped switch over per-config
                # rungs would lower to a select executing every branch).
                # Bit-safe only for sweep_xla (factory-enforced): each
                # config's thetas are invariant to any rung ≥ its own K·V.
                thetas = jax.lax.switch(
                    _rung_index(cfg, jnp.max(st.k)),
                    [partial(eval_rung, nb) for nb in cfg.rungs], st)
            new = jax.vmap(body_one)(st, ops, thetas)

            # freeze configs whose cond is already false: conds are monotone
            # (a frozen config stays frozen), so the loop runs max_c(nsel_c)
            # bodies and every config's trajectory is exactly its sequential
            # one
            def gate(old, upd):
                g = go.reshape(go.shape + (1,) * (upd.ndim - 1))
                return jnp.where(g, upd, old)

            return jax.tree_util.tree_map(gate, st, new)

        return jax.lax.while_loop(cond, body, st)

    return run


def init_ensemble_state(cap: int, n_attrs: int, valid, n_cfgs: int) -> SelectionState:
    """Fresh stacked state: :func:`init_state` broadcast to a leading [C]."""
    st = init_state(cap, n_attrs, valid)
    return jax.tree_util.tree_map(
        lambda leaf: jnp.broadcast_to(leaf, (n_cfgs,) + leaf.shape), st)


def run_ensemble(runner, cap: int, n_attrs: int, valid, x, d,
                 ops: EnsembleOperands):
    """Init stacked state → one while_loop dispatch → final stacked state.

    Returns ``(final_state, loop_s)``; unpack per config with
    :func:`unpack_ensemble_result`.
    """
    import time

    traces_before = _jit_cache_size(runner)
    t0 = time.perf_counter()
    with obs.span("engine.dispatch_ensemble", configs=ops.n_cfgs,
                  n_attrs=n_attrs, cap=cap) as sp:
        st = init_ensemble_state(cap, n_attrs, valid, ops.n_cfgs)
        fin = jax.block_until_ready(runner(st, x, d, ops))
        traces_after = _jit_cache_size(runner)
        sp.set(compiled=traces_after > traces_before)
    obs.counter("plar_engine_ensemble_runs_total",
                "stacked-engine dispatches completed").inc()
    return fin, time.perf_counter() - t0


def unpack_ensemble_result(fin: SelectionState, core_counts):
    """Stacked final state → per-config (reduct, theta_history, iterations,
    n_evals) — one device→host transfer for the whole grid."""
    order = np.asarray(fin.order)
    hist = np.asarray(fin.theta_history)
    nsel = np.asarray(fin.n_selected)
    n_attrs = fin.remaining.shape[-1]
    out = []
    for c, cc in enumerate(core_counts):
        ns = int(nsel[c])
        reduct = [int(a) for a in order[c, :ns]]
        h = [float(t) for t in hist[c, :ns]]
        iters = ns - int(cc)
        out.append((reduct, h, iters, iters * n_attrs))
    return out
