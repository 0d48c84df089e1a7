"""Forward heuristic attribute reduction: HAR / FSPA baselines + PLAR.

Implements the paper's Algorithm 1 (HAR), the FSPA accelerator of Qian et al.
(the paper's single-machine state-of-the-art baseline), and the PLAR greedy
loop (Algorithm 2) in single-process form.  The mesh-distributed MDP version
lives in :mod:`repro.core.distributed` and reuses these building blocks.

Faithfulness notes (DESIGN.md §2):

* HAR here means: no GrC initialization (every raw row is its own record), no
  model parallelism (candidates evaluated one chunk of 1 at a time), and every
  evaluation re-keys from scratch (``mode="spark"``) — the cost shape of the
  original sequential algorithm, vectorized enough to run under XLA.
* FSPA = HAR + universe shrinking.  Because θ of a *pure* class is exactly 0
  for SCE/LCE/CCE and exactly ``-|E|/|U|`` for PR, dropping pure classes and
  carrying a single PR correction scalar reproduces HAR's Θ values *exactly*
  (so reducts are identical, matching the paper's Tables 6–9).
* PLAR = GrC init + MP (candidate chunks) + the incremental packed-id
  evaluation (beyond-paper; ``mode="spark"`` gives the paper-faithful loop).
"""
from __future__ import annotations

import dataclasses
import time
from functools import lru_cache, partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from . import measures
from .engine import (
    DEVICE_BACKENDS,
    ENSEMBLE_BACKENDS,
    ENSEMBLE_DELTAS,
    EnsembleOperands,
    make_engine_run,
    make_ensemble_run,
    run_engine,
    run_ensemble,
    unpack_ensemble_result,
)
from .granularity import (
    Granularity,
    build_granularity,
    build_granularity_streaming,
    column_terms,
    dyn_column_terms,
    compact_ids,
    ids_from_presence,
    next_pow2,
    pack_ids,
    presence_bitmap,
    row_fingerprints,
    with_capacity,
)
from .plan import (
    SWEEP_BACKENDS,
    candidate_theta,
    contingency_from_ids,
    ids_by_sort,
    ladder_rungs,
    rung_for,
    subset_ids,
)

__all__ = ["ReductionResult", "plar_reduce", "plar_reduce_ensemble",
           "har_reduce", "fspa_reduce", "raw_granularity",
           "resolve_granularity", "bagged_weights", "expand_ensemble_grid",
           "normalize_ensemble_configs", "partition_reduce_params",
           "ENSEMBLE_SHARED_KEYS"]

_MODES = ("incremental", "spark")
_BACKENDS = ("segment", "onehot", "pallas", "fused", "fused_xla", "sweep",
             "sweep_xla")
_ENGINES = ("auto", "host", "device")


def _resolve_engine(engine: str, backend: str) -> str:
    """Validate the engine knob and resolve ``auto``.

    ``auto`` prefers the device-resident while_loop engine (core/engine.py)
    and falls back to the host loop only where the device engine cannot run:
    the interpret-mode Pallas backends (``pallas``/``fused``).
    """
    if engine not in _ENGINES:
        raise ValueError(
            f"unknown engine: {engine!r} (one of: {', '.join(_ENGINES)})")
    if engine == "device" and backend not in DEVICE_BACKENDS:
        raise ValueError(
            f"engine='device' does not support backend={backend!r} "
            f"(one of: {', '.join(DEVICE_BACKENDS)}); use engine='host'")
    if engine == "auto":
        return "device" if backend in DEVICE_BACKENDS else "host"
    return engine


# kept as an alias: the canonical definition moved next to the capacity
# policy it governs (granularity.merge_granularity)
_next_pow2 = next_pow2


@dataclasses.dataclass
class ReductionResult:
    reduct: List[int]               # selected attributes, core first then greedy order
    core: List[int]
    theta_full: float               # Θ(D|C) — the stopping target
    theta_history: List[float]      # Θ(D|R) after each greedy addition
    iterations: int
    n_evaluations: int              # candidate evaluations performed (bench metric)
    elapsed_s: float
    per_iteration_s: List[float]
    # set by the serving layer's graceful degradation (§3.10): True marks a
    # last-known-good result served because the fresh dispatch failed
    stale: bool = False

    @property
    def n_selected(self) -> int:
        return len(self.reduct)


def raw_granularity(x: jnp.ndarray, d: jnp.ndarray, *, n_dec: int, v_max: int) -> Granularity:
    """A decision table *without* GrC initialization: every row is a granule.

    This is what HAR/FSPA operate on — evaluation cost scales with |U|, not
    |U/A|, exactly the gap the paper's Fig. 9 measures.
    """
    n, n_attrs = x.shape
    return Granularity(
        x=jnp.asarray(x, jnp.int32),
        d=jnp.asarray(d, jnp.int32),
        w=jnp.ones((n,), jnp.int32),
        valid=jnp.ones((n,), bool),
        num=jnp.int32(n),
        n_total=jnp.int32(n),
        n_attrs=n_attrs,
        n_dec=n_dec,
        v_max=v_max,
    )


# ---------------------------------------------------------------------------
# jitted inner pieces
# ---------------------------------------------------------------------------


@jax.jit
def _full_fingerprints(x, valid):
    h1 = row_fingerprints(x, 0)
    h2 = row_fingerprints(x, 7919)
    return h1, h2


@lru_cache(maxsize=None)
def _eval_chunk_incremental(delta, backend, n_bins, m, v_max,
                            selector=None):
    """Evaluate a chunk of candidates via packed incremental ids (optimized)."""

    @jax.jit
    def run(r_ids, cand_cols, x, d, w, active, n, pr_correction):
        x_cand = jnp.take(x, cand_cols, axis=1).T          # [nc, G]
        packed = pack_ids(r_ids[None, :], x_cand, v_max)    # [nc, G]
        return candidate_theta(
            delta, packed, d, w, active, n, n_bins=n_bins, m=m,
            backend=backend, selector=selector
        ) + pr_correction

    return run


@lru_cache(maxsize=None)
def _eval_chunk_sweep(delta, backend, n_bins, m, v_max, selector=None):
    """Sweep backends (DESIGN.md §5.3): read-once slab form — candidate rows
    sliced from the pre-transposed ``x_t [A, cap]``, pack fused downstream."""

    @jax.jit
    def run(r_ids, cand_cols, x_t, d, w, active, n, pr_correction):
        x_cand = jnp.take(x_t, cand_cols, axis=0)          # [nc, cap]
        return candidate_theta(
            delta, None, d, w, active, n, n_bins=n_bins, m=m,
            backend=backend, x_t=x_cand, r_ids=r_ids, v_max=v_max,
            selector=selector
        ) + pr_correction

    return run


@lru_cache(maxsize=None)
def _eval_chunk_spark(delta, n_bins, m, v_max):
    """Paper-faithful: re-key granules from scratch + sort per candidate."""

    @jax.jit
    def run(hR1, hR2, cand_cols, x, d, w, active, n, pr_correction):
        def one(col):
            t1 = dyn_column_terms(x, col, 0)
            t2 = dyn_column_terms(x, col, 7919)
            ids, _k = ids_by_sort([hR2 + t2, hR1 + t1], active)
            cont = contingency_from_ids(ids, d, w, active, n_bins=n_bins, m=m)
            return measures.evaluate(delta, cont, n)

        return jax.lax.map(one, cand_cols) + pr_correction

    return run


@lru_cache(maxsize=None)
def _make_advance(n_bins, v_max, m, delta):
    @jax.jit
    def advance(r_ids, x_col, d, w, active, n):
        packed = pack_ids(r_ids, x_col, v_max)
        new_ids, k_new, _ = compact_ids(packed, active, n_bins)
        cont = contingency_from_ids(new_ids, d, w, active, n_bins=n_bins, m=m)
        theta = measures.evaluate(delta, cont, n)
        # purity per class → per granule (for FSPA-style shrinking)
        e = cont.sum(-1)
        pure_row = (cont.max(-1) == e) & (e > 0)
        g_pure = pure_row[new_ids] & active
        return new_ids, k_new, theta, g_pure

    return advance


# ---------------------------------------------------------------------------
# core (attribute core) computation
# ---------------------------------------------------------------------------


def _core_path(exact: bool, n_attrs: int) -> str:
    """How :func:`_core_inner_thetas` groups: ``"exact"`` (prefix and
    suffix ranks, paired) or ``"sketch"`` (fingerprint sorts)."""
    return "exact" if exact and n_attrs <= 128 else "sketch"


@partial(jax.jit, static_argnames=("v_max",))
def _exact_core_ids(x, valid, v_max):
    """Exact class ids of C\\{a} for every a, ``([A, cap], K [A])``: row a
    is bitwise ``exact_class_ids(x[:, C\\{a}], valid, radix=v_max)``.

    The class of a row under C\\{a} is the pair (P_a, S_{a+1}): P_a ranks
    the prefix (x_0…x_{a−1}) as the left-to-right fold of
    :func:`exact_class_ids` does, S_{a+1} ranks the suffix
    (x_{a+1}…x_{A−1}) lexicographically by a right-to-left fold,
    ``x_j·cap + S_{j+1}`` with x_j the primary key.  Ranking the pair by a
    two-key sort, P_a primary, numbers the classes as a lexsort on C\\{a}
    would.  Both scans fold A − 1 columns into ``cap·v_max`` presence bins;
    the last attribute's ids are P_{A−1} itself.  Nothing here depends on
    the measure, so the sort (the costly compile) is compiled once a shape.
    """
    cap = x.shape[0]
    n_bins = cap * v_max
    x_t = x.T

    def ranks(p):
        return ids_from_presence(presence_bitmap(p, valid, n_bins), p, valid)

    def suffix(s, col):
        s, _ = ranks(col * cap + s)
        return s, s

    def prefix(carry, xs):
        p, _ = carry
        col, s_next = xs
        return ranks(p * v_max + col), ids_by_sort([s_next, p], valid)

    zeros = jnp.zeros((cap,), jnp.int32)
    _, s = jax.lax.scan(suffix, zeros, x_t[1:], reverse=True)
    init = (zeros, jnp.any(valid).astype(jnp.int32))
    (p, k), (ids, ks) = jax.lax.scan(prefix, init, (x_t[:-1], s))
    return (jnp.concatenate([ids, p[None]]),
            jnp.concatenate([ks, k[None]]))


@lru_cache(maxsize=None)
def _exact_core(delta, n_bins, m):
    """Θ(D|C\\{a}) for every a from :func:`_exact_core_ids`' ``[A, cap]``
    ids: one program per measure and shape, a ``lax.map`` of contingency
    and Θ over the attributes."""

    @jax.jit
    def core(ids, d, w, valid, n_total):
        def one(ids_a):
            cont = contingency_from_ids(ids_a, d, w, valid, n_bins=n_bins,
                                        m=m)
            return measures.evaluate(delta, cont, n_total)

        return jax.lax.map(one, ids)

    return core


@lru_cache(maxsize=None)
def _sketch_core(delta, n_bins, m, chunk, n_attrs):
    """Θ(D|C\\{a}) for every a of an ``n_attrs``-column table, from the
    linear sketch h(C\\{a}) = h(C) − term_a: one program per shape, every
    attribute in one dispatch (a ``lax.map`` over ⌈A/chunk⌉ chunks of
    ``chunk`` attributes, each chunk's groupings batched)."""

    @jax.jit
    def sketch(x, d, w, valid, n_total, h1, h2):
        def one(col):
            t1 = dyn_column_terms(x, col, 0)
            t2 = dyn_column_terms(x, col, 7919)
            ids, _k = ids_by_sort([h2 - t2, h1 - t1], valid)
            cont = contingency_from_ids(ids, d, w, valid, n_bins=n_bins, m=m)
            return measures.evaluate(delta, cont, n_total)

        return jax.lax.map(one, jnp.arange(n_attrs, dtype=jnp.int32),
                           batch_size=chunk)

    return sketch


def _readbacks(path: str):
    return obs.counter(f"plar_core_readbacks_{path}_total",
                       f"device-to-host reads of the core's {path} path")


def _core_inner_thetas(gran: Granularity, delta: str, *, exact: bool, chunk: int = 64) -> np.ndarray:
    """Θ(D|C\\{a}) for every a ∈ C (paper lines 3–8, the MP'd core step)."""
    A = gran.n_attrs
    cap = gran.capacity
    n_bins = cap  # ≤ G distinct classes always
    path = _core_path(exact, A)

    if path == "exact":
        ids, _ = _exact_core_ids(gran.x, gran.valid, gran.v_max)
        core = _exact_core(delta, n_bins, gran.n_dec)
        out = np.asarray(core(ids, gran.d, gran.w, gran.valid, gran.n_total),
                         np.float64)
        _readbacks(path).inc()
        return out

    # Linear-sketch path: h(C\{a}) = h(C) - term_a  — O(1) per candidate.
    with obs.span("core.fingerprints"):
        h1, h2 = jax.block_until_ready(_full_fingerprints(gran.x, gran.valid))
    with obs.span("core.sketch", A=A, chunks=-(-A // chunk), capacity=cap):
        sketch = _sketch_core(delta, n_bins, gran.n_dec, chunk, A)
        out = np.asarray(sketch(gran.x, gran.d, gran.w, gran.valid,
                                gran.n_total, h1, h2), np.float64)
    _readbacks(path).inc()
    return out


# ---------------------------------------------------------------------------
# main driver
# ---------------------------------------------------------------------------


def _shrink_capacity(gran: Granularity) -> Granularity:
    """Shrink the static capacity to the live granule count (next pow2):
    the paper's space win |U/A| ≪ |U| only pays if downstream shapes shrink
    with it.  One host sync — the Spark analogue is the driver's count()
    action after caching the RDD.  Streaming and monolithic builds land on
    the *same* capacity here (same live count), which is what makes their
    reducts and Θ histories byte-identical (engine n_bins = cap·v_max)."""
    cap2 = next_pow2(max(int(gran.num), 16))
    return with_capacity(gran, cap2) if cap2 != gran.capacity else gran


def _iter_chunks(source, chunk_rows: int):
    """Chunk iterator over the *protocol* surface (``n_chunks``/``chunk``)
    only — a conforming GranuleSource need not provide the ``chunks``
    convenience wrapper TabularStream has."""
    return (source.chunk(i, chunk_rows) for i in range(source.n_chunks(chunk_rows)))


def _materialize(source, chunk_rows: int):
    """Concatenate a GranuleSource's chunks into full (x, d) host arrays."""
    xs, ds = zip(*_iter_chunks(source, chunk_rows))
    return np.concatenate(xs), np.concatenate(ds)


def _check_source_args(x, d, source):
    """Shared (x, d)/source exclusivity + source-type validation — one copy
    for both drivers, so the error surface cannot drift between them."""
    if source is not None and (x is not None or d is not None):
        raise ValueError("pass either (x, d) arrays or source=, not both")
    if source is None and (x is None or d is None):
        raise ValueError("pass (x, d) arrays or source=")
    if (source is not None and not isinstance(source, Granularity)
            and not hasattr(source, "chunk")):
        raise TypeError(
            f"source must be a Granularity or GranuleSource, got {type(source)!r}")


def resolve_granularity(
    x=None,
    d=None,
    *,
    source=None,
    grc_init: bool = True,
    n_dec: Optional[int] = None,
    v_max: Optional[int] = None,
    exact: bool = True,
    chunk_rows: int = 65536,
) -> Granularity:
    """The one ingestion seam: everything the drivers accept → ``Granularity``.

    * a prebuilt :class:`Granularity` (``source=``) — used as-is (capacity
      re-packed when ``grc_init``, verbatim otherwise);
    * a :class:`~repro.data.GranuleSource` (``source=``, anything with a
      ``chunk`` method) — streamed chunkwise through
      :func:`build_granularity_streaming`, so the decision table never
      exists whole.  ``grc_init=False`` (the HAR/FSPA cost model: every raw
      row its own granule) has no compressed representation to stream into,
      so the chunks are materialized — unrunnable at paper scale *by
      design*; that cost gap is the paper's Fig. 9.
    * raw ``(x, d)`` arrays — the legacy path, now a thin adapter over the
      same build.

    Metadata: a source's declared ``n_dec``/``v_max`` are authoritative; the
    array adapter *infers* them from realized data when not given.  Byte
    parity between the two paths therefore requires passing the declared
    values to the array call too (a class that happens never to materialize
    would otherwise change the inferred ``m``/``n_bins``).
    """
    _check_source_args(x, d, source)

    if isinstance(source, Granularity):
        return _shrink_capacity(source) if grc_init else source

    if source is not None:
        n_dec = source.n_dec if n_dec is None else n_dec
        v_max = source.v_max if v_max is None else v_max
        if grc_init:
            return _shrink_capacity(build_granularity_streaming(
                _iter_chunks(source, chunk_rows), n_dec=n_dec, v_max=v_max,
                exact=exact))
        x, d = _materialize(source, chunk_rows)

    x = jnp.asarray(x, jnp.int32)
    d = jnp.asarray(d, jnp.int32)
    if n_dec is None:
        n_dec = int(jnp.max(d)) + 1
    if v_max is None:
        v_max = int(jnp.max(x)) + 1
    if not grc_init:
        return raw_granularity(x, d, n_dec=n_dec, v_max=v_max)
    return _shrink_capacity(
        build_granularity(x, d, n_dec=n_dec, v_max=v_max, exact=exact))


def _validate_warm_start(warm_start, n_attrs: Optional[int]) -> List[int]:
    """Canonicalize + validate a warm-start prefix (shared by the sequential
    driver and the ensemble grid — one error surface for both).

    ``n_attrs=None`` skips the range check (grid normalization runs before a
    granularity exists; the driver re-validates with the real A).

    A prefix longer than ``max_features`` is deliberately NOT an error:
    like core attributes, the forced prefix folds unconditionally and the
    cap gates only further greedy additions — a cold run whose core
    overflows the cap returns more than ``max_features`` attributes, and
    warm-repairing from that result must be expressible (DESIGN.md §3.9).
    """
    warm: List[int] = []
    for a in warm_start:
        ai = int(a)
        if ai != a:
            raise ValueError(
                f"warm_start entries must be integral attribute "
                f"indices, got {a!r}")
        warm.append(ai)
    if len(set(warm)) != len(warm):
        raise ValueError(f"warm_start contains duplicates: {warm}")
    if n_attrs is not None:
        bad = [a for a in warm if not 0 <= a < n_attrs]
        if bad:
            raise ValueError(
                f"warm_start attributes {bad} out of range [0, {n_attrs})")
    return warm


def plar_reduce(
    x=None,
    d=None,
    *,
    source=None,                         # Granularity | GranuleSource (alt. to x, d)
    chunk_rows: int = 65536,             # streaming-ingestion chunk size
    delta: str = "PR",
    n_dec: Optional[int] = None,
    v_max: Optional[int] = None,
    eps: float = 0.0,
    tol: float = 1e-6,
    tie_tol: float = 1e-5,
    max_features: Optional[int] = None,
    mode: str = "incremental",          # "incremental" (optimized) | "spark" (paper-faithful)
    backend: str = "segment",           # Θ backend: segment|onehot|pallas|fused|fused_xla|sweep|sweep_xla
    ladder: bool = False,                # K-adaptive bin ladder (DESIGN.md §5.3)
    selector: str = "analytic",          # tile/rung selection: heuristic|analytic|pinned
    mp_chunk: int = 64,                  # model-parallelism level (paper Table 12 knob)
    grc_init: bool = True,               # paper Fig. 9 knob
    shrink: bool = False,                # FSPA universe shrinking
    exact: bool = True,
    compute_core: bool = True,
    engine: str = "auto",                # "device" while_loop | "host" legacy loop
    warm_start: Optional[Sequence[int]] = None,  # resume greedy from this prefix
) -> ReductionResult:
    """PLAR (Algorithm 2) on one process.  See module docstring for modes.

    ``warm_start`` seeds the selection with a previously chosen prefix (the
    online-service repair path, DESIGN.md §3.7): the prefix attributes are
    folded as forced selections — re-recording their Θ values on *this*
    granularity — and the greedy loop resumes from there.  It replaces the
    core computation (the prefix stands in for the core, so ``core`` comes
    back empty) and, on the device engine, runs as a seed + resume pair of
    dispatches of the same single compile
    (:func:`~repro.core.engine.init_state_from_reduct` /
    :func:`~repro.core.engine.engine_resume`).  For a prefix the cold run
    would itself have selected, the result is byte-identical to the cold run
    (asserted by tests/test_engine.py::test_warm_start_parity).

    Like core attributes, the forced prefix folds unconditionally:
    ``max_features`` caps only further *greedy* additions — a prefix longer
    than the cap folds whole and adds nothing, mirroring a cold run whose
    forced core overflows the cap (so warm-repairing from such a result
    stays expressible).  A prefix is validated up front — entries must be
    integral, unique, and in ``[0, A)`` — raising ``ValueError`` instead of
    a shape error inside the compiled engine.  ``warm_start=prefix,
    max_features=len(prefix)`` folds the prefix and adds nothing — a pure
    re-evaluation of its Θ trajectory.
    """
    t0 = time.perf_counter()
    if mode not in _MODES:
        raise ValueError(
            f"unknown mode: {mode!r} (one of: {', '.join(_MODES)})")
    if backend not in _BACKENDS:
        raise ValueError(
            f"unknown Θ backend: {backend!r} (one of: {', '.join(_BACKENDS)})")
    from repro.kernels.contingency.autotune import SELECTOR_MODES
    if selector not in SELECTOR_MODES:
        raise ValueError(
            f"unknown selector: {selector!r} "
            f"(one of: {', '.join(SELECTOR_MODES)})")
    engine = _resolve_engine(engine, backend)
    kind = ("arrays" if source is None else
            "granules" if isinstance(source, Granularity) else "rows")
    with obs.span("reduction.plar_reduce", delta=delta, engine=engine,
                  source=kind) as root:
        gran = resolve_granularity(
            x, d, source=source, grc_init=grc_init, n_dec=n_dec, v_max=v_max,
            exact=exact, chunk_rows=chunk_rows)

        A = gran.n_attrs
        m = gran.n_dec
        cap = gran.capacity
        n = gran.n_total
        n_evals = 0
        root.set(A=A, capacity=cap)

        warm: Optional[List[int]] = None
        if warm_start is not None:
            warm = _validate_warm_start(warm_start, A)

        # Θ(D|C): stopping target.
        with obs.span("reduction.theta_full"):
            all_cols = jnp.arange(A, dtype=jnp.int32)
            ids_c, _k = subset_ids(gran, all_cols, exact=exact)
            cont_c = contingency_from_ids(ids_c, gran.d, gran.w, gran.valid,
                                          n_bins=cap, m=m)
            theta_full = float(measures.evaluate(delta, cont_c, n))

        # --- core (skipped under warm_start: the prefix stands in for it) ---
        core: List[int] = []
        if compute_core and warm is None:
            with obs.span("reduction.core", A=A,
                          path=_core_path(exact, A)):
                inner = _core_inner_thetas(gran, delta, exact=exact)
            sig = inner - theta_full  # Θ(D|C\{a}) - Θ(D|C)
            core = [int(a) for a in range(A) if sig[a] > eps + tie_tol]
            n_evals += A
        forced = core if warm is None else warm

        if engine == "device":
            # Device-resident engine: core folding + greedy loop + stopping rule
            # run as ONE lax.while_loop (core/engine.py) — a single dispatch, a
            # single compile (n_bins = cap·v_max is static), and one device→host
            # transfer at the end.
            max_sel = int(max_features) if max_features is not None else A
            runner = make_engine_run(
                delta, mode, backend, A, cap, m, gran.v_max, float(tol),
                float(tie_tol), bool(shrink), max_sel, int(mp_chunk),
                bool(ladder), str(selector))
            reduct, theta_hist, iterations, ev, per_iter = run_engine(
                runner, cap, A, gran.valid, gran.x, gran.d, gran.w, n,
                theta_full, core, warm_start=warm)
            root.set(k=len(reduct))
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

        # --- greedy loop state (engine == "host": the legacy escape hatch) ---
        r_ids = jnp.zeros((cap,), jnp.int32)
        k = 1
        active = gran.valid
        # float32 accumulation, mirroring the device engine bit-for-bit (so the
        # two engines' theta histories are byte-identical, asserted in tests)
        pr_correction = np.float32(0.0)
        reduct: List[int] = []
        theta_hist: List[float] = []
        per_iter_s: List[float] = []

        v = gran.v_max

        # The advance (and, ladder off, the evaluation) uses the engine's static
        # bin bound cap·V: one compile for the whole run (no power-of-two
        # recompile ladder) and Θ summed over the same padded rows as
        # engine="device" — zero rows add exactly 0 in f32, but reduction
        # *grouping* depends on length, so equal lengths ⇒ equal bits (candidate
        # thetas AND recorded histories).  The §5.3 ladder shrinks only the
        # *candidate evaluation* bins; the advance keeps the full bound, which is
        # what keeps theta histories byte-identical across every (backend,
        # ladder) combination.
        adv = _make_advance(cap * v, v, m, delta)

        # K-adaptive candidate-eval bins (ladder on): the host twin of the
        # engine's lax.switch — same static rung set, chosen per iteration from
        # the synced k, one (lru-cached) compile per rung actually visited.
        # The selector-pruned set is a function of (cap, m) only, so host and
        # device engines derive identical rungs (byte parity, DESIGN.md §5.3).
        rungs = ladder_rungs(cap * v, selector=selector, g=cap, m=m)

        def _eval_bins_for(k_):
            if ladder:
                return rung_for(k_, v, rungs)
            # device-capable backends pin the full static bound for bit parity
            # with engine="device"; host-only Pallas backends keep the cheaper
            # pow2 ladder (no device twin to match)
            return cap * v if backend in DEVICE_BACKENDS else _next_pow2(max(k_, 1)) * v

        # read-once candidate slab for the sweep backends, hoisted out of the
        # loop (the device engine hoists the same transpose before its while_loop)
        x_t_full = jnp.swapaxes(gran.x, 0, 1) if backend in SWEEP_BACKENDS else None

        # The stop threshold mirrors the device cond's f32 arithmetic exactly, so
        # both engines run the same number of iterations even when theta_r lands
        # within an ulp of it.
        stop_thresh = measures.f32_threshold(theta_full, tol)

        def _shrink_step(g_pure):
            nonlocal pr_correction, active
            if delta == "PR":
                shed = jnp.sum(jnp.where(g_pure, gran.w, 0)).astype(jnp.float32)
                pr_correction = pr_correction - np.float32(shed / jnp.float32(n))
            active = active & ~g_pure

        # fold the forced prefix (core attributes, or the warm-start prefix)
        for a in forced:
            r_ids, k_new, theta_r, g_pure = adv(r_ids, gran.x[:, a], gran.d, gran.w, active, n)
            k = int(k_new)
            reduct.append(a)
            theta_hist.append(float(np.float32(theta_r) + pr_correction))
            if shrink:
                _shrink_step(g_pure)

        theta_r = theta_hist[-1] if theta_hist else float("inf")

        remaining = [a for a in range(A) if a not in reduct]
        iterations = 0
        while remaining and theta_r > stop_thresh:
            if max_features is not None and len(reduct) >= max_features:
                break
            it0 = time.perf_counter()
            nc = min(mp_chunk, max(len(remaining), 1))

            thetas = np.full((len(remaining),), np.inf, np.float64)
            if mode == "spark":
                # re-key from scratch: fingerprint of current R columns
                if reduct:
                    hR1 = sum_terms(gran.x, reduct, 0)
                    hR2 = sum_terms(gran.x, reduct, 7919)
                else:
                    hR1 = jnp.zeros((cap,), jnp.uint32)
                    hR2 = jnp.zeros((cap,), jnp.uint32)
                runner = _eval_chunk_spark(delta, cap, m, v)
                for s in range(0, len(remaining), nc):
                    cols = np.asarray(remaining[s : s + nc], np.int32)
                    pad = nc - len(cols)
                    padded = np.concatenate([cols, np.full((pad,), cols[-1], np.int32)])
                    vals = np.asarray(
                        runner(hR1, hR2, jnp.asarray(padded), gran.x, gran.d, gran.w, active, n, pr_correction)
                    )
                    thetas[s : s + len(cols)] = vals[: len(cols)]
            else:
                # Candidate-eval bin bound: full static cap·V for device-capable
                # backends (bit parity with engine="device"), a §5.3 rung when
                # the ladder is on (matching the device engine's switch), pow2
                # for the host-only Pallas backends.
                eval_bins = _eval_bins_for(k)
                if backend in SWEEP_BACKENDS:
                    runner = _eval_chunk_sweep(delta, backend, eval_bins, m, v,
                                               selector)
                    table = x_t_full
                else:
                    runner = _eval_chunk_incremental(delta, backend, eval_bins,
                                                     m, v, selector)
                    table = gran.x
                for s in range(0, len(remaining), nc):
                    cols = np.asarray(remaining[s : s + nc], np.int32)
                    pad = nc - len(cols)
                    padded = np.concatenate([cols, np.full((pad,), cols[-1], np.int32)])
                    vals = np.asarray(
                        runner(r_ids, jnp.asarray(padded), table, gran.d, gran.w, active, n, pr_correction)
                    )
                    thetas[s : s + len(cols)] = vals[: len(cols)]
            n_evals += len(remaining)

            best = measures.argmin_with_ties(thetas, tie_tol)  # paper line 13: argmin Θ
            a_opt = remaining[best]

            r_ids, k_new, theta_active, g_pure = adv(r_ids, gran.x[:, a_opt], gran.d, gran.w, active, n)
            k = int(k_new)
            theta_r = float(np.float32(theta_active) + pr_correction)
            reduct.append(a_opt)
            remaining.remove(a_opt)
            theta_hist.append(theta_r)
            if shrink:
                _shrink_step(g_pure)
            iterations += 1
            per_iter_s.append(time.perf_counter() - it0)

        root.set(k=len(reduct))
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


# ---------------------------------------------------------------------------
# reduct ensembles: one compile for a whole config grid (DESIGN.md §3.8)
# ---------------------------------------------------------------------------


# Per-config knobs the ensemble grid accepts; everything else (mode, backend,
# ladder, mp_chunk, ingestion) is shared — those are *static* trace choices,
# and sharing them is what lets the grid share one compile.
_ENSEMBLE_DEFAULTS = {
    "delta": "PR",
    "tol": 1e-6,
    "tie_tol": 1e-5,
    "max_features": None,
    "shrink": False,
    "compute_core": True,
    "eps": 0.0,
    "seed": None,          # bagged row-weight resample seed (None = no bag)
    "warm_start": None,    # forced greedy-resume prefix (replaces the core)
}

# Driver kwargs of :func:`plar_reduce` that the stacked engine *shares*
# across a grid (static trace choices + ingestion) — the complement of
# ``_ENSEMBLE_DEFAULTS``.  The serving scheduler uses this split to decide
# whether heterogeneous single-config queries can ride one stacked dispatch:
# per-config knobs may differ, shared knobs must agree.
ENSEMBLE_SHARED_KEYS = ("mode", "backend", "ladder", "selector", "mp_chunk",
                        "exact", "grc_init", "chunk_rows")


def partition_reduce_params(delta: str, params: dict):
    """Split one ``plar_reduce``-style ``(delta, params)`` query into the
    ``(config, shared)`` pair the stacked ensemble engine takes — or return
    ``None`` when the query cannot be expressed on it.

    A query is stackable when its measure is in :data:`ENSEMBLE_DELTAS`,
    every param is either a per-config grid knob (``_ENSEMBLE_DEFAULTS``) or
    a shared static (:data:`ENSEMBLE_SHARED_KEYS`), the backend (if given)
    is an :data:`ENSEMBLE_BACKENDS` member, and the ladder (if on) rides
    ``sweep_xla`` (the §3.8 shared-rung constraint).  Queries that fall
    outside — host-only Pallas backends, ``engine="host"``, unknown knobs —
    are served solo by the scheduler instead.
    """
    if delta not in ENSEMBLE_DELTAS:
        return None
    config = {"delta": delta}
    shared = {}
    for k, v in params.items():
        if k in _ENSEMBLE_DEFAULTS and k != "delta":
            config[k] = v
        elif k in ENSEMBLE_SHARED_KEYS:
            shared[k] = v
        else:
            return None
    if shared.get("backend", "segment") not in ENSEMBLE_BACKENDS:
        return None
    if shared.get("ladder") and shared.get("backend") != "sweep_xla":
        return None
    if shared.get("mode", "incremental") not in _MODES:
        return None
    return config, shared


def expand_ensemble_grid(configs, seeds=None):
    """Expand ``configs`` (dicts or bare measure names) × ``seeds``.

    ``seeds`` crosses every config with one bagged replica per seed (the
    bagged-ensemble idiom: ``configs=["PR"], seeds=range(8)`` is an 8-bag
    PR ensemble).  Configs carrying their own explicit ``seed`` cannot be
    combined with ``seeds=`` (ambiguous).  Returns plain dicts, defaults
    NOT yet filled — callers that key caches off configs use this expanded
    raw form so cache keys stay minimal.
    """
    expanded = []
    for c in configs:
        if isinstance(c, str):
            c = {"delta": c}
        c = dict(c)
        if seeds is None:
            expanded.append(c)
            continue
        if c.get("seed") is not None:
            raise ValueError(
                "pass bag seeds either per config ('seed') or via seeds=, "
                "not both")
        for s in seeds:
            expanded.append({**c, "seed": int(s)})
    return expanded


def normalize_ensemble_configs(configs, seeds=None) -> List[dict]:
    """Validate + default-fill an ensemble grid (see ``_ENSEMBLE_DEFAULTS``)."""
    expanded = expand_ensemble_grid(configs, seeds)
    if not expanded:
        raise ValueError("ensemble configs must be non-empty")
    out = []
    for c in expanded:
        unknown = sorted(set(c) - set(_ENSEMBLE_DEFAULTS))
        if unknown:
            raise ValueError(
                f"unknown ensemble config keys {unknown} "
                f"(one of: {', '.join(sorted(_ENSEMBLE_DEFAULTS))})")
        full = {**_ENSEMBLE_DEFAULTS, **c}
        if full["delta"] not in ENSEMBLE_DELTAS:
            raise ValueError(
                f"unknown measure: {full['delta']!r} "
                f"(one of: {', '.join(ENSEMBLE_DELTAS)})")
        if full["warm_start"] is not None:
            # integral/dupe validation here; range re-checked by the
            # driver once the granularity (and so A) exists
            full["warm_start"] = _validate_warm_start(
                full["warm_start"], None)
        out.append(full)
    return out


def bagged_weights(gran: Granularity, seed: int) -> np.ndarray:
    """Bootstrap resample of the row multiset as granule weights ``[cap]``.

    Draws ``n_total`` rows with replacement from the live rows — a
    multinomial over granules weighted by ``w`` — and returns the resampled
    per-granule counts.  Reweighting ``w`` keeps the granularity itself
    (``x``/ids/capacity) shared across every bag: granules are equivalence
    classes of *attribute values*, so a row resample only changes how many
    rows sit in each class, never the classes — no per-seed rebuild, and the
    stacked engine can carry all bags over one granule table.  Zero-weight
    granules stay live (``valid`` is untouched): they contribute 0 to every
    contingency and Θ, and keeping them preserves class numbering so results
    match a sequential run on the same reweighted granularity bit-for-bit.
    """
    w = np.asarray(gran.w, np.int64)
    valid = np.asarray(gran.valid)
    live = np.where(valid, w, 0)
    total = int(live.sum())
    if total <= 0:
        raise ValueError("cannot bag an empty granularity")
    rng = np.random.default_rng(int(seed))
    return rng.multinomial(total, live / live.sum()).astype(np.int32)


def plar_reduce_ensemble(
    x=None,
    d=None,
    *,
    source=None,                         # Granularity | GranuleSource (alt. to x, d)
    configs: Sequence,                   # per-config dicts (or measure names)
    seeds: Optional[Sequence[int]] = None,  # bag grid: configs × seeds
    chunk_rows: int = 65536,
    n_dec: Optional[int] = None,
    v_max: Optional[int] = None,
    mode: str = "incremental",
    backend: str = "segment",            # ENSEMBLE_BACKENDS
    ladder: bool = False,                # requires backend="sweep_xla"
    selector: str = "analytic",          # tile/rung selection mode
    mp_chunk: int = 64,
    grc_init: bool = True,
    exact: bool = True,
) -> List[ReductionResult]:
    """A grid of PLAR reductions over ONE granularity in ONE engine dispatch.

    Every config runs the same greedy selection :func:`plar_reduce` would —
    per-config reducts and Θ histories are byte-identical to N sequential
    runs (tests/test_ensemble.py) — but the grid shares a single XLA compile
    and a single pass over the granule/candidate tiles per iteration
    (DESIGN.md §3.8).  Per-config knobs: ``delta``, ``tol``, ``tie_tol``,
    ``max_features``, ``shrink``, ``compute_core``, ``eps``, ``seed``
    (a bagged row-weight resample via :func:`bagged_weights`; the sequential
    twin of config ``c`` is then ``plar_reduce`` on the same granularity
    with ``w`` replaced), and ``warm_start`` (a forced greedy-resume prefix
    riding the forced-core path — the stacked twin of
    ``plar_reduce(warm_start=...)``, byte-identical to it per config, which
    is what lets the serving scheduler batch warm repairs).  Shared knobs
    (``mode``, ``backend``, ``ladder``, ``mp_chunk``) are static trace
    choices.

    Results come back in grid order (``configs`` × ``seeds``); ``elapsed_s``
    is the per-config share of the total wall clock, and ``per_iteration_s``
    entries are the loop average over every executed body in the grid.
    """
    t0 = time.perf_counter()
    if mode not in _MODES:
        raise ValueError(
            f"unknown mode: {mode!r} (one of: {', '.join(_MODES)})")
    if backend not in ENSEMBLE_BACKENDS:
        raise ValueError(
            f"ensemble backend must be one of {', '.join(ENSEMBLE_BACKENDS)}; "
            f"got {backend!r} (run plar_reduce per config for host-only "
            f"backends)")
    cfgs = normalize_ensemble_configs(configs, seeds)
    gran = resolve_granularity(
        x, d, source=source, grc_init=grc_init, n_dec=n_dec, v_max=v_max,
        exact=exact, chunk_rows=chunk_rows)

    A = gran.n_attrs
    m = gran.n_dec
    cap = gran.capacity
    C = len(cfgs)

    # Θ(D|C) ids are w-independent — computed once for the whole grid; only
    # the contingency reweights per config.
    all_cols = jnp.arange(A, dtype=jnp.int32)
    ids_c, _k = subset_ids(gran, all_cols, exact=exact)

    base_w = np.asarray(gran.w, np.int32)
    ws = np.zeros((C, cap), np.int32)
    core_attrs = np.zeros((C, max(A, 1)), np.int32)
    core_counts = np.zeros((C,), np.int32)
    delta_idx = np.zeros((C,), np.int32)
    theta_fulls = np.zeros((C,), np.float64)
    ns = np.zeros((C,), np.int64)
    cores: List[List[int]] = []
    evals0 = np.zeros((C,), np.int64)

    for j, c in enumerate(cfgs):
        w_j = (bagged_weights(gran, c["seed"]) if c["seed"] is not None
               else base_w)
        n_j = int(np.where(np.asarray(gran.valid), w_j, 0).sum())
        ws[j] = w_j
        ns[j] = n_j
        delta_idx[j] = ENSEMBLE_DELTAS.index(c["delta"])
        cont_j = contingency_from_ids(
            ids_c, gran.d, jnp.asarray(w_j), gran.valid, n_bins=cap, m=m)
        theta_fulls[j] = float(
            measures.evaluate(c["delta"], cont_j, jnp.int32(n_j)))

        core_j: List[int] = []
        if c["warm_start"] is not None:
            # warm resume (DESIGN.md §3.7 on the stacked engine): the prefix
            # stands in for the core — forced folds through the same
            # core_attrs path, core computation skipped, ``core`` comes back
            # empty, exactly like ``plar_reduce(warm_start=...)``
            forced_j = _validate_warm_start(c["warm_start"], A)
            core_attrs[j, : len(forced_j)] = forced_j
            core_counts[j] = len(forced_j)
        elif c["compute_core"]:
            gran_j = gran if c["seed"] is None else dataclasses.replace(
                gran, w=jnp.asarray(w_j), n_total=jnp.int32(n_j))
            inner = _core_inner_thetas(gran_j, c["delta"], exact=exact)
            sig = inner - theta_fulls[j]
            core_j = [int(a) for a in range(A)
                      if sig[a] > c["eps"] + c["tie_tol"]]
            evals0[j] = A
            core_attrs[j, : len(core_j)] = core_j
            core_counts[j] = len(core_j)
        cores.append(core_j)

    ops = EnsembleOperands(
        delta_idx=jnp.asarray(delta_idx),
        tol=jnp.asarray([c["tol"] for c in cfgs], jnp.float32),
        tie_tol=jnp.asarray([c["tie_tol"] for c in cfgs], jnp.float32),
        max_sel=jnp.asarray(
            [A if c["max_features"] is None else int(c["max_features"])
             for c in cfgs], jnp.int32),
        shrink=jnp.asarray([bool(c["shrink"]) for c in cfgs], bool),
        theta_full=jnp.asarray(theta_fulls, jnp.float32),
        n=jnp.asarray(ns, jnp.int32),
        w=jnp.asarray(ws),
        core_attrs=jnp.asarray(core_attrs),
        core_count=jnp.asarray(core_counts),
    )
    runner = make_ensemble_run(
        mode, backend, C, A, cap, m, gran.v_max, int(mp_chunk), bool(ladder),
        str(selector))
    fin, loop_s = run_ensemble(
        runner, cap, A, gran.valid, gran.x, gran.d, ops)
    per_cfg = unpack_ensemble_result(fin, core_counts)

    elapsed = time.perf_counter() - t0
    total_bodies = sum(len(r[0]) for r in per_cfg)
    per_body = loop_s / total_bodies if total_bodies else 0.0
    results = []
    for j, (reduct, hist, iters, ev) in enumerate(per_cfg):
        results.append(ReductionResult(
            reduct=reduct,
            core=cores[j],
            theta_full=float(theta_fulls[j]),
            theta_history=hist,
            iterations=iters,
            n_evaluations=int(evals0[j]) + ev,
            elapsed_s=elapsed / C,
            per_iteration_s=[per_body] * len(reduct),
        ))
    return results


def sum_terms(x, cols: Sequence[int], seed: int):
    """Fingerprint restricted to a column subset (recomputed from scratch)."""
    h = jnp.zeros((x.shape[0],), jnp.uint32)
    for c in cols:
        h = h + column_terms(x[:, c], c, x.shape[1], seed)
    return h


def har_reduce(x=None, d=None, **kw) -> ReductionResult:
    """Paper baseline: Algorithm 1 — no GrC, sequential, re-key per candidate."""
    kw.setdefault("mode", "spark")
    kw.setdefault("mp_chunk", 1)
    return plar_reduce(x, d, grc_init=False, shrink=False, **kw)


def fspa_reduce(x=None, d=None, **kw) -> ReductionResult:
    """Paper baseline: FSPA — HAR + exact universe shrinking (positive approximation)."""
    kw.setdefault("mode", "spark")
    kw.setdefault("mp_chunk", 1)
    return plar_reduce(x, d, grc_init=False, shrink=True, **kw)
