"""GrC-based initialization: the granularity representation of a decision table.

The paper (PLAR §3.3) converts the decision table ``S = (U, C ∪ D)`` into the
granularity representation ``G^(C∪D) = {(E⃗, |E|)}`` — distinct rows with
multiplicities — once, and caches it in distributed memory.  All later work
(evaluating ``Θ(D|B)`` for candidate subsets ``B``) operates on granules.

TPU/XLA adaptation (static shapes, no host round-trips):

* Rows are fingerprinted with a *linear* polynomial hash
  ``h(row) = Σ_j mix32(x[:, j] ⊕ seed_j) · m_j (mod 2³²)`` with two independent
  seeds.  Linearity lets us add/remove one column's contribution in O(1) — used
  by the attribute-core computation, where the paper re-maps from scratch.
* "unique rows" is the reduceByKey of the GrC build.  ``exact=True`` groups
  the actual columns without a sort (:func:`exact_class_ids`: one
  presence-bitmap compaction per column — a large multi-operand sort is
  what a TPU compiles slowest); ``exact=False`` sorts the 64-bit fingerprint
  pair only (collision probability < G²/2⁻⁶⁴, used for very wide tables
  such as SDSS).  Either way a ``segment_sum`` merges the weights.
* The output table is padded to a static capacity with a validity mask; ``num``
  carries the live granule count.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

__all__ = [
    "Granularity",
    "build_granularity",
    "build_granularity_streaming",
    "fold_chunk",
    "merge_granularity",
    "with_capacity",
    "next_pow2",
    "column_terms",
    "dyn_column_terms",
    "row_fingerprints",
    "regranulate",
    "pack_ids",
    "compact_ids",
    "exact_class_ids",
    "ids_by_sort",
    "project_columns",
]

_GOLDEN = np.uint32(0x9E3779B9)


def _mix32(v: jnp.ndarray) -> jnp.ndarray:
    """SplitMix-style 32-bit finalizer (uint32 in, uint32 out)."""
    v = v.astype(jnp.uint32)
    v = v ^ (v >> 16)
    v = v * jnp.uint32(0x7FEB352D)
    v = v ^ (v >> 15)
    v = v * jnp.uint32(0x846CA68B)
    v = v ^ (v >> 16)
    return v


def _column_seeds(n_cols: int, seed: int) -> np.ndarray:
    """Deterministic per-column (seed, multiplier) pairs, host-side."""
    idx = np.arange(n_cols, dtype=np.uint64)
    mask = np.uint64(0xFFFFFFFF)
    col_seed = (idx * np.uint64(_GOLDEN) + np.uint64(seed) * np.uint64(0x85EBCA6B)) & mask
    mult = (((col_seed ^ (col_seed >> np.uint64(13))) * np.uint64(0xC2B2AE35)) & mask) | np.uint64(1)
    return np.stack([col_seed, mult], axis=0).astype(np.uint32)  # [2, n_cols]


def dyn_column_terms(x: jnp.ndarray, col: jnp.ndarray, seed: int) -> jnp.ndarray:
    """:func:`column_terms` for a *traced* column index (dynamic gather)."""
    seeds = jnp.asarray(_column_seeds(x.shape[1], seed))
    return _mix32(x[:, col].astype(jnp.uint32) ^ seeds[0, col]) * seeds[1, col]


def column_terms(x_col: jnp.ndarray, col_index: int, n_cols: int, seed: int) -> jnp.ndarray:
    """Hash term contributed by one column: mix32(v ⊕ seed_j) · m_j  (uint32).

    ``row_fingerprints(x) == Σ_j column_terms(x[:, j], j)`` — the linear-sketch
    property used to *remove* a column from a fingerprint in O(1).
    """
    seeds = _column_seeds(n_cols, seed)
    cs = jnp.uint32(seeds[0, col_index])
    mult = jnp.uint32(seeds[1, col_index])
    return _mix32(x_col.astype(jnp.uint32) ^ cs) * mult


def row_fingerprints(x: jnp.ndarray, seed: int) -> jnp.ndarray:
    """Linear polynomial fingerprint of each row (uint32), vectorized over columns."""
    n_cols = x.shape[-1]
    seeds = _column_seeds(n_cols, seed)
    cs = jnp.asarray(seeds[0])  # [A]
    mult = jnp.asarray(seeds[1])  # [A]
    terms = _mix32(x.astype(jnp.uint32) ^ cs[None, :]) * mult[None, :]
    return terms.sum(axis=-1, dtype=jnp.uint32)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Granularity:
    """Padded granularity representation ``G^(A)`` of a decision table.

    Attributes:
      x:     [cap, A] int32 — representative feature vector of each granule.
      d:     [cap]    int32 — decision label of each granule.
      w:     [cap]    int32 — multiplicity |E| (0 for padding slots).
      valid: [cap]    bool  — slot liveness mask.
      num:   scalar  int32 — number of live granules G.
      n_total: scalar int32 — |U| = Σ w.
    Static metadata (aux): n_attrs, n_dec (m), v_max (max categorical code + 1).
    """

    x: jnp.ndarray
    d: jnp.ndarray
    w: jnp.ndarray
    valid: jnp.ndarray
    num: jnp.ndarray
    n_total: jnp.ndarray
    n_attrs: int
    n_dec: int
    v_max: int

    @property
    def capacity(self) -> int:
        return self.x.shape[0]

    def tree_flatten(self):
        children = (self.x, self.d, self.w, self.valid, self.num, self.n_total)
        aux = (self.n_attrs, self.n_dec, self.v_max)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


def ids_by_sort(keys: Sequence[jnp.ndarray],
                valid: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact dense ids for arbitrary sort keys (the reduceByKey grouping).

    ``keys[-1]`` is the primary sort key.  Returns ids in *original* order and
    the number of distinct keys K.  Invalid slots get id 0 and do not count.
    """
    n = valid.shape[0]
    sentineled = []
    for k in keys:
        ku = k.astype(jnp.uint32)
        sentineled.append(jnp.where(valid, ku, jnp.uint32(0xFFFFFFFF)))
    order = jnp.lexsort(tuple(sentineled))
    valid_s = valid[order]
    neq = jnp.zeros((n - 1,), bool)
    for k in sentineled:
        ks = k[order]
        neq = neq | (ks[1:] != ks[:-1])
    b = jnp.concatenate([jnp.ones((1,), bool), neq]) & valid_s
    ids_sorted = jnp.cumsum(b.astype(jnp.int32)) - 1
    ids_sorted = jnp.maximum(ids_sorted, 0)
    ids = jnp.zeros((n,), jnp.int32).at[order].set(jnp.where(valid_s, ids_sorted, 0))
    return ids, b.sum().astype(jnp.int32)


@partial(jax.jit, static_argnames=("n_dec", "v_max", "exact", "seed", "capacity"))
def build_granularity(
    x: jnp.ndarray,
    d: jnp.ndarray,
    *,
    n_dec: int,
    v_max: int,
    w: Optional[jnp.ndarray] = None,
    valid: Optional[jnp.ndarray] = None,
    exact: bool = True,
    seed: int = 0,
    capacity: Optional[int] = None,
) -> Granularity:
    """GrC initialization: build ``G^(C∪D)`` from (possibly pre-weighted) rows.

    Accepting input weights makes this the shard-merge step too: re-granulating
    a concatenation of per-shard granule tables merges duplicate keys exactly
    (the reduceByKey of the distributed build).
    """
    n, n_attrs = x.shape
    cap = capacity or n
    if w is None:
        w = jnp.ones((n,), dtype=jnp.int32)
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)
    w = jnp.where(valid, w, 0)

    if exact:
        # granules in lexicographic (x_0, …, x_{A-1}, d) order
        keys = jnp.concatenate([x, d[:, None].astype(x.dtype)], axis=1)
        ids, num = exact_class_ids(keys, valid, radix=max(v_max, n_dec))
    else:
        # fingerprint-pair buckets: primary h1, then h2, then d
        ids, num = ids_by_sort(
            [d, row_fingerprints(x, seed + 7919), row_fingerprints(x, seed)],
            valid)

    # Invalid (padding) rows — and, when num overflows cap, the overflow
    # ids (the caller rebuilds at a larger capacity) — scatter out of
    # bounds and are dropped.
    ids_w = jnp.where(valid & (ids < cap), ids, cap)
    w_g = jax.ops.segment_sum(w, ids_w, num_segments=cap)
    # Representative rows: every row in a segment shares the key, any write wins.
    x_g = jnp.zeros((cap, n_attrs), x.dtype).at[ids_w].set(x)
    d_g = jnp.zeros((cap,), d.dtype).at[ids_w].set(d)
    valid_g = jnp.arange(cap) < num

    return Granularity(
        x=x_g,
        d=d_g,
        w=jnp.where(valid_g, w_g, 0),
        valid=valid_g,
        num=num,
        n_total=w.sum().astype(jnp.int32),
        n_attrs=n_attrs,
        n_dec=n_dec,
        v_max=v_max,
    )


def next_pow2(v: int) -> int:
    """Smallest power of two ≥ v (1 for v ≤ 1)."""
    return 1 << max(0, (int(v) - 1)).bit_length()


def with_capacity(gran: Granularity, capacity: int) -> Granularity:
    """Re-pad a *front-packed* granularity (live slots first, the layout
    :func:`build_granularity` emits) to a new static capacity.

    Shrinking below the live count would silently drop granules, so it
    raises; growing appends zero-weight padding.  One host sync on ``num``
    when shrinking — the Spark analogue is the driver's ``count()`` action.
    """
    cap = gran.capacity
    if capacity == cap:
        return gran
    if capacity < cap:
        if int(gran.num) > capacity:
            raise ValueError(
                f"capacity {capacity} < live granule count {int(gran.num)}")
        if int(gran.valid[:capacity].sum()) != int(gran.num):
            raise ValueError(
                "granularity is not front-packed: live slots extend past the "
                f"requested capacity {capacity}")
        x = gran.x[:capacity]
        d = gran.d[:capacity]
        w = gran.w[:capacity]
        valid = gran.valid[:capacity]
    else:
        pad = capacity - cap
        x = jnp.concatenate([gran.x, jnp.zeros((pad, gran.n_attrs), gran.x.dtype)])
        d = jnp.concatenate([gran.d, jnp.zeros((pad,), gran.d.dtype)])
        w = jnp.concatenate([gran.w, jnp.zeros((pad,), gran.w.dtype)])
        valid = jnp.concatenate([gran.valid, jnp.zeros((pad,), bool)])
    return Granularity(
        x=x, d=d, w=w, valid=valid, num=gran.num, n_total=gran.n_total,
        n_attrs=gran.n_attrs, n_dec=gran.n_dec, v_max=gran.v_max,
    )


def merge_granularity(a: Granularity, b: Granularity, *, exact: bool = True,
                      seed: int = 0, capacity: Optional[int] = None) -> Granularity:
    """Monoid merge: ``G^(A∪B) = G^(A) ⊕ G^(B)`` — the chunked reduceByKey.

    Concatenates the two padded tables and re-granulates with the input
    weights (concat → sort → adjacent-compare → ``segment_sum``), so
    duplicate keys across the operands merge weight-additively.  The merge is
    associative and commutative up to padding: the output's live prefix is
    the *globally sorted* distinct-key table, independent of operand order
    or how rows were split between operands.

    Capacity-doubling policy: the result capacity starts at
    ``next_pow2(max(capacity or 0, a.capacity, b.capacity))`` and doubles
    (via ``next_pow2`` of the true distinct count) whenever the live keys
    overflow it.  The overflow check is one host sync of ``num`` — ``num``
    counts sort boundaries *before* the scatter clips, so a clipped build is
    always detected and rebuilt; capacities stay powers of two so the
    streaming fold compiles O(log G) variants, not one per merge.
    """
    if (a.n_attrs, a.n_dec, a.v_max) != (b.n_attrs, b.n_dec, b.v_max):
        raise ValueError(
            "merge_granularity operands disagree on static metadata: "
            f"{(a.n_attrs, a.n_dec, a.v_max)} vs {(b.n_attrs, b.n_dec, b.v_max)}")
    x = jnp.concatenate([a.x, b.x])
    d = jnp.concatenate([a.d, b.d])
    w = jnp.concatenate([a.w, b.w])
    valid = jnp.concatenate([a.valid, b.valid])
    cap = next_pow2(max(capacity or 1, a.capacity, b.capacity))
    while True:
        g = build_granularity(
            x, d, n_dec=a.n_dec, v_max=a.v_max, w=w, valid=valid,
            exact=exact, seed=seed, capacity=cap,
        )
        num = int(g.num)
        if num <= cap:
            return g
        cap = next_pow2(num)


def build_granularity_streaming(
    chunks,
    *,
    n_dec: int,
    v_max: int,
    exact: bool = True,
    seed: int = 0,
) -> Granularity:
    """GrC initialization without the whole table: fold :func:`merge_granularity`
    over an iterable of ``(x, d)`` row chunks.

    Each chunk is granulated at its own ``next_pow2`` capacity and merged
    into the accumulator, so peak memory is O(chunk + accumulator capacity)
    — the decision table never exists whole.  Because the merge is a monoid
    and the final fold step re-sorts the full distinct-key set, the live
    prefix of the result is *element-wise identical* to a monolithic
    :func:`build_granularity` over the concatenated rows (only the padded
    capacity may differ); `tests/test_streaming.py` asserts this per
    chunk size.
    """
    acc: Optional[Granularity] = None
    for xc, dc in chunks:
        acc = fold_chunk(acc, xc, dc, n_dec=n_dec, v_max=v_max, exact=exact,
                         seed=seed)
    if acc is None:
        raise ValueError("build_granularity_streaming: no non-empty chunks")
    return acc


def fold_chunk(acc: Optional[Granularity], xc, dc, *, n_dec: int, v_max: int,
               exact: bool = True, seed: int = 0) -> Optional[Granularity]:
    """One step of the streaming fold: granulate a row chunk and merge it.

    The single home of the capacity/shrink policy, shared by the
    single-process and per-data-shard (``distributed``) folds: both operands
    shrink to their live counts before the merge — on redundant tables a
    chunk's granularity is far smaller than the chunk, and the merge sort
    should pay for live keys, not padding.  The host syncs are the per-merge
    count() the policy already requires.
    """
    xc = jnp.asarray(xc, jnp.int32)
    dc = jnp.asarray(dc, jnp.int32)
    if xc.shape[0] == 0:
        return acc
    with obs.span("pipeline.fold_chunk", rows=int(xc.shape[0]),
                  fresh=acc is None) as sp:
        g = build_granularity(
            xc, dc, n_dec=n_dec, v_max=v_max, exact=exact, seed=seed,
            capacity=next_pow2(xc.shape[0]),
        )
        g = with_capacity(g, next_pow2(max(int(g.num), 1)))
        if acc is None:
            sp.set(granules=int(g.num))
            return g
        acc = merge_granularity(acc, g, exact=exact, seed=seed)
        acc = with_capacity(acc, next_pow2(max(int(acc.num), 1)))
        sp.set(granules=int(acc.num))
    return acc


def regranulate(gran: Granularity, cols: jnp.ndarray, *, exact: bool = True, seed: int = 0) -> Granularity:
    """Coarsen ``G^(C∪D)`` onto the column subset ``cols`` (Corollary 3.3).

    ``cols`` is a static index array; the result's ``x`` holds only those columns.
    """
    x_sub = gran.x[:, cols]
    return build_granularity(
        x_sub,
        gran.d,
        n_dec=gran.n_dec,
        v_max=gran.v_max,
        w=gran.w,
        valid=gran.valid,
        exact=exact,
        seed=seed,
        capacity=gran.capacity,
    )


def project_columns(gran: Granularity, cols: Sequence[int]) -> Granularity:
    """Alias of :func:`regranulate` taking a Python column list."""
    return regranulate(gran, jnp.asarray(list(cols), dtype=jnp.int32))


def pack_ids(r_ids: jnp.ndarray, x_col: jnp.ndarray, v_max: int) -> jnp.ndarray:
    """Refine class ids with one attribute: ``p = r·V + v``  (Corollary 3.4).

    Exact: two granules share ``p`` iff they share both the current class and
    the candidate attribute value.  Range: ``[0, K·V)``.
    """
    return r_ids * v_max + x_col


def presence_bitmap(p: jnp.ndarray, valid: jnp.ndarray, n_bins: int) -> jnp.ndarray:
    """0/1 bitmap of which packed ids occur among valid slots (int32 [n_bins])."""
    p_safe = jnp.where(valid, p, 0)
    return jnp.zeros((n_bins,), jnp.int32).at[p_safe].max(valid.astype(jnp.int32))


def ids_from_presence(presence: jnp.ndarray, p: jnp.ndarray, valid: jnp.ndarray):
    """Dense renumbering given a (possibly psum-merged) presence bitmap."""
    presence = (presence > 0).astype(jnp.int32)
    rank = jnp.cumsum(presence) - presence  # exclusive prefix count
    p_safe = jnp.where(valid, p, 0)
    new_ids = jnp.where(valid, rank[p_safe], 0)
    return new_ids, presence.sum()


@partial(jax.jit, static_argnames=("radix",))
def exact_class_ids(cols: jnp.ndarray, valid: jnp.ndarray, radix: int):
    """Exact dense class ids of the rows of ``cols [n, k]`` (values in
    ``[0, radix)``), without a sort.

    Folds one column at a time, ``p = id·radix + v``, and renumbers ``p``
    densely through the presence bitmap (:func:`compact_ids`).  After column
    j the ids rank the rows' ``(col_0, …, col_j)`` prefixes lexicographically
    — the numbering a lexsort with ``col_0`` as primary key gives — and
    ``K ≤ n`` bounds every step's packed range by ``n·radix``.  Returns
    ``(ids, K)``; invalid rows get id 0 and do not count.
    """
    n, k = cols.shape
    n_bins = n * radix

    def fold(j, carry):
        ids, _ = carry
        p = ids * radix + jnp.take(cols, j, axis=1).astype(jnp.int32)
        return ids_from_presence(presence_bitmap(p, valid, n_bins), p, valid)

    init = (jnp.zeros((n,), jnp.int32), jnp.any(valid).astype(jnp.int32))
    return jax.lax.fori_loop(0, k, fold, init)


@partial(jax.jit, static_argnames=("n_bins",))
def compact_ids(p: jnp.ndarray, valid: jnp.ndarray, n_bins: int):
    """Renumber sparse packed ids to dense ``[0, K_new)`` via presence bitmap.

    Sort-free: presence = scatter-max of validity, rank = cumsum.  The bitmap
    commutes with ``psum`` over data shards, so all shards agree on the global
    numbering without a gather (§3.1 of DESIGN.md).
    """
    presence = presence_bitmap(p, valid, n_bins)
    new_ids, k_new = ids_from_presence(presence, p, valid)
    return new_ids, k_new, presence
