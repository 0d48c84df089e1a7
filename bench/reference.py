"""The plain reference: PLAR's answer for a decision table, in numpy.

It imports nothing of the program.  From the table's rows it computes what
``plar_reduce`` must return (DESIGN.md §2, paper Algorithm 2):

* the granules: the distinct rows of C ∪ D with their multiplicities
  (:class:`DistinctRows`, the host witness ``chip_smoke.py`` uses);
* Θ(D|C), the stopping target;
* the core: every a with Θ(D|C\\{a}) − Θ(D|C) > eps + tie_tol;
* the reduct: the core folded in index order, then greedy additions, each
  the lowest-index candidate within ``tie_tol`` of the least Θ(D|R ∪ {a}),
  until Θ(D|R) ≤ Θ(D|C) + tol; and Θ(D|R) after each addition.

Counts are exact integers and every Θ is computed in float64.  With
``precision="bfloat16"`` the same computation holds its counts and its
per-class terms in bfloat16 and sums them in float32: the control, which has
to come out as not correct.

Granules and the classes of R are grouped exactly (by value).  The classes
of C\\{a}, for the core, are grouped by a random 64-bit linear hash (see
:func:`inner_thetas`).
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# cells of one [candidates, granules] count matrix
CELLS = 1 << 21
# numpy releases the GIL in its array loops: candidates are split over
# threads, since the reference runs after the measured window
THREADS = min(8, os.cpu_count() or 1)


class DistinctRows:
    """Distinct rows of a stream of row blocks and their weights, in numpy
    on the host: the witness the device's grouping is checked against.
    Rows are compared as byte strings; values must be below 256."""

    def __init__(self):
        self.keys = None
        self.counts = None

    def add(self, rows, weights=None):
        rows = np.ascontiguousarray(rows, np.uint8)
        keys = rows.view(np.dtype((np.void, rows.shape[1])))[:, 0]
        counts = (np.ones(len(keys), np.int64) if weights is None
                  else np.asarray(weights, np.int64))
        if self.keys is not None:
            keys = np.concatenate([self.keys, keys])
            counts = np.concatenate([self.counts, counts])
        self.keys, inv = np.unique(keys, return_inverse=True)
        self.counts = np.bincount(inv, weights=counts).astype(np.int64)

    def rows(self) -> np.ndarray:
        width = self.keys.dtype.itemsize
        return self.keys.view(np.uint8).reshape(-1, width)


def granules(x, d, w=None):
    """Distinct rows of C ∪ D, sorted: (x uint8 [G, A], d [G], w [G])."""
    dr = DistinctRows()
    dr.add(np.column_stack([x, d]), w)
    rows = dr.rows()
    return rows[:, :-1], rows[:, -1].astype(np.int64), dr.counts


# ---------------------------------------------------------------------------
# Θ from class counts
# ---------------------------------------------------------------------------


class _Arith:
    """float64 arithmetic, or bfloat16 values with float32 sums (control)."""

    def __init__(self, precision: str):
        self.precision = precision
        if precision == "float64":
            self.hold = lambda a: np.asarray(a, np.float64)
            self.acc = np.float64
        elif precision == "bfloat16":
            import ml_dtypes

            bf16 = ml_dtypes.bfloat16
            self.hold = lambda a: np.asarray(a, np.float32).astype(
                bf16).astype(np.float32)
            self.acc = np.float32
        else:
            raise ValueError(f"unknown precision {precision!r}")

    def total(self, a) -> np.ndarray:
        """Sums over the last axis of the terms ``a``, each term held first."""
        return np.sum(self.hold(a), axis=-1, dtype=self.acc).astype(np.float64)


def _raw(delta, c, e, e_of_cell, nz, ar: _Arith) -> np.ndarray:
    """Unnormalised Σθ' per candidate: the program's θ' rows
    (``core/measures.py``) written out.  ``c`` [C, U] are the cells (one per
    class of R ∪ {a} and decision), ``e`` [C, K] the class counts,
    ``e_of_cell`` [C, U] the count of each cell's class, ``nz`` [C, K] the
    nonzero cells of each class.  Counts are exact integers."""
    if delta == "SCE" and ar.precision == "float64":
        # Σ_j c_j·log(c_j/e) = Σ_j c_j·log c_j − e·log e, the same sum
        # regrouped; c·log c read from a table of the integers up to max e
        top = int(e.max()) if e.size else 0
        k = np.arange(top + 1, dtype=np.float64)
        xlogx = k * np.log(np.maximum(k, 1.0))
        return xlogx[c].sum(axis=-1) - xlogx[e].sum(axis=-1)
    c, e, e_of_cell = ar.hold(c), ar.hold(e), ar.hold(e_of_cell)
    if delta == "PR":
        return ar.total(e * (nz == 1))
    if delta == "SCE":
        pos = c > 0
        logs = ar.hold(np.log(np.where(pos, c, 1.0))
                       - np.log(np.where(pos, e_of_cell, 1.0)))
        return ar.total(np.where(pos, c * logs, 0.0))
    if delta == "LCE":
        return ar.total(c * (e_of_cell - c))
    if delta == "CCE":
        return (ar.total(e * e * np.maximum(e - 1, 0))
                - ar.total(c * c * np.maximum(c - 1, 0)))
    raise ValueError(f"unknown measure {delta!r}")


def _scale(delta, raw, n):
    n = float(n)
    if delta in ("PR", "SCE"):
        return -raw / n
    if delta == "LCE":
        return raw / (n * n)
    return raw / max(n * n * (n - 1.0), 1.0)


def _segment_sums(a, ends):
    """Sums of the runs of the last axis of ``a`` that end at ``ends``."""
    cs = np.cumsum(a, axis=-1)[..., ends]
    return np.diff(cs, axis=-1, prepend=0)


def candidate_thetas(delta, r_ids, cand, d, w, n, v_max, ar: _Arith):
    """Θ(D | R ∪ {a}) for every column a of ``cand`` [G, C], where granule g
    is in class ``r_ids[g]`` of R.

    Granules are sorted by (class, decision).  For each value v, segmented
    sums give, per (class, decision) group and candidate, the weight of the
    granules that carry v — the cells of the class (r, v) — and segmented
    sums of those over each class's groups give the class counts.  The
    cells of the last value are what the others leave of each group."""
    order = np.lexsort((d, r_ids))
    r_s, d_s = r_ids[order], d[order]
    w_s = np.asarray(w, np.int64)[order]
    G = len(order)
    ends = np.flatnonzero(np.r_[(r_s[1:] != r_s[:-1])
                                | (d_s[1:] != d_s[:-1]), True])
    r_g = r_s[ends]
    cls_end = np.r_[r_g[1:] != r_g[:-1], True]
    cls_ends = np.flatnonzero(cls_end)
    cls_of_grp = np.cumsum(np.r_[True, cls_end[:-1]]) - 1
    n_grp = _segment_sums(w_s, ends)

    def cells_raw(xs):                                    # xs [C, G]
        raw = np.zeros(xs.shape[0], np.float64)
        rest = np.broadcast_to(n_grp, (xs.shape[0], len(ends)))
        for v in range(v_max):
            if v < v_max - 1:
                c = _segment_sums(np.where(xs == v, w_s, 0), ends)
                rest = rest - c
            else:
                c = rest                                  # [C, U]
            e = _segment_sums(c, cls_ends)                # [C, K]
            nz = _segment_sums((c > 0).astype(np.int64), cls_ends)
            raw += _raw(delta, c, e, e[:, cls_of_grp], nz, ar)
        return raw

    step = max(1, min(CELLS // max(G, 1), -(-cand.shape[1] // THREADS)))
    chunks = [np.ascontiguousarray(cand[order, s:s + step].T)
              for s in range(0, cand.shape[1], step)]
    with ThreadPoolExecutor(THREADS) as pool:
        raw = np.concatenate(list(pool.map(cells_raw, chunks)))
    return _scale(delta, raw, n)


def theta_of_classes(delta, ids, d, w, n, ar: _Arith) -> float:
    zero = np.zeros((len(ids), 1), np.uint8)
    return float(candidate_thetas(delta, ids, zero, d, w, n, 1, ar)[0])


def _dense(keys) -> np.ndarray:
    return np.unique(keys, return_inverse=True)[1].reshape(-1)


def row_classes(x) -> np.ndarray:
    """Dense class ids of the rows of ``x`` (equal rows, equal id)."""
    x = np.ascontiguousarray(x, np.uint8)
    return _dense(x.view(np.dtype((np.void, x.shape[1])))[:, 0])


def inner_thetas(delta, x, d, w, n, theta_full, ar: _Arith) -> np.ndarray:
    """Θ(D | C\\{a}) for every attribute a.

    The classes of C\\{a} are grouped by a random 64-bit linear hash,
    h(C) − term_a: two distinct classes collide with probability below
    G²/2⁶⁴ (under 1e-9 at 100,000 granules).  Where C\\{a} has as many
    classes as C, it is the same partition and Θ(D|C\\{a}) = Θ(D|C)."""
    G, A = x.shape
    k_full = len(np.unique(row_classes(x)))
    rng = np.random.default_rng(0x5EED)
    table = rng.integers(0, 2**63, (A, int(x.max()) + 1), dtype=np.uint64)
    terms = table[np.arange(A)[None, :], x]                      # [G, A]
    h = terms.sum(axis=1, dtype=np.uint64)
    out = np.full(A, theta_full, np.float64)
    for a in range(A):
        keys, ids = np.unique(h - terms[:, a], return_inverse=True)
        if len(keys) < k_full:
            out[a] = theta_of_classes(delta, ids.reshape(-1), d, w, n, ar)
    return out


def reduce(x, d, w, *, delta: str, v_max: int, tol: float = 1e-6,
           tie_tol: float = 1e-5, eps: float = 0.0,
           precision: str = "float64") -> dict:
    """PLAR's answer for the granules (x, d, w): Θ(D|C), Θ(D|C\\{a}) for
    every a, core, reduct and Θ(D|R) after each addition."""
    ar = _Arith(precision)
    x = np.asarray(x)
    d = np.asarray(d, np.int64)
    w = np.asarray(w, np.int64)
    n = int(w.sum())
    A = x.shape[1]
    theta_full = theta_of_classes(delta, row_classes(x), d, w, n, ar)
    inner = inner_thetas(delta, x, d, w, n, theta_full, ar)
    core = [a for a in range(A) if inner[a] - theta_full > eps + tie_tol]

    r_ids = np.zeros(len(x), np.int64)
    reduct, hist = [], []
    for a in core:
        r_ids = _dense(r_ids * v_max + x[:, a])
        reduct.append(a)
        hist.append(theta_of_classes(delta, r_ids, d, w, n, ar))
    theta_r = hist[-1] if hist else np.inf
    remaining = [a for a in range(A) if a not in core]
    while remaining and theta_r > theta_full + tol:
        th = candidate_thetas(delta, r_ids, x[:, remaining], d, w, n, v_max,
                              ar)
        best = int(np.flatnonzero(th <= th.min() + tie_tol)[0])
        a = remaining.pop(best)
        r_ids = _dense(r_ids * v_max + x[:, a])
        reduct.append(a)
        theta_r = float(th[best])
        hist.append(theta_r)
    return {"theta_full": theta_full, "inner": inner, "core": core,
            "reduct": reduct, "theta_history": hist}
