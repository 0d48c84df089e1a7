"""The benchmark's own copy of the Table-5 stand-in generator.

A table is ``n_proto = max(2, int(n_rows · distinct_fraction))`` prototype
rows drawn from one seed, and, where ``n_proto < n_rows``, each block of
``ROW_BLOCK`` rows samples prototypes with Zipf(1/rank) popularity from a
second stream seeded by ``(seed, block)``.  Attributes copy an earlier one
with probability ``redundancy``; the decision mixes ``relevance`` attributes
and is flipped to a random class with probability ``noise``.  With
``near_duplicates`` at 0 the copy yields the same rows as the program's
``repro.data.paper_dataset`` for the same parameters and seed
(``bench/tests/test_bench_reference.py``); it is kept here so that no change
to the program can change the benchmark's inputs.

``near_duplicates`` > 0 remakes that share of the prototypes as copies of
another prototype with one of ``near_duplicate_attrs`` columns redrawn, the
columns drawn from those that no other column copies: records of one
connection flood that differ in a count field.  Removing such a column
merges the pairs, so the table has a core (:func:`prototypes`).

A table can be relabelled (:func:`relabel`): the values of each column and
the decisions renamed.  The relabelled table has the same partitions, so
the same granule counts, core and greedy reduct: every run seed does the
same work on tables that differ as bytes.  (Columns are not permuted: the
greedy rule breaks ties by column index, and a permutation changes the
reduct's length.)
"""
from __future__ import annotations

import dataclasses

import numpy as np

ROW_BLOCK = 65536


@dataclasses.dataclass(frozen=True)
class Shape:
    n_rows: int
    n_attrs: int
    v_max: int
    n_dec: int
    distinct_fraction: float
    redundancy: float = 0.4
    relevance: int = 3
    noise: float = 0.05
    near_duplicates: float = 0.0
    near_duplicate_attrs: int = 0

    @classmethod
    def of(cls, config: dict) -> "Shape":
        return cls(**{f.name: config[f.name] for f in dataclasses.fields(cls)
                      if f.name in config})

    @property
    def n_proto(self) -> int:
        return max(2, int(self.n_rows * self.distinct_fraction))


def _decide(shape: Shape, x, rel):
    d = np.zeros(len(x), np.int64)
    for a in rel:
        d = d * shape.v_max + x[:, a]
    return (d % shape.n_dec).astype(np.int32)


def prototypes(shape: Shape, seed: int):
    """Prototype rows ``[n_proto, A]`` and their decisions ``[n_proto]``."""
    rng = np.random.default_rng(seed)
    n_proto = shape.n_proto
    x = rng.integers(0, shape.v_max, (n_proto, shape.n_attrs)).astype(np.int32)
    for j in range(1, shape.n_attrs):
        if rng.random() < shape.redundancy:
            x[:, j] = x[:, rng.integers(0, j)]
    rel = rng.choice(shape.n_attrs, size=min(shape.relevance, shape.n_attrs),
                     replace=False)
    d = _decide(shape, x, rel)
    flip = rng.random(n_proto) < shape.noise
    d[flip] = rng.integers(0, shape.n_dec, flip.sum())
    if shape.near_duplicates > 0:
        _near_duplicates(shape, np.random.default_rng([seed, 1]), x, d, rel)
    return x, d


def _near_duplicates(shape: Shape, rng, x, d, rel) -> None:
    """Remake ``near_duplicates`` of the prototypes, in place, each as a
    copy of another with one detail column redrawn; its decision follows
    the copy's relevant attributes and the same noise."""
    n_proto = len(x)
    _, group, size = np.unique(x.T, axis=0, return_inverse=True,
                               return_counts=True)
    single = np.flatnonzero(size[group.reshape(-1)] == 1)
    detail = rng.choice(single, size=min(shape.near_duplicate_attrs,
                                         len(single)), replace=False)
    m = int(n_proto * shape.near_duplicates)
    rows = rng.choice(n_proto, size=m, replace=False)
    parent = (rows + rng.integers(1, n_proto, m)) % n_proto
    col = rng.choice(detail, size=m)
    new = x[parent]
    new[np.arange(m), col] = ((new[np.arange(m), col]
                               + rng.integers(1, shape.v_max, m))
                              % shape.v_max)
    x[rows] = new
    d[rows] = _decide(shape, new, rel)
    flip = rng.random(m) < shape.noise
    d[rows[flip]] = rng.integers(0, shape.n_dec, flip.sum())


def row_index(shape: Shape, seed: int) -> np.ndarray:
    """The prototype behind every row ``[n_rows]``, block by block."""
    n_proto = shape.n_proto
    w = 1.0 / np.arange(1, n_proto + 1)
    p = w / w.sum()
    blocks = []
    for b in range(-(-shape.n_rows // ROW_BLOCK)):
        lo = b * ROW_BLOCK
        hi = min(lo + ROW_BLOCK, shape.n_rows)
        rng = np.random.default_rng((seed, b))
        blocks.append(rng.choice(n_proto, size=hi - lo, p=p))
    return np.concatenate(blocks)


class Table:
    """One generated decision table, held whole on the host.

    It serves the program as a chunked row source (``n_chunks``/``chunk``,
    the protocol ``plar_reduce(source=...)`` reads), and keeps what it was
    made from (``proto_x``, ``proto_d``, ``index``) for the reference.
    """

    def __init__(self, shape: Shape, seed: int, relabel_seed=None):
        self.shape = shape
        self.seed = seed
        self.n_rows, self.n_attrs = shape.n_rows, shape.n_attrs
        self.v_max, self.n_dec = shape.v_max, shape.n_dec
        self.proto_x, self.proto_d = prototypes(shape, seed)
        if relabel_seed is not None:
            self.proto_x, self.proto_d = relabel(shape, relabel_seed,
                                                 self.proto_x, self.proto_d)
        if shape.n_proto >= shape.n_rows:
            self.index = None
            self.x = self.proto_x[: shape.n_rows]
            self.d = self.proto_d[: shape.n_rows]
        else:
            self.index = row_index(shape, seed)
            self.x = self.proto_x[self.index]
            self.d = self.proto_d[self.index]

    def n_chunks(self, chunk_rows: int) -> int:
        return -(-self.n_rows // chunk_rows)

    def chunk(self, step: int, chunk_rows: int):
        lo = step * chunk_rows
        hi = min(lo + chunk_rows, self.n_rows)
        return self.x[lo:hi], self.d[lo:hi]

    def weighted_rows(self):
        """The table as distinct prototypes with their row counts: the same
        multiset of rows as ``(x, d)``, at a size the reference can group."""
        if self.index is None:
            return self.x, self.d, np.ones(self.n_rows, np.int64)
        counts = np.bincount(self.index, minlength=self.shape.n_proto)
        used = counts > 0
        return self.proto_x[used], self.proto_d[used], counts[used]


def relabel(shape: Shape, seed: int, x, d):
    """``x`` with each column's values renamed and ``d`` with its classes
    renamed, drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    names = np.argsort(rng.random((shape.n_attrs, shape.v_max)), axis=1)
    x = names[np.arange(shape.n_attrs)[None, :], x]
    d = rng.permutation(shape.n_dec)[d]
    return x.astype(np.int32), d.astype(np.int32)


def table_seed(seed: int, t: int) -> int:
    """The seed of the ``t``-th table a run draws from ``--seed``."""
    return int(np.random.SeedSequence([seed % 2**64, t]).generate_state(
        1, np.uint64)[0])
