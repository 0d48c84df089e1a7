"""The batch entry: reductions run back to back through ``plar_reduce``.

Workload keys:

* ``source``   — ``"rows"``: each reduction streams a table of its own,
  generated in set-up, through ``plar_reduce(source=<rows>)``;
  ``"granules"``: one table is folded once in set-up, and every reduction
  starts from those resident granules, ``plar_reduce(source=<granules>)``.
* ``deltas``   — the measures the reductions cycle through, in order.
* ``options``  — keyword arguments of ``plar_reduce`` (every reduction).
* ``tables``   — row tables drawn in set-up for the window (``rows``).
* ``base_seed`` — where given, table t is the table of seed
  ``(base_seed, t)`` relabelled by ``(seed, t)`` (:func:`table`): every run
  seed does the same work, on tables that differ as bytes.
* ``check``    — ``{"count": k, "among": b}``: k reductions, drawn from the
  seed among the first b of the window, are compared with the reference
  (the last completed one where none of them completed).
* ``limits``   — the limit of each number compared; ``<number>.<measure>``
  in place of ``<number>`` gives each measure a limit of its own.  A number
  with no limit is logged and not compared.

The numbers: ``granules_differ``, ``core_differ`` and ``reduct_differ``
count what differs from the reference; ``core_gap`` is the widest gap
between the program's Θ(D|C\\{a}) over all a and the reference's, and
``theta_gap`` the widest over Θ(D|C) and the Θ history, each over the
largest |Θ| of the reference's run.

Set-up draws table 0 and warms up with one reduction per measure on it;
the window's reduction i uses table 1 + i, so no reduction in the process
sees a table that an earlier one saw.
"""
from __future__ import annotations

import numpy as np

from bench import gen, reference, work
from bench.harness import Check, log


def table(shape: gen.Shape, workload: dict, seed: int, t: int) -> gen.Table:
    """The ``t``-th table a run of ``seed`` draws."""
    if "base_seed" in workload:
        return gen.Table(shape, gen.table_seed(workload["base_seed"], t),
                         relabel_seed=gen.table_seed(seed, t))
    return gen.Table(shape, gen.table_seed(seed, t))


class Entry:
    def __init__(self, config: dict, workload: dict, seed: int):
        self.config = config
        self.workload = workload
        self.seed = seed
        self.shape = gen.Shape.of(config["table"])
        self.options = dict(workload["options"])
        self.deltas = list(workload["deltas"])
        self.source = workload["source"]
        check = workload["check"]
        rng = np.random.default_rng([seed % 2**64, 0xC4EC])
        among = check["among"]
        self.sample = set(int(i) for i in rng.choice(
            among, size=min(check["count"], among), replace=False))
        self.tables = []
        self.results = {}        # unit index -> ReductionResult
        self.granules = {}       # unit index -> the Granularity it reduced
        self.num = {}            # unit index -> live granule count (device)
        self.inner = {}          # unit index -> the program's inner Θs

    # -- set-up -------------------------------------------------------------

    def _table(self, t: int) -> gen.Table:
        while len(self.tables) <= t:
            self.tables.append(table(self.shape, self.workload, self.seed,
                                     len(self.tables)))
        return self.tables[t]

    def setup(self) -> None:
        import jax

        from repro.core import reduction

        self.reduction = reduction
        resolve = reduction.resolve_granularity
        seen = {}

        def spy(*a, **kw):
            g = resolve(*a, **kw)
            seen["granules"] = g
            return g

        inner_thetas = reduction._core_inner_thetas

        def spy_inner(*a, **kw):
            out = inner_thetas(*a, **kw)
            seen["inner"] = out
            return out

        reduction.resolve_granularity = spy
        reduction._core_inner_thetas = spy_inner
        self._resolve = resolve
        self._inner_thetas = inner_thetas
        self._seen = seen
        n_tables = 1 + (self.workload["tables"] if self.source == "rows"
                        else 0)
        for t in range(n_tables):
            self._table(t)
        if self.source == "granules":
            self.resident = jax.block_until_ready(resolve(
                source=self._table(0),
                chunk_rows=self.options.get("chunk_rows", 65536)))
        for delta in self.deltas:
            self._reduce(self._warm_source(), delta)

    def _warm_source(self):
        return self.resident if self.source == "granules" else self._table(0)

    def _reduce(self, src, delta):
        return self.reduction.plar_reduce(source=src, delta=delta,
                                          **self.options)

    # -- the window ---------------------------------------------------------

    def delta(self, i: int) -> str:
        return self.deltas[i % len(self.deltas)]

    def label(self, i: int) -> str:
        return f"plar_reduce {self.delta(i)}"

    def step(self, i: int) -> None:
        if self.source == "rows":
            if 1 + i >= len(self.tables):
                log(f"  table {1 + i} drawn inside the window: raise "
                    f"'tables' in the workload")
            src = self._table(1 + i)
        else:
            src = self.resident
        self.results[i] = self._reduce(src, self.delta(i))
        g = self._seen.pop("granules")
        self.num[i] = g.num
        self.inner[i] = self._seen.pop("inner", None)
        # keep the granules of the sampled reductions, and of the last one
        self.granules = {k: v for k, v in self.granules.items()
                         if k in self.sample}
        self.granules[i] = g

    def end_to_end(self, t0: float, t1: float, units) -> dict:
        done = sum(u.ok for u in units)
        return {"reduct_s": (t1 - t0) / done if done else float("inf")}

    def work(self, units) -> dict:
        out = {}
        for u in units:
            r = self.results.get(u.index)
            if r is None:
                continue
            out[u.index] = {"engine_bytes": work.greedy_bytes(
                int(self.num[u.index]), self.shape.n_attrs, len(r.core),
                r.iterations)}
        return out

    def free(self) -> None:
        """Pull what the check needs to the host and drop the device state."""
        self.reduction.resolve_granularity = self._resolve
        self.reduction._core_inner_thetas = self._inner_thetas
        done = sorted(self.results)
        chosen = sorted(self.sample & set(done)) or done[-1:]
        self.checked = {}
        for i in chosen:
            g = self.granules[i]
            valid = np.asarray(g.valid)
            self.checked[i] = (np.asarray(g.x)[valid], np.asarray(g.d)[valid],
                               np.asarray(g.w)[valid])
        self.granules.clear()
        self.resident_host = None
        if self.source == "granules":
            g = self.resident
            valid = np.asarray(g.valid)
            self.resident_host = (np.asarray(g.x)[valid],
                                  np.asarray(g.d)[valid],
                                  np.asarray(g.w)[valid])
            self.resident = None

    # -- the check ----------------------------------------------------------

    def check(self, units) -> list:
        limits = self.workload["limits"]
        worst = {k: 0.0 for k in limits}
        ref_granules = {}
        if self.source == "granules":
            ref_granules[0] = reference.granules(*self._table(0)
                                                 .weighted_rows())
            worst["granules_differ"] = float(granules_differ(
                self.resident_host, ref_granules[0]))
        for i, got in sorted(self.checked.items()):
            t = 0 if self.source == "granules" else 1 + i
            if t not in ref_granules:
                ref_granules[t] = reference.granules(
                    *self._table(t).weighted_rows())
            ref_g = ref_granules[t]
            nums = compare(self.results[i], got, ref_g, self.delta(i),
                           self.shape.v_max, self.options,
                           inner=self.inner[i], log_to=log)
            nums = per_measure(nums, self.delta(i), limits)
            log(f"  checked unit {i} ({self.delta(i)}): " + ", ".join(
                f"{k} {v!r}" + ("" if k in limits else " (not compared)")
                for k, v in nums.items()))
            for k, v in nums.items():
                if k in limits:
                    worst[k] = max(worst[k], v)
        return [Check(k, worst[k], limits[k]) for k in limits]


def per_measure(nums: dict, delta: str, limits: dict) -> dict:
    """``nums`` as floats, each under ``<number>.<delta>`` where the
    limits give the measure a limit of its own."""
    return {(f"{k}.{delta}" if f"{k}.{delta}" in limits else k): float(v)
            for k, v in nums.items()}


def _keyed(x, d, w):
    """Granules as sorted byte keys of (row, decision, weight)."""
    x = np.asarray(x)
    if x.size and (x.min() < 0 or x.max() > 255):
        raise ValueError("granule values outside 0..255")
    rows = np.column_stack([x.astype(np.uint8), np.asarray(d, np.uint8),
                            np.asarray(w, "<i8")[:, None].view(np.uint8)])
    rows = np.ascontiguousarray(rows)
    return np.sort(rows.view(np.dtype((np.void, rows.shape[1])))[:, 0])


def granules_differ(got, ref) -> int:
    """Granules (row, decision, weight) that the two tables do not share;
    0 where they are the same multiset."""
    try:
        a = _keyed(*got)
    except ValueError:
        return len(got[2]) + len(ref[2])
    b = _keyed(*ref)
    if len(a) == len(b) and np.array_equal(a, b):
        return 0
    return int(max(1, len(np.setxor1d(a, b)), abs(len(a) - len(b))))


def compare(result, got_granules, ref_granules, delta, v_max,
            options, inner=None, log_to=None) -> dict:
    """The numbers compared for one reduction (0 where it agrees): the
    granules, core and reduct that differ; the widest gap between the
    program's Θ(D|C\\{a}) (``inner``, where given) and the reference's; and
    the widest gap between the program's Θ(D|C) and Θ(D|R) history and the
    reference's.  Gaps are over the largest |Θ| of the reference's run."""
    ref = reference.reduce(*ref_granules, delta=delta, v_max=v_max,
                           tol=options.get("tol", 1e-6),
                           tie_tol=options.get("tie_tol", 1e-5))
    hist_p = np.asarray(result.theta_history, np.float64)
    hist_r = np.asarray(ref["theta_history"], np.float64)
    scale = max([abs(ref["theta_full"])] + list(np.abs(hist_r)) + [1e-30])
    n = min(len(hist_p), len(hist_r))
    gaps = [abs(result.theta_full - ref["theta_full"])]
    gaps += list(np.abs(hist_p[:n] - hist_r[:n]))
    red_p, red_r = list(result.reduct), ref["reduct"]
    if log_to is not None:
        log_to(f"    program   Θ(D|C) {result.theta_full!r} reduct {red_p} "
               f"Θ {[float(t) for t in hist_p]}")
        log_to(f"    reference Θ(D|C) {ref['theta_full']!r} reduct {red_r} "
               f"Θ {[float(t) for t in hist_r]}")
    nums = {
        "granules_differ": granules_differ(got_granules, ref_granules),
        "core_differ": len(set(result.core) ^ set(ref["core"])),
        "reduct_differ": sum(a != b for a, b in zip(red_p, red_r))
        + abs(len(red_p) - len(red_r)),
        "theta_gap": float(max(gaps)) / scale,
    }
    if inner is not None:
        inner = np.asarray(inner, np.float64)
        nums["core_gap"] = (float(np.max(np.abs(inner - ref["inner"])))
                            / scale if inner.shape == ref["inner"].shape
                            else float("inf"))
    return nums
