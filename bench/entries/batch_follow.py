"""The batch entry with a check that follows the program's own greedy path.

The window runs as in ``batch.py``.  The check keeps ``batch.py``'s exact
numbers for the granules and the core, and ``core_gap``, against the plain
reference; the reduct and its Θ history are checked along the program's
own path (``bench/follow.py``), where float64 has to admit each step.

Workload keys, beside ``batch.py``'s:

* ``limits``      — ``granules_differ``, ``core_differ``, ``core_gap`` as in
  ``batch.py``; ``theta_gap`` over the float64 Θ of the program's own
  prefixes; ``reduct_differ``: the steps float64 refuses, the sum of the
  picks that lie above the tie band by more than the ``pick_gap`` limit,
  ``tie_break_differ`` and ``stop_differ``.
* ``path_limits`` — ``pick_gap``, ``tie_break_differ``, ``stop_differ``
  (``bench/follow.py``).

Logged, not compared: ``reference_path_differ``, the steps at which the
program's reduct differs from the reference's own greedy path.
"""
from __future__ import annotations

from bench import follow, reference, sketch_work
from bench.entries import batch
from bench.harness import Check, log


def compare(result, got_granules, ref_granules, delta, v_max, options,
            pick_limit, inner=None, log_to=None) -> dict:
    """The numbers compared for one reduction (0 where it agrees)."""
    tol = options.get("tol", 1e-6)
    tie_tol = options.get("tie_tol", 1e-5)
    nums = batch.compare(result, got_granules, ref_granules, delta, v_max,
                         options, inner=inner, log_to=log_to)
    ref_path = nums.pop("reduct_differ")
    path = follow.follow(*ref_granules, reduct=result.reduct,
                         core=result.core, theta_full=result.theta_full,
                         theta_history=result.theta_history, delta=delta,
                         v_max=v_max, pick_limit=pick_limit, tol=tol,
                         tie_tol=tie_tol)
    if log_to is not None:
        log_to(f"    float64 on the program's path Θ(D|C) "
               f"{path['theta_full']!r} Θ {path['theta_history']}")
        log_to("    steps (pick, its Θ and the best lower-index Θ above "
               "the float64 least): " + ", ".join(
                   f"({st['pick']}, {st['above_least']:.3e}, "
                   f"{st['lower_above_least']:.3e})" for st in path["steps"]))
    over = sum(1 for g in path["pick_gaps"] if g > pick_limit)
    nums.update(
        theta_gap=path["theta_gap"], pick_gap=path["pick_gap"],
        tie_break_differ=path["tie_break_differ"],
        stop_differ=path["stop_differ"],
        reduct_differ=over + path["tie_break_differ"] + path["stop_differ"],
        reference_path_differ=ref_path)
    return nums


class Entry(batch.Entry):
    def __init__(self, config: dict, workload: dict, seed: int):
        super().__init__(config, workload, seed)
        self.capacity = {}       # unit index -> granule capacity

    def step(self, i: int) -> None:
        super().step(i)
        self.capacity[i] = self.granules[i].capacity

    def work(self, units) -> dict:
        out = super().work(units)
        for i, w in out.items():
            w["sketch_bytes"] = sketch_work.sketch_bytes(
                self.capacity[i], self.shape.n_attrs)
        return out

    def check(self, units) -> list:
        limits = dict(self.workload["limits"])
        path_limits = self.workload["path_limits"]
        compared = dict(limits, **path_limits)
        worst = {k: 0.0 for k in compared}
        for i, got in sorted(self.checked.items()):
            ref_g = reference.granules(*self._table(1 + i).weighted_rows())
            nums = compare(self.results[i], got, ref_g, self.delta(i),
                           self.shape.v_max, self.options,
                           path_limits["pick_gap"], inner=self.inner[i],
                           log_to=log)
            log(f"  checked unit {i} ({self.delta(i)}): " + ", ".join(
                f"{k} {v!r}" + ("" if k in compared else " (not compared)")
                for k, v in nums.items()))
            for k, v in nums.items():
                if k in compared:
                    worst[k] = max(worst[k], float(v))
        return [Check(k, worst[k], compared[k]) for k in compared]
