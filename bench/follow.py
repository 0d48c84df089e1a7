"""The path-following check: PLAR's rule, in float64, along the program's
own greedy path.

Where candidates lie within float32 rounding of a tie, a sound float32 run
and the float64 reference (``bench/reference.py``) may take different
greedy steps, and from there on their reducts and Θ histories differ though
neither is wrong.  So this check does not compare the program's reduct
with the reference's.  It walks the program's path R_0 ⊂ R_1 ⊂ … (the core
first, then each pick) and asks, at every step, whether float64 admits
what the program did:

* ``pick_gap``: how far the program's pick lies above the float64 tie band,
  max(0, Θ(p_k) − (min_a Θ(a) + tie_tol)), the largest over steps (each
  step's in ``pick_gaps``);
* ``tie_break_differ``: steps at which the program passed over a
  lower-index candidate that lies inside the float64 tie band by more than
  the ``pick_gap`` limit;
* ``stop_differ``: 1 where the program stopped on a prefix whose float64 Θ
  misses Θ(D|C) + tol, or ran past a prefix that meets it, each by more
  than the ``pick_gap`` limit times the larger of |Θ(D|R)| and |Θ(D|C)|;
* ``theta_gap``: the widest gap between the program's Θ(D|C) and Θ history
  and the float64 Θ of its own prefixes.

Gaps are over the run's scale, the largest |Θ| among the float64 Θ(D|C)
and the Θ of the program's prefixes.  Every Θ(D|R_k ∪ {a}) is
:func:`reference.candidate_thetas`, exact counts in float64.
"""
from __future__ import annotations

import numpy as np

from bench import reference


def follow(x, d, w, *, reduct, core, theta_full, theta_history, delta: str,
           v_max: int, pick_limit: float, tol: float = 1e-6,
           tie_tol: float = 1e-5) -> dict:
    """The path-following numbers of one reduction of the granules
    (x, d, w): the program's ``reduct`` (core first), ``core``, Θ(D|C)
    ``theta_full`` and ``theta_history``, one Θ per attribute of the
    reduct.  ``pick_limit`` is the margin that a skipped tie (over scale)
    or a stop (over the Θs it compares) has to miss by to count."""
    ar = reference._Arith("float64")
    x = np.asarray(x)
    d = np.asarray(d, np.int64)
    w = np.asarray(w, np.int64)
    n = int(w.sum())
    A = x.shape[1]
    full = reference.theta_of_classes(delta, reference.row_classes(x), d, w,
                                      n, ar)
    reduct = [int(a) for a in reduct]
    n_core = len(core)
    r_ids = np.zeros(len(x), np.int64)
    hist, steps = [], []            # float64 Θ(D|R_k); one dict a pick
    for k, p in enumerate(reduct):
        remaining = [a for a in range(A) if a not in reduct[:k]]
        if k < n_core or p not in remaining:
            th_p = (reference.theta_of_classes(
                delta, reference._dense(r_ids * v_max + x[:, p]), d, w, n,
                ar) if 0 <= p < A else np.inf)
            if k >= n_core:                      # a pick repeated or unknown
                steps.append({"pick": p, "above_least": np.inf,
                              "lower_above_least": np.inf})
        else:
            th = reference.candidate_thetas(delta, r_ids, x[:, remaining], d,
                                            w, n, v_max, ar)
            j = remaining.index(p)
            th_p = float(th[j])
            least = float(th.min())
            # how far the pick, and the best lower-index candidate, lie
            # above the float64 least
            steps.append({"pick": p, "above_least": th_p - least,
                          "lower_above_least": float(th[:j].min()) - least
                          if j else np.inf})
        if 0 <= p < A:
            r_ids = reference._dense(r_ids * v_max + x[:, p])
        hist.append(float(th_p))

    finite = [abs(h) for h in hist if np.isfinite(h)]
    scale = max([abs(full)] + finite + [1e-30])
    margin = pick_limit * scale
    pick_gaps = [max(0.0, st["above_least"] - tie_tol) / scale
                 for st in steps]
    tie_break = sum(1 for st in steps
                    if st["lower_above_least"] <= tie_tol - margin)
    # a stop compares Θ(D|R) with Θ(D|C) + tol, both near Θ(D|C): its
    # rounding is relative to them, not to the run's scale
    target = full + tol

    def stop_margin(h):
        return pick_limit * max(abs(h), abs(full))

    stopped_short = not hist or hist[-1] > target + stop_margin(hist[-1])
    ran_past = any(h <= target - stop_margin(h)
                   for h in hist[max(n_core, 1) - 1:-1])
    hist_p = np.asarray(theta_history, np.float64)
    gaps = [abs(float(theta_full) - full)]
    if len(hist_p) == len(hist):
        gaps += list(np.abs(hist_p - np.asarray(hist)))
    else:
        gaps.append(np.inf)
    return {
        "pick_gap": float(max(pick_gaps + [0.0])),
        "pick_gaps": pick_gaps,
        "steps": steps,
        "tie_break_differ": int(tie_break),
        "stop_differ": int(stopped_short or ran_past),
        "theta_gap": float(max(gaps)) / scale,
        "theta_full": full,
        "theta_history": hist,
        "scale": scale,
    }
