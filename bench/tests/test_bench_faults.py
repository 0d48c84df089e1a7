"""A whole run of the harness on the CPU at a small size, past its look for
a chip, with the timed path broken underneath: ``correct`` has to come out
false for each fault a cell can have, and true with none."""
import time

import numpy as np
import pytest

from bench import harness

CONFIG = {"name": "small", "table": {
    "n_rows": 40_000, "n_attrs": 10, "v_max": 5, "n_dec": 4,
    "distinct_fraction": 0.05, "near_duplicates": 0.05,
    "near_duplicate_attrs": 2}}
LIMITS = {"granules_differ": 0, "core_differ": 0, "reduct_differ": 0,
          "core_gap": 1e-5, "theta_gap": 1e-5}
ROWS = {"entry": "batch", "source": "rows", "deltas": ["SCE"],
        "options": {"chunk_rows": 8192}, "tables": 2,
        "check": {"count": 1, "among": 1}, "limits": LIMITS}
RESIDENT = {"entry": "batch", "source": "granules", "deltas": ["PR", "LCE"],
            "options": {"chunk_rows": 8192},
            "check": {"count": 2, "among": 2}, "limits": LIMITS}
PER_MEASURE = dict(RESIDENT, limits={
    "granules_differ": 0, "core_differ": 0, "reduct_differ": 0,
    "core_gap.PR": 1e-5, "theta_gap.PR": 1e-5, "theta_gap.LCE": 1e-5})
RELABELLED = dict(ROWS, base_seed=5)


def run(workload, seed=2**31 + 3):
    bm = harness.benchmark()
    cell = {"name": "kdd99.stream", "chips": 1}
    return harness.run(cell, CONFIG, workload, bm, seed=seed, seconds=0.0,
                       trace=False, t_process=time.perf_counter(),
                       require_tpu=False)


def half_the_chunks(monkeypatch):
    from repro.core import granularity

    fold, calls = granularity.fold_chunk, []

    def skip_odd(acc, xc, dc, **kw):
        calls.append(1)
        return acc if len(calls) % 2 == 0 else fold(acc, xc, dc, **kw)

    monkeypatch.setattr(granularity, "fold_chunk", skip_odd)


def merge_returns_its_state(monkeypatch):
    from repro.core import granularity

    monkeypatch.setattr(granularity, "merge_granularity",
                        lambda a, b, **kw: a)


def core_skipped(monkeypatch):
    """Θ(D|C) for every Θ(D|C\\{a}): no attribute is ever in the core."""
    from repro.core import reduction

    inner_thetas = reduction._core_inner_thetas

    def skipped(*a, **kw):
        inner = inner_thetas(*a, **kw)
        return np.full_like(inner, inner.min())

    monkeypatch.setattr(reduction, "_core_inner_thetas", skipped)


def reduct_altered(monkeypatch):
    from repro.core import reduction

    run_engine = reduction.run_engine

    def altered(*a, **kw):
        reduct, hist, iters, ev, per = run_engine(*a, **kw)
        n_attrs = a[2]
        reduct = reduct[:-1] + [(reduct[-1] + 1) % n_attrs]
        return reduct, hist, iters, ev, per

    monkeypatch.setattr(reduction, "run_engine", altered)


def theta_altered(monkeypatch):
    from repro.core import reduction

    run_engine = reduction.run_engine

    def altered(*a, **kw):
        reduct, hist, iters, ev, per = run_engine(*a, **kw)
        return reduct, hist[:-1] + [hist[-1] + 1e-3], iters, ev, per

    monkeypatch.setattr(reduction, "run_engine", altered)


@pytest.mark.parametrize("workload", [ROWS, RESIDENT, PER_MEASURE,
                                      RELABELLED],
                         ids=["rows", "granules", "limit-per-measure",
                              "relabelled"])
def test_a_sound_run_is_correct(workload):
    result = run(workload)
    assert result["correct"], result["checks"]
    assert set(result["checks"]) == set(workload["limits"])
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"reduct_s", "setup_s"}


@pytest.mark.parametrize("fault,workload", [
    (half_the_chunks, ROWS),
    (half_the_chunks, RESIDENT),
    (merge_returns_its_state, ROWS),
    (core_skipped, ROWS),
    (core_skipped, PER_MEASURE),
    (reduct_altered, ROWS),
    (reduct_altered, RESIDENT),
    (theta_altered, ROWS),
    (theta_altered, PER_MEASURE),
], ids=["half-the-chunks-rows", "half-the-chunks-granules",
        "merge-returns-its-state", "core-skipped-rows",
        "core-skipped-granules", "reduct-altered-rows",
        "reduct-altered-granules", "theta-altered",
        "theta-altered-per-measure"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault, workload):
    fault(monkeypatch)
    result = run(workload)
    assert result["correct"] is False, result["checks"]
