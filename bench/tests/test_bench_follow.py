"""The path-following check of ``gisette.batch`` on the CPU, at a small
Gisette shape (200 attributes, past the exact core's 128): a whole run of
the harness under the cell's own limits is correct, each fault of the
timed path's reduct, core or Θ makes it not correct, and so does the
bfloat16 control.  Also the sketch's byte count and its two readers."""
import time

import numpy as np
import pytest

from bench import control_follow, harness, reference, sketch_work
from bench.harness import Records, Unit

BM = harness.benchmark()
CELL, GISETTE, WORKLOAD = harness.find_cell(BM, "gisette.batch")
CONFIG = dict(GISETTE, table=dict(GISETTE["table"], n_rows=400,
                                  n_attrs=200, near_duplicates=0.2))
SMALL = dict(WORKLOAD, tables=2)
SEED = 2**31 + 11


def run():
    return harness.run(dict(CELL), CONFIG, SMALL, BM, seed=SEED, seconds=0.0,
                       trace=False, t_process=time.perf_counter(),
                       require_tpu=False)


def test_a_sound_run_is_correct():
    result = run()
    assert result["correct"], result["checks"]
    assert set(result["checks"]) == set(WORKLOAD["limits"]) | set(
        WORKLOAD["path_limits"])
    assert result["attempted"] == 1 and result["failed"] == 0


def _host_granules(args):
    """The granules ``run_engine`` was given, live slots only."""
    valid = np.asarray(args[3])
    return tuple(np.asarray(a)[valid] for a in args[4:7])


def _with_reduct(monkeypatch, alter):
    """``run_engine`` whose reduct and Θ history ``alter`` rewrites; the
    returned list records each rewrite, so a fault that found nothing to
    break cannot pass for one that was caught."""
    from repro.core import reduction

    run_engine, applied = reduction.run_engine, []

    def altered(*a, **kw):
        reduct, hist, iters, ev, per = run_engine(*a, **kw)
        reduct, hist = alter(list(reduct), list(hist), a)
        applied.append(reduct)
        return reduct, hist, iters, ev, per

    monkeypatch.setattr(reduction, "run_engine", altered)
    return applied


def _candidates(reduct, k, args):
    """The columns left after the program's first k attributes and their
    float64 Θ(D|R_k ∪ {a})."""
    x, d, w = _host_granules(args)
    r_ids = np.zeros(len(x), np.int64)
    for a in reduct[:k]:
        r_ids = reference._dense(r_ids * 2 + x[:, a])
    rest = [a for a in range(x.shape[1]) if a not in reduct[:k]]
    return rest, reference.candidate_thetas(
        "SCE", r_ids, x[:, rest], d, w, int(w.sum()), 2,
        reference._Arith("float64"))


def worse_pick(reduct, hist, args):
    """The first greedy pick replaced by the candidate of the largest
    float64 Θ, far beyond ``tie_tol``."""
    k = len(args[9])
    rest, th = _candidates(reduct, k, args)
    assert th.max() > th.min() + 1e-3
    reduct[k] = rest[int(np.argmax(th))]
    return reduct, hist


def tie_skipped(reduct, hist, args):
    """The last pick that has a later candidate tied with it at the float64
    least, replaced by that candidate: no worse a Θ, but the lower-index tie
    passed over."""
    for k in reversed(range(len(args[9]), len(reduct))):
        rest, th = _candidates(reduct, k, args)
        j = rest.index(reduct[k])
        later = [i for i in range(j + 1, len(rest))
                 if th[i] <= th.min() + 1e-6 and rest[i] not in reduct]
        if th[j] <= th.min() + 1e-6 and later:
            reduct[k] = rest[later[0]]
            return reduct, hist
    raise AssertionError("no pick has a later tie")


def cut_short(reduct, hist, args):
    return reduct[:-1], hist[:-1]


def one_step_long(reduct, hist, args):
    extra = min(set(range(args[2])) - set(reduct))
    return reduct + [extra], hist + [hist[-1]]


def theta_scaled(reduct, hist, args):
    return reduct, [h * (1 + 1e-3) for h in hist]


@pytest.mark.parametrize("alter", [worse_pick, tie_skipped, cut_short,
                                   one_step_long, theta_scaled],
                         ids=lambda f: f.__name__.replace("_", "-"))
def test_a_broken_path_is_not_correct(monkeypatch, alter):
    applied = _with_reduct(monkeypatch, alter)
    result = run()
    assert applied and result["failed"] == 0
    assert result["correct"] is False, result["checks"]


def test_a_skipped_core_is_not_correct(monkeypatch):
    """Θ(D|C) for every Θ(D|C\\{a}): no attribute is ever in the core."""
    from repro.core import reduction

    inner_thetas = reduction._core_inner_thetas

    def skipped(*a, **kw):
        inner = inner_thetas(*a, **kw)
        return np.full_like(inner, inner.min())

    monkeypatch.setattr(reduction, "_core_inner_thetas", skipped)
    result = run()
    assert result["correct"] is False
    assert result["checks"]["core_differ"]["value"] > 0


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_bfloat16_control_fails_a_limit(seed):
    assert control_follow.control(CONFIG, SMALL, seed)["fails"]


def test_sketch_bytes_by_hand():
    # Gisette: capacity 2^13, 5,000 attributes; per slot a one-byte value,
    # two 4-byte fingerprints, a 1-byte decision, a 4-byte weight and a
    # 1-byte mask: 15 bytes
    assert sketch_work.sketch_bytes(8192, 5000) == 614_400_000


def sketched_by_hand():
    """Two reductions of 10 s: sketches 2.0–3.0 and 12.0–14.0 inside their
    cores; chip 0 busy 0.5 s in the first sketch and 1.0 s in the second,
    chip 1 0.5 s in each."""
    spans = lambda o, sk: [  # noqa: E731
        ("reduction.core", o + 1.5, o + sk + 2.5),
        ("core.fingerprints", o + 1.6, o + 1.9),
        ("core.sketch", o + 2.0, o + 2.0 + sk)]
    units = [Unit(0, 0.0, 10.0, "r", True, spans=spans(0.0, 1.0)),
             Unit(1, 10.0, 20.0, "r", True, spans=spans(10.0, 2.0))]
    ns = lambda t: round(t * 1e9)  # noqa: E731
    busy0 = [(ns(2.0), ns(2.5)), (ns(5.0), ns(6.0)), (ns(12.5), ns(13.5))]
    busy1 = [(ns(2.5), ns(3.0)), (ns(13.0), ns(13.5))]
    trace = {"ns": ns, "window_ns": ns(20.0), "devices": {
        "/device:TPU:0": {"merged": busy0}, "/device:TPU:1": {"merged": busy1}}}
    work = {0: {"engine_bytes": 1, "sketch_bytes": 819e9 * 0.25},
            1: {"engine_bytes": 1, "sketch_bytes": 819e9 * 0.5}}
    return Records(units, 0.0, 20.0, 0, work, {"hbm_bw": 819e9}, trace)


def test_sketch_readers_by_hand():
    rec = sketched_by_hand()
    assert harness.metric_reader("core.sketch_s")(rec) == pytest.approx(1.5)
    # least 0.75 s over the mean busy time inside the sketches, 1.25 s
    assert harness.metric_reader("core.sketch_roofline")(rec) == \
        pytest.approx(60.0)


@pytest.mark.parametrize("name", ["core.sketch_s", "core.sketch_roofline"])
def test_sketch_readers_read_none_without_a_sketch(name):
    """A program whose core takes no sketch span (the exact path, or the
    parent of this instrumentation) gives nothing to read."""
    rec = sketched_by_hand()
    for u in rec.units:
        u.spans = [s for s in u.spans if s[0] != "core.sketch"]
    assert harness.metric_reader(name)(rec) is None
    rec = sketched_by_hand()
    rec.trace = None
    if name == "core.sketch_roofline":
        assert harness.metric_reader(name)(rec) is None
