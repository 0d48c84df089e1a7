"""The trace reduction, by hand and on a small trace recorded on a
TPU v5e (``bench/testdata/small_trace.xplane.pb.gz``: one ``plar_reduce``
of a 3,000 x 6 table inside a ``bench.reduce`` annotation)."""
import gzip
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import trace

TRACE = Path(__file__).resolve().parents[1] / "testdata" / \
    "small_trace.xplane.pb.gz"


def test_union_gaps_and_cover_by_hand():
    merged = trace.union([(5, 7), (0, 2), (1, 3), (10, 12), (6, 8), (9, 9)])
    assert merged == [(0, 3), (5, 8), (10, 12)]
    assert trace.covered(merged, 2, 11) == 1 + 3 + 1
    assert trace.gaps(merged, 2, 11) == [(3, 5), (8, 10)]
    assert trace.gaps(merged, -4, 14) == [(-4, 0), (3, 5), (8, 10), (12, 14)]
    assert trace.busy_within(merged, [(0, 6), (4, 11)]) == 3 + 3 + 1


def test_labels_name_the_innermost_span():
    spans = [("outer", 0, 100), ("inner", 10, 20), ("other", 50, 60)]
    assert trace.label_at(15, spans) == "inner"
    assert trace.label_at(30, spans) == "outer"
    assert trace.label_at(200, spans) == "outside"


def test_self_times_take_out_nested_ops():
    ops = [("while", 0, 10), ("body", 1, 4), ("body", 5, 9), ("x", 12, 13)]
    assert [t for _, _, t in trace.self_times(ops)] == [3, 3, 4, 1]


def test_device_summary_by_hand():
    ops = [("a", 0, 4), ("b", 2, 6), ("a", 8, 9)]
    spans = [("bench.reduce", 0, 10), ("engine.dispatch", 6, 8)]
    s = trace.device_summary(ops, 0, 10, spans)
    assert s["busy_ns"] == 7 and s["window_ns"] == 10
    assert s["idle_frac"] == pytest.approx(0.3)
    assert s["device_ops"] == [["a", 5e-9], ["b", 4e-9]]
    assert dict((k, v) for k, v in s["idle_gaps"]) == pytest.approx(
        {"engine.dispatch": 2e-9, "bench.reduce": 1e-9})


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "small_trace.xplane.pb"
    with gzip.open(TRACE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return trace.load(str(path))


def test_the_recorded_trace_has_a_device_and_the_annotation(recorded):
    assert list(recorded["devices"]) == ["/device:TPU:0"]
    ops = recorded["devices"]["/device:TPU:0"]
    assert len(ops) > 10 and all(e >= s for _, s, e in ops)
    marks = [ev for ev in recorded["host"] if ev[0] == "bench.reduce"]
    assert len(marks) == 1


def test_busy_time_of_the_recorded_trace_by_brute_force(recorded):
    ops = recorded["devices"]["/device:TPU:0"]
    _, lo, hi = next(ev for ev in recorded["host"] if ev[0] == "bench.reduce")
    s = trace.device_summary(ops, lo, hi, [("bench.reduce", lo, hi)])
    # brute force at 1 µs: a microsecond is busy if any op covers it
    step = 1000
    grid = np.zeros((hi - lo) // step + 1, bool)
    for _, a, b in ops:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            grid[(a - lo) // step:(b - lo) // step + 1] = True
    busy = int(grid.sum()) * step
    assert abs(s["busy_ns"] - busy) <= step * 2 * len(ops)
    assert 0.0 < s["idle_frac"] < 1.0
    total_idle = sum(v for _, v in s["idle_gaps"]) * 1e9
    assert total_idle == pytest.approx(s["window_ns"] - s["busy_ns"],
                                       abs=1.0)
