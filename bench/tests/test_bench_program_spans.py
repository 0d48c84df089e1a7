"""The readers of the program's spans inside ``plar_reduce``: Θ(D|C) and the
core (``reduction.core_s``), the fold's merges (``ingest.merge_s``) and the
device's idle time during the fold (``ingest.idle_s``), on records built by
hand, and ``None`` where there is nothing to read."""
import pytest

from bench import harness
from bench.harness import Records, Unit

ROOT = harness.ROOT
NEW = ("reduction.core_s", "ingest.merge_s", "ingest.idle_s")


def streamed_by_hand():
    """Two streamed reductions of 10 s, two chunks then one, on two chips.

    Unit 0: copies 0.2–0.5 and 3.8–4.0; folds 0.5–3.5 (merge 1.5–3.4) and
    4.0–6.0 (merge 4.5–6.0); Θ(D|C) 0.5 s, core 1.0 s.  Unit 1: copy
    10.2–10.5, fold 10.5–14.5 (merge 11.0–14.4); Θ(D|C) 0.5 s, core 1.5 s.
    """
    u0 = Unit(0, 0.0, 10.0, "r", True, spans=[
        ("ingest.h2d", 0.2, 0.5), ("ingest.granulate", 0.5, 1.5),
        ("ingest.merge", 1.5, 3.4), ("pipeline.fold_chunk", 0.5, 3.5),
        ("ingest.h2d", 3.8, 4.0), ("ingest.granulate", 4.0, 4.5),
        ("ingest.merge", 4.5, 6.0), ("pipeline.fold_chunk", 4.0, 6.0),
        ("reduction.theta_full", 6.0, 6.5), ("reduction.core", 6.5, 7.5),
        ("engine.dispatch", 8.0, 9.0), ("reduction.plar_reduce", 0.1, 9.9)])
    u1 = Unit(1, 10.0, 20.0, "r", True, spans=[
        ("ingest.h2d", 10.2, 10.5), ("ingest.granulate", 10.5, 11.0),
        ("ingest.merge", 11.0, 14.4), ("pipeline.fold_chunk", 10.5, 14.5),
        ("reduction.theta_full", 14.5, 15.0), ("reduction.core", 15.0, 16.5),
        ("engine.dispatch", 17.0, 19.0), ("reduction.plar_reduce", 10.1, 19.9)])
    ns = lambda t: round(t * 1e9)  # noqa: E731
    # chip 0 is busy 1.0 s inside the copies and folds of unit 0
    # (1–2, and 3–4 of which 3–3.5 and 3.8–4 lie inside), 1.0 s inside
    # unit 1's fold, and in the engine; chip 1 only in the engine
    busy0 = [(ns(1.0), ns(2.0)), (ns(3.0), ns(4.0)), (ns(8.0), ns(8.5)),
             (ns(12.0), ns(13.0)), (ns(17.0), ns(17.5))]
    busy1 = [(ns(8.0), ns(8.5)), (ns(17.0), ns(17.5))]
    trace = {"ns": ns, "window_ns": ns(20.0), "devices": {
        "/device:TPU:0": {"merged": busy0, "idle_frac": 0.7},
        "/device:TPU:1": {"merged": busy1, "idle_frac": 0.95}}}
    work = {0: {"engine_bytes": 0.0}, 1: {"engine_bytes": 0.0}}
    return Records([u0, u1], 0.0, 20.0, 0, work, {"hbm_bw": 819e9}, trace)


def resident_by_hand():
    """The same two reductions from resident granules: no fold."""
    rec = streamed_by_hand()
    for u in rec.units:
        u.spans = [s for s in u.spans if not s[0].startswith(
            ("ingest.", "pipeline."))]
    return rec


# copies and folds cover 3.3 + 2.2 s of unit 0 and 4.3 s of unit 1 (9.8 s);
# chip 0 is busy 1.0 + 0.5 + 0.2 + 1.0 = 2.7 s of it, chip 1 none
IDLE = ((9.8 - 2.7) + 9.8) / 2 / 2


@pytest.mark.parametrize("name,value", [
    ("reduction.core_s", ((0.5 + 1.0) + (0.5 + 1.5)) / 2),
    ("ingest.merge_s", ((1.9 + 1.5) + 3.4) / 2),
    ("ingest.idle_s", IDLE),
])
def test_span_readers_by_hand(name, value):
    assert harness.metric_reader(name)(streamed_by_hand()) == \
        pytest.approx(value)


@pytest.mark.parametrize("name,value", [
    ("reduction.core_s", 1.75),
    ("ingest.merge_s", None),
    ("ingest.idle_s", None),
])
def test_resident_reductions_read_only_the_core(name, value):
    got = harness.metric_reader(name)(resident_by_hand())
    assert got == (None if value is None else pytest.approx(value))


@pytest.mark.parametrize("name", NEW)
def test_no_spans_read_none(name):
    """A program without these spans (the parent of this instrumentation)
    gives nothing to read, with or without a trace."""
    rec = streamed_by_hand()
    for u in rec.units:
        u.spans = [s for s in u.spans
                   if s[0] in ("pipeline.fold_chunk", "engine.dispatch")]
    assert harness.metric_reader(name)(rec) is None
    for u in rec.units:
        u.spans = []
    assert harness.metric_reader(name)(rec) is None


def test_idle_needs_a_trace():
    rec = streamed_by_hand()
    rec.trace = None
    assert harness.metric_reader("ingest.idle_s")(rec) is None


def test_the_new_metrics_name_known_layers_and_cells():
    bm = harness.benchmark(ROOT)
    per_layer = {m["name"]: m for m in bm["per_layer"]}
    old_layers = {m["layer"] for m in bm["per_layer"] if m["name"] not in NEW}
    for name in NEW:
        m = per_layer[name]
        assert m["layer"] in old_layers and m["moves"] == "reduct_s"
        assert m["unit"] == "s" and m["better"] == "lower"
    assert per_layer["reduction.core_s"]["workloads"] == [
        "kdd99.stream", "kdd99.resident4"]
    assert per_layer["ingest.merge_s"]["workloads"] == ["kdd99.stream"]
    assert per_layer["ingest.idle_s"]["workloads"] == ["kdd99.stream"]
