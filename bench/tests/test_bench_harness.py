"""The harness on the CPU: BENCHMARK.json against its contract, discovery
of configurations, workloads, entries and metrics by name, the window rule,
the metric readers, and no result without a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness
from bench.harness import Records, Unit

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bm():
    return harness.benchmark(ROOT)


def test_benchmark_json_keeps_its_contract(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["paths"] == ["bench"] and bm["command"][1] == "bench/run.py"
    rs = bm["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check of 24 cells fits its time
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    cells = [c["name"] for c in bm["workloads"]]
    configs = [c["name"] for c in bm["configs"]]
    e2e = [m["name"] for m in bm["end_to_end"]]
    metrics = e2e + [m["name"] for m in bm["per_layer"]]
    for names in (cells, configs, metrics):
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    assert "setup_s" in e2e
    for m in bm["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25 and m["bound"] >= 0.01
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bm["per_layer"]:
        assert m["moves"] in e2e and m["layer"] and "\n" not in m["layer"]
        assert set(m.get("workloads", cells)) <= set(cells)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for c in bm["workloads"]:
        assert c["config"] in configs and c["chips"] in (1, 4)
        assert 1 <= len(c["why"]) <= 200
        assert harness.cell_metrics(bm, c["name"], "per_layer")
        assert len(harness.cell_metrics(bm, c["name"], "end_to_end")) >= 2
    assert len({(c["config"], c["traffic"]) for c in bm["workloads"]}) \
        == len(cells)
    assert sum(c["chips"] == 4 for c in bm["workloads"]) <= max(
        1, len(cells) // 2)
    assert len(json.dumps(bm)) < 64 * 1024


def test_configs_are_found_by_name_and_name_their_source(bm):
    for c in bm["configs"]:
        path = ROOT / c["file"]
        assert path.parts[len(ROOT.parts)] == "bench"
        cfg = harness.load_json(path)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        # every cut names its reason, and is a key of the table
        assert set(cfg["reduced"]) == set(cfg.get("cuts", {}))
        assert set(cfg["reduced"]) <= set(cfg["table"])
        assert cfg["assumed"] and cfg["table"]["n_rows"] > 0


def test_cells_find_their_workload_and_entry(bm):
    for c in bm["workloads"]:
        cell, config, workload = harness.find_cell(bm, c["name"])
        assert config["name"] == c["config"]
        entry = harness.entry_class(workload["entry"])
        for method in ("setup", "step", "label", "end_to_end", "work",
                       "free", "check"):
            assert callable(getattr(entry, method))
        exact = {"granules_differ", "core_differ", "reduct_differ"}
        assert exact <= set(workload["limits"])
        gaps = set(workload["limits"]) - exact
        numbers = {"theta_gap", "core_gap"}
        assert gaps <= numbers | {f"{k}.{d}" for k in numbers
                                  for d in workload["deltas"]}
        # every measure's Θ history is compared
        assert all("theta_gap" in gaps or f"theta_gap.{d}" in gaps
                   for d in workload["deltas"])


def test_every_metric_has_a_reader(bm):
    for m in bm["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_an_unknown_cell_is_an_error(bm):
    with pytest.raises(KeyError):
        harness.find_cell(bm, "no.such.cell")


class FakeClock:
    """A clock that moves only when a unit runs."""

    def __init__(self, durations):
        self.t = 100.0
        self.durations = list(durations)

    def __call__(self):
        return self.t

    def step(self, i):
        self.t += self.durations[i]


@pytest.mark.parametrize("durations,seconds,units,length", [
    ([3.0, 3.0, 3.0, 3.0], 5.0, 2, 6.0),      # the unit that crosses 5 s ends it
    ([2.0, 3.0, 1.0, 1.0], 5.0, 2, 5.0),      # ending exactly at 5 s ends it
    ([10.0, 1.0], 5.0, 1, 10.0),              # one long unit is a whole window
    ([1.0] * 10, 3.5, 4, 4.0),
])
def test_the_window_holds_whole_units(durations, seconds, units, length):
    clock = FakeClock(durations)
    t0, t1, done = harness.window(clock.step, seconds, clock=clock)
    assert len(done) == units and t1 - t0 == pytest.approx(length)
    assert [u.seconds for u in done] == durations[:units]
    assert all(u.ok for u in done)


def test_a_failed_unit_is_counted_and_the_window_goes_on():
    clock = FakeClock([1.0, 1.0, 1.0])

    def step(i):
        clock.step(i)
        if i == 1:
            raise RuntimeError("boom")

    _, _, done = harness.window(step, 2.5, clock=clock)
    assert [u.ok for u in done] == [True, False, True]
    assert "boom" in done[1].error


def records_by_hand():
    """Two reductions of 10 s: folds 3+2 and 4, dispatch 1 and 2."""
    u0 = Unit(0, 0.0, 10.0, "r", True, spans=[
        ("pipeline.fold_chunk", 0.5, 3.5), ("pipeline.fold_chunk", 4.0, 6.0),
        ("engine.dispatch", 8.0, 9.0)])
    u1 = Unit(1, 10.0, 20.0, "r", True, spans=[
        ("pipeline.fold_chunk", 10.5, 14.5), ("engine.dispatch", 17.0, 19.0)])
    ns = lambda t: round(t * 1e9)  # noqa: E731
    busy = [(ns(8.0), ns(8.5)), (ns(17.0), ns(17.5))]
    trace = {"ns": ns, "devices": {"/device:TPU:0": {
        "merged": busy, "idle_frac": 0.95}}, "window_ns": ns(20.0)}
    work = {0: {"engine_bytes": 819e9 * 0.25}, 1: {"engine_bytes": 0.0}}
    return Records([u0, u1], 0.0, 20.0, 0, work, {"hbm_bw": 819e9}, trace)


@pytest.mark.parametrize("name,value", [
    ("ingest.fold_s", (5.0 + 4.0) / 2),
    ("engine.dispatch_s", (1.0 + 2.0) / 2),
    ("reduction.self_s", ((10 - 5 - 1) + (10 - 4 - 2)) / 2),
    ("engine_roofline", 100.0 * 0.25 / 1.0),
    ("device.idle_frac", 0.95),
    ("jax.compiles_in_window", 0),
])
def test_metric_readers_by_hand(name, value):
    assert harness.metric_reader(name)(records_by_hand()) == pytest.approx(
        value)


@pytest.mark.parametrize("name", ["ingest.fold_s", "engine.dispatch_s",
                                  "reduction.self_s", "engine_roofline",
                                  "device.idle_frac"])
def test_a_reader_with_nothing_to_read_returns_none(name):
    rec = records_by_hand()
    rec.trace = None
    for u in rec.units:
        u.spans = []
    assert harness.metric_reader(name)(rec) is None


def run_bench(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kdd99.stream",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_without_a_tpu(tmp_path):
    r = run_bench(ROOT, {"HOME": str(tmp_path)})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_run_exits_nonzero_beside_no_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = run_bench(tmp_path, {"HOME": str(tmp_path)})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
