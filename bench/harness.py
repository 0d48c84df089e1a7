"""The harness: finds a cell's files by name, times the window, reads the
trace and the per-layer metrics, and prints the result.

Everything that belongs to one configuration, traffic mix, entry or
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives:

* ``bench/configs/<config>.json``   — the configuration;
* ``bench/workloads/<cell>.json``   — the cell's traffic, which names its
  entry;
* ``bench/entries/<entry>.py``      — a class ``Entry`` that sets the cell
  up, runs one unit of work, and checks what the units produced;
* ``bench/metrics/<metric>.py``     — a function ``read(records)`` that
  returns the metric, or ``None`` where it finds nothing to read.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(Exception):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


# ---------------------------------------------------------------------------
# discovery
# ---------------------------------------------------------------------------


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def find_cell(bm: dict, name: str):
    """``(cell, config, workload)`` of the cell ``name``."""
    cells = {c["name"]: c for c in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} (cells: {', '.join(sorted(cells))})")
    cell = cells[name]
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    workload = load_json(BENCH / "workloads" / f"{name}.json")
    return cell, config, workload


def _module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def entry_class(name: str):
    return _module(BENCH / "entries" / f"{name}.py").Entry


def metric_reader(name: str) -> Callable:
    return _module(BENCH / "metrics" / f"{name}.py").read


def cell_metrics(bm: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``cell`` reports."""
    return [m for m in bm[kind] if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Unit:
    """One unit of work of the window (here: one reduction)."""
    index: int
    start: float
    end: float
    label: str
    ok: bool
    error: str = ""
    spans: list = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.end - self.start


def window(step: Callable[[int], None], seconds: float, *,
           label: Callable[[int], str] = lambda i: "unit",
           annotate: Optional[Callable] = None,
           clock: Callable[[], float] = time.perf_counter):
    """Run ``step(0), step(1), …`` back to back.  The window closes when
    the first unit that ends after ``seconds`` have elapsed ends, so every
    unit in it is whole.  Returns ``(t0, t1, units)``."""
    units: List[Unit] = []
    t0 = clock()
    i = 0
    while True:
        s = clock()
        ok, err = True, ""
        try:
            if annotate is None:
                step(i)
            else:
                with annotate(i):
                    step(i)
        except Exception as e:  # a failed unit counts against attempted
            ok, err = False, f"{type(e).__name__}: {e}"
        e = clock()
        units.append(Unit(i, s, e, label(i), ok, err))
        i += 1
        if e - t0 >= seconds:
            return t0, e, units


# ---------------------------------------------------------------------------
# records the per-layer metrics read
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Records:
    units: List[Unit]
    t0: float
    t1: float
    compiles_in_window: int
    work: dict                       # per unit index: {"engine_bytes": …}
    peaks: dict
    trace: Optional[dict] = None     # see _read_trace

    def spans(self, unit: Unit, name: str) -> list:
        return [s for s in unit.spans if s[0] == name]


def _obs_spans(units: List[Unit], records, epoch: float) -> None:
    """Hand each program span ``(name, start, end)``, in ``perf_counter``
    seconds (the recorder's times plus its ``epoch``), to the unit it lies
    in."""
    for rec in records:
        if rec.ph != "X":
            continue
        s, e = epoch + rec.t_start, epoch + rec.t_start + rec.dur
        for u in units:
            if u.start <= s and e <= u.end:
                u.spans.append((rec.name, s, e))
                break


def _epoch(obs) -> float:
    """The ``perf_counter`` time from which the program's recorder counts:
    an event recorded at a known time gives it (to some microseconds)."""
    t = time.perf_counter()
    obs.event("bench.epoch")
    rec = [r for r in obs.get_tracer().records() if r.name == "bench.epoch"]
    return t - rec[-1].t_start


def _read_trace(log_dir: str, units: List[Unit], t0: float, t1: float,
                unit_marks: List[float]) -> dict:
    """Device numbers of the traced window, on the profiler's clock.

    The host clock and the profiler's are tied by the benchmark's own
    ``bench.reduce`` annotations: each was opened at a known
    ``perf_counter`` time, and the trace gives its start.  The spread of
    those offsets says how well the two clocks agree."""
    from bench import trace as tr

    data = tr.load(tr.find_xplane(log_dir))
    marks = sorted(s for name, s, _ in data["host"] if name == "bench.reduce")
    if len(marks) != len(unit_marks):
        raise RuntimeError(f"{len(marks)} bench.reduce annotations in the "
                           f"trace for {len(unit_marks)} units")
    offsets = [m - round(p * 1e9) for m, p in zip(marks, unit_marks)]
    off = int(statistics.median(offsets))
    skew = max(abs(o - off) for o in offsets)

    def ns(t: float) -> int:
        return round(t * 1e9) + off

    lo, hi = ns(t0), ns(t1)
    spans = [(n, s, e) for n, s, e in data["host"] if n == "bench.reduce"]
    spans += [(n, ns(s), ns(e)) for u in units for n, s, e in u.spans]
    per_dev = {name: tr.device_summary(ops, lo, hi, spans)
               for name, ops in data["devices"].items() if ops}
    if not per_dev:
        raise RuntimeError("the trace holds no device operation")
    return {"devices": per_dev, "ns": ns, "clock_skew_ns": skew,
            "window_ns": hi - lo}


# ---------------------------------------------------------------------------
# a run
# ---------------------------------------------------------------------------


def device_info(chips: int, *, require_tpu: bool = True) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def run(cell: dict, config: dict, workload: dict, bm: dict, *, seed: int,
        seconds: float, trace: bool, t_process: float,
        require_tpu: bool = True) -> dict:
    """One run of one cell; returns the result line's object."""
    import jax

    from bench import peaks as pk
    from bench.counters import CompileCounter

    device = device_info(cell["chips"], require_tpu=require_tpu)
    chip_peaks = pk.peaks(device["kind"]) if require_tpu else pk.PEAKS[
        "TPU v5 lite"]
    counter = CompileCounter()
    entry = entry_class(workload["entry"])(config, workload, seed)
    entry.setup()
    log(f"[setup] {time.perf_counter() - t_process:.3f}s, "
        f"{counter.compiles} executables compiled or loaded, "
        f"{counter.cache_hits} from the persistent cache")

    log_dir = None
    tracer = None
    if trace:
        from repro import obs

        tracer = obs.enable()
        tracer.clear()
        epoch = _epoch(obs)
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # no per-call Python events: they
        opts.host_tracer_level = 1     # slow the host; annotations stay
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    marks: List[float] = []

    def annotate(i):
        marks.append(time.perf_counter())
        return jax.profiler.TraceAnnotation("bench.reduce")

    compiles0 = counter.compiles
    t0, t1, units = window(entry.step, seconds, label=entry.label,
                           annotate=annotate if trace else None)
    compiles = counter.compiles - compiles0
    setup_s = t0 - t_process
    peak = memory_peak_bytes()
    log(f"[window] {len(units)} units in {t1 - t0:.3f}s, "
        f"{compiles} compiles inside it")
    for u in units:
        log(f"  unit {u.index} {u.label}: {u.seconds:.3f}s"
            + ("" if u.ok else f" FAILED {u.error}"))

    records = Records(units, t0, t1, compiles, {}, chip_peaks)
    if trace:
        jax.profiler.stop_trace()
        from repro import obs

        _obs_spans(units, tracer.records(), epoch)
        obs.disable()
        records.trace = _read_trace(log_dir, units, t0, t1, marks)
        shutil.rmtree(log_dir, ignore_errors=True)
        log(f"[trace] host and profiler clocks agree within "
            f"{records.trace['clock_skew_ns'] / 1e6:.3f} ms")
    records.work = entry.work(units)
    entry.free()

    t_check = time.perf_counter()
    checks = entry.check(units)
    log(f"[check] reference took {time.perf_counter() - t_check:.1f}s")

    failed = sum(not u.ok for u in units)
    result = {
        "correct": failed == 0 and all(c.ok for c in checks),
        "attempted": len(units),
        "failed": failed,
        "metrics": {},
        "device": dict(device, memory_peak_bytes=peak),
    }
    if trace:
        devs = records.trace["devices"].values()
        result["device"]["busy_s"] = statistics.mean(
            d["busy_ns"] for d in devs) / 1e9
        result["device"]["window_s"] = records.trace["window_ns"] / 1e9
        first = next(iter(records.trace["devices"].values()))
        result["breakdown"] = {"device_ops": first["device_ops"],
                               "idle_gaps": first["idle_gaps"]}
        for m in cell_metrics(bm, cell["name"], "per_layer"):
            value = metric_reader(m["name"])(records)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    else:
        e2e = dict(entry.end_to_end(t0, t1, units), setup_s=setup_s)
        for m in cell_metrics(bm, cell["name"], "end_to_end"):
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in checks}
    for c in checks:
        log(f"check {c.name} {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAIL'}")
    return result
