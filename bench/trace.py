"""Reduction of a ``jax.profiler`` trace to the benchmark's device numbers.

A trace is read once into plain interval lists (:func:`load`): for each
device plane the intervals of its XLA ops, and the host's annotations.  All
times are nanoseconds on the profiler's clock.  From them:

* busy time: the union of a device's op intervals inside the window;
* idle share: 1 − busy / window;
* device time inside given host spans (the engine's dispatches);
* the ops that took most time, and the idle gaps of the device, each gap
  labelled with the innermost host span open at its midpoint, summed by
  label.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[int, int]

# Lines of a TPU's plane: the XLA ops that ran on it, and the programs
# (modules) they ran in.
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def op_name(module: str, op: str) -> str:
    """``jit_run/fusion.15`` from ``jit_run(1234)`` and the op's HLO text
    ``%fusion.15 = s32[...] fusion(...)``."""
    return (module.split("(")[0] + "/"
            + op.split(" = ")[0].lstrip("%").strip())


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line) -> List[tuple]:
    return [(ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
            for ev in line.events]


def _named_ops(ops: List[tuple], modules: List[tuple]) -> List[tuple]:
    """Each op named ``module/op`` after the module that holds it."""
    modules = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in modules]
    out = []
    for name, s, e in ops:
        k = bisect.bisect_right(starts, s) - 1
        module = modules[k][0] if k >= 0 and s < modules[k][2] else "?"
        out.append((op_name(module, name), s, e))
    return out


def load(path: str) -> dict:
    """``{"devices": {plane: [(op, start, end), ...]},
    "host": [(name, start, end), ...]}`` from an ``.xplane.pb``; ops are
    named ``module/op``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    host: List[tuple] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: _events(line) for line in plane.lines}
            devices[plane.name] = _named_ops(lines.get(OPS_LINE, []),
                                             lines.get(MODULES_LINE, []))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    return {"devices": devices, "host": host}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``[start, end)`` intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in merged if e > lo and s < hi]


def covered(merged: Sequence[Interval], lo: int, hi: int) -> int:
    return sum(e - s for s, e in clip(merged, lo, hi))


def gaps(merged: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The parts of ``[lo, hi)`` that no interval covers."""
    out, t = [], lo
    for s, e in clip(merged, lo, hi):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def label_at(t: int, spans: Sequence[tuple], default: str = "outside") -> str:
    """Name of the innermost (shortest) span ``(name, start, end)`` open at
    ``t``."""
    best, best_len = default, None
    for name, s, e in spans:
        if s <= t < e and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def self_times(ops: Sequence[tuple]) -> List[tuple]:
    """``(name, start, self time)`` of each op: its duration less that of
    the ops nested directly inside it (a ``while`` op holds its body's)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = {i: ops[i][2] - ops[i][1] for i in order}
    stack: List[int] = []
    for i in order:
        _, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][2]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [(ops[i][0], ops[i][1], own[i]) for i in range(len(ops))]


def device_summary(ops: Sequence[tuple], lo: int, hi: int,
                   spans: Sequence[tuple], top: int = 10) -> dict:
    """Busy time, idle share, top ops by self time (of the ops that start
    in the window) and labelled idle time of one device over the window
    ``[lo, hi)``."""
    merged = union((s, e) for _, s, e in ops)
    busy = covered(merged, lo, hi)
    per_op: Dict[str, int] = defaultdict(int)
    for name, s, own in self_times(ops):
        if lo <= s < hi:
            per_op[name] += own
    idle: Dict[str, int] = defaultdict(int)
    for s, e in gaps(merged, lo, hi):
        idle[label_at((s + e) // 2, spans)] += e - s
    ranked = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    ranked_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_ns": busy,
        "window_ns": hi - lo,
        "idle_frac": 1.0 - busy / (hi - lo),
        "device_ops": [[k, v / 1e9] for k, v in ranked],
        "idle_gaps": [[k, v / 1e9] for k, v in ranked_idle],
        "merged": merged,
    }


def busy_within(merged: Sequence[Interval], spans: Iterable[Interval]) -> int:
    """Device busy time inside the union of host spans."""
    return sum(covered(merged, s, e) for s, e in union(spans))
