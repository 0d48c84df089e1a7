"""Seconds per reduction in which the device is idle during the streaming
fold: the time of the union of the program's ``ingest.h2d`` and
``pipeline.fold_chunk`` spans (``core/granularity.py``), less the device's
busy time inside them from the profiler trace, averaged over the chips used
and over the window's reductions.  A program that records no
``ingest.h2d`` span gives nothing to read."""
from bench import trace

NAMES = ("ingest.h2d", "pipeline.fold_chunk")


def read(records):
    spans = [(n, s, e) for u in records.units for n, s, e in u.spans
             if n in NAMES]
    if records.trace is None or not any(n == NAMES[0] for n, _, _ in spans):
        return None
    ns = records.trace["ns"]
    merged = trace.union((ns(s), ns(e)) for _, s, e in spans)
    span_ns = sum(e - s for s, e in merged)
    devices = records.trace["devices"].values()
    idle = sum(span_ns - trace.busy_within(d["merged"], merged)
               for d in devices) / len(devices)
    return idle / 1e9 / len(records.units)
