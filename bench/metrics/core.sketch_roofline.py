"""Share of its roofline that the core's sketch reaches, in percent.

Least time: the bytes the window's sketches must read
(``bench/sketch_work.py``, counted from the granule capacity and the
attributes) over the chip's published HBM bandwidth (``bench/peaks.py``).
Sketch time: the device's busy time inside the program's ``core.sketch``
spans, from the profiler trace.  The sketch sorts and counts and does no
arithmetic to speak of, so the bandwidth term is the roofline."""
from bench import trace


def read(records):
    if records.trace is None:
        return None
    ns = records.trace["ns"]
    spans = [(ns(s), ns(e)) for u in records.units
             for n, s, e in u.spans if n == "core.sketch"]
    least = sum(w.get("sketch_bytes", 0) for w in records.work.values()) \
        / records.peaks["hbm_bw"]
    devices = records.trace["devices"].values()
    busy = sum(trace.busy_within(d["merged"], spans) for d in devices) \
        / len(devices) / 1e9
    if not spans or busy <= 0 or least <= 0:
        return None
    return 100.0 * least / busy
