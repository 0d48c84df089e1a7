"""Share of its roofline that the greedy engine reaches, in percent.

Least time: the bytes the greedy loops of the window's reductions must read
(``bench/work.py``, counted from the problem's sizes) over the chip's
published HBM bandwidth (``bench/peaks.py``).  Engine time: the device's
busy time inside the program's ``engine.dispatch`` spans, from the profiler
trace.  The engine is bound by memory (it does no arithmetic beyond
counting), so the bandwidth term is the roofline."""
from bench import trace


def read(records):
    if records.trace is None:
        return None
    ns = records.trace["ns"]
    spans = [(ns(s), ns(e)) for u in records.units
             for n, s, e in u.spans if n == "engine.dispatch"]
    least = sum(w["engine_bytes"] for w in records.work.values()) \
        / records.peaks["hbm_bw"]
    devices = records.trace["devices"].values()
    busy = sum(trace.busy_within(d["merged"], spans) for d in devices) \
        / len(devices) / 1e9
    if not spans or busy <= 0 or least <= 0:
        return None
    return 100.0 * least / busy
