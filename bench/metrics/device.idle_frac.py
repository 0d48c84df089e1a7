"""Share of the traced window in which no operation ran on the device:
1 − (union of the device's op intervals) / window, from the profiler trace,
averaged over the chips used."""


def read(records):
    if records.trace is None:
        return None
    devices = records.trace["devices"].values()
    return sum(d["idle_frac"] for d in devices) / len(devices)
