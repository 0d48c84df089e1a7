"""Seconds per reduction in the greedy engine: the program's
``engine.dispatch`` spans (``core/engine.py``), which close after
``block_until_ready`` and the unpack, summed per reduction and averaged over
the window's reductions."""


def read(records):
    per_unit = [sum(e - s for _, s, e in records.spans(u, "engine.dispatch"))
                for u in records.units]
    if not any(per_unit):
        return None
    return sum(per_unit) / len(per_unit)
