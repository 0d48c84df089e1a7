"""Seconds per reduction in the core's sketch: the program's ``core.sketch``
spans (``core/reduction.py``), which close after the A groupings of
Θ(D|C\\{a}) are read back, summed per reduction and averaged over the
window's reductions.  A program whose core takes no sketch has none."""


def read(records):
    per_unit = [sum(e - s for n, s, e in u.spans if n == "core.sketch")
                for u in records.units]
    if not any(per_unit):
        return None
    return sum(per_unit) / len(per_unit)
