"""Seconds per reduction in Θ(D|C) and the core: the program's
``reduction.theta_full`` and ``reduction.core`` spans
(``core/reduction.py``), which close after Θ(D|C) and every Θ(D|C\\{a})
are read back, summed per reduction and averaged over the window's
reductions."""

NAMES = ("reduction.theta_full", "reduction.core")


def read(records):
    per_unit = [sum(e - s for n, s, e in u.spans if n in NAMES)
                for u in records.units]
    if not any(per_unit):
        return None
    return sum(per_unit) / len(per_unit)
