"""Seconds per reduction in the streaming GrC fold: the program's
``pipeline.fold_chunk`` spans (``core/granularity.py``), which close after
the merged granule count is read back, summed per reduction and averaged
over the window's reductions."""


def read(records):
    per_unit = [sum(e - s for _, s, e in records.spans(u, "pipeline.fold_chunk"))
                for u in records.units]
    if not any(per_unit):
        return None
    return sum(per_unit) / len(per_unit)
