"""Seconds per reduction merging chunks into the streaming fold's
accumulator: the program's ``ingest.merge`` spans (``core/granularity.py``,
inside ``pipeline.fold_chunk``), which close after the merged granule count
is read back and the accumulator is shrunk, summed per reduction and
averaged over the window's reductions."""


def read(records):
    per_unit = [sum(e - s for _, s, e in records.spans(u, "ingest.merge"))
                for u in records.units]
    if not any(per_unit):
        return None
    return sum(per_unit) / len(per_unit)
