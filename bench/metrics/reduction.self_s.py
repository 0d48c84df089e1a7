"""Seconds per reduction outside the fold and the engine: the benchmark's
own span around each ``plar_reduce`` call, less the ``pipeline.fold_chunk``
and ``engine.dispatch`` spans inside it.  That is Θ(D|C), the core
(``core/reduction.py``), the capacity shrink and the host-to-device copies
of the chunks, averaged over the window's reductions."""

CHILDREN = ("pipeline.fold_chunk", "engine.dispatch")


def read(records):
    if not any(s[0] == "engine.dispatch" for u in records.units
               for s in u.spans):
        return None
    per_unit = [u.seconds - sum(e - s for n, s, e in u.spans if n in CHILDREN)
                for u in records.units]
    return sum(per_unit) / len(per_unit)
