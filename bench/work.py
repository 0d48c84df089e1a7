"""The work a greedy reduction must do, counted from the problem's sizes.

The count is the algorithm's, not the implementation's: in every greedy
iteration each remaining candidate column is read once over the G live
granules, at one byte a value (every configuration has v_max ≤ 256), and
each granule's class id (4 bytes), weight (4 bytes) and decision (1 byte)
once.  Lane padding, bins of ``cap·v_max`` and capacity padding are not
counted, so no change of representation or kernel moves the count, and the
least time it gives is a true lower bound.
"""
from __future__ import annotations

VALUE_BYTES = 1
GRANULE_BYTES = 4 + 4 + 1


def greedy_bytes(granules: int, n_attrs: int, n_core: int,
                 iterations: int) -> int:
    """Bytes the greedy loop must read: iteration t (from 0) has
    ``n_attrs - n_core - t`` candidates left."""
    total = 0
    for t in range(iterations):
        remaining = n_attrs - n_core - t
        total += remaining * granules * VALUE_BYTES + granules * GRANULE_BYTES
    return total
