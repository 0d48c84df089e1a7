"""The bytes the core's sketch must read, counted from the problem's sizes.

For each of the A attributes the sketch groups the granules of C\\{a} by
two 32-bit row fingerprints less the attribute's own term, and counts the
classes against the decisions.  It reads, at the granule capacity: the
attribute's column (one byte a value, as in ``work.py``), the two row
fingerprints (4 bytes each), the decision (1 byte), the weight (4 bytes)
and the validity mask (1 byte).  Capacity padding is counted, as the
program sweeps it; lane padding and the sort's own passes are not.
"""
from __future__ import annotations

from bench.work import VALUE_BYTES

ROW_BYTES = VALUE_BYTES + 4 + 4 + 1 + 4 + 1


def sketch_bytes(capacity: int, n_attrs: int) -> int:
    """Bytes read by the A groupings of Θ(D|C\\{a})."""
    return n_attrs * capacity * ROW_BYTES
