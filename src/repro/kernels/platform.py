"""Where the Pallas kernels run: compiled by Mosaic on a TPU, interpreted
everywhere else.

The one place the repo decides interpret mode.  Kernel entry points take
``interpret=None`` and resolve it here, from the platform JAX runs on; an
explicit ``True``/``False`` is for tests that must force one mode (e.g. a
compile for a described TPU from a CPU host).
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """``interpret`` if given, else True unless the default backend is a TPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
