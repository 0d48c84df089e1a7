"""Public wrappers for the contingency kernels.

Handles the TPU lane-width padding of the decision axis (M → multiple of 128)
and unpadding of the result; callers see the logical ``[nc, n_bins, n_dec]``
(unfused) or ``[nc]`` (fused Θ).  The kernels compile on a TPU and run in
the Pallas interpreter elsewhere (:mod:`repro.kernels.platform`).

Tile resolution happens *here*, in plain Python, before the jitted inner
calls: passing ``bk=None``/``bg=None`` (and ``bc=None`` for the sweep) routes
through :func:`repro.kernels.contingency.autotune.resolve_tiles`, whose
default mode is the **analytic** roofline selector (DESIGN.md §5.2).  The
resolved tiles become ordinary static arguments of the jitted kernels, so
every compiled executable is keyed on its concrete tiling — no selector
state is ever baked into a trace, and switching ``selector`` can never serve
a stale compile.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

# Re-exported for kernel callers: the row math and its normalization live in
# one module (repro.core.measures).
from repro.core.measures import theta_scale  # noqa: F401  (public re-export)

from .autotune import resolve_tiles, select_block_sizes  # noqa: F401 (re-export)
from .fused import fused_theta_pallas
from .kernel import contingency_pallas
from .sweep import sweep_theta_pallas

LANE = 128


def _lane_padded_wd(w: jnp.ndarray, d: jnp.ndarray, n_dec: int):
    """w ⊙ one-hot(d) with the decision axis padded to the 128 lane width.

    The single home of the kernels' padding contract: padded columns are
    all-zero, so they contribute 0 to every count and every θ' epilogue.
    """
    m_pad = -(-n_dec // LANE) * LANE
    wd = w[:, None] * (d[:, None] == jnp.arange(m_pad)[None, :]).astype(jnp.float32)
    return wd, m_pad


def contingency(
    packed: jnp.ndarray,   # [nc, G] int32
    d: jnp.ndarray,        # [G] int32
    w: jnp.ndarray,        # [G] float32 (already masked: 0 on padding slots)
    *,
    n_bins: int,
    n_dec: int,
    bk: Optional[int] = None,
    bg: Optional[int] = None,
    selector: Optional[str] = None,
) -> jnp.ndarray:
    """counts[c, k, j] = Σ_g w_g · 1[packed[c,g]=k] · 1[d_g=j]."""
    nc, g = packed.shape
    m_pad = -(-n_dec // LANE) * LANE
    if bk is None or bg is None:
        rk, rg = resolve_tiles("contingency", nc=nc, g=g, n_bins=n_bins,
                               m=m_pad, selector=selector)
        bk = rk if bk is None else bk
        bg = rg if bg is None else bg
    return _contingency_jit(packed, d, w, n_bins=n_bins, n_dec=n_dec,
                            bk=bk, bg=bg)


@partial(jax.jit, static_argnames=("n_bins", "n_dec", "bk", "bg"))
def _contingency_jit(packed, d, w, *, n_bins, n_dec, bk, bg):
    wd, _ = _lane_padded_wd(w, d, n_dec)
    out = contingency_pallas(packed, wd, n_bins=n_bins, bk=bk, bg=bg)
    return out[:, :, :n_dec]


def fused_theta(
    packed: jnp.ndarray,   # [nc, G] int32
    d: jnp.ndarray,        # [G] int32
    w: jnp.ndarray,        # [G] float32 (already masked: 0 on padding slots)
    n,                     # |U| scalar — normalization only, never enters the kernel
    *,
    delta: str,
    n_bins: int,
    n_dec: int,
    bk: Optional[int] = None,
    bg: Optional[int] = None,
    selector: Optional[str] = None,
) -> jnp.ndarray:
    """Θ(D|B∪{a})[c] without materializing the [nc, K, M] contingency tensor.

    Semantics: ``measures.evaluate(delta, contingency(...), n)`` with the θ
    row-reduction fused into the kernel's accumulation epilogue (DESIGN.md
    §5.2).  Default tiling comes from the analytic selector.
    """
    nc, g = packed.shape
    m_pad = -(-n_dec // LANE) * LANE
    if bk is None or bg is None:
        rk, rg = resolve_tiles("fused", nc=nc, g=g, n_bins=n_bins, m=m_pad,
                               delta=delta, selector=selector)
        bk = rk if bk is None else bk
        bg = rg if bg is None else bg
    return _fused_theta_jit(packed, d, w, n, delta=delta, n_bins=n_bins,
                            n_dec=n_dec, bk=bk, bg=bg)


@partial(jax.jit, static_argnames=("delta", "n_bins", "n_dec", "bk", "bg"))
def _fused_theta_jit(packed, d, w, n, *, delta, n_bins, n_dec, bk, bg):
    wd, _ = _lane_padded_wd(w, d, n_dec)
    raw = fused_theta_pallas(
        packed, wd, n_bins=n_bins, delta=delta, bk=bk, bg=bg)
    return theta_scale(delta, raw, n)


def sweep_theta(
    x_t: jnp.ndarray,      # [nc, G] int32 — pre-transposed candidate slab
    r_ids: jnp.ndarray,    # [G]     int32 — shared class ids of U/R
    d: jnp.ndarray,        # [G]     int32
    w: jnp.ndarray,        # [G]   float32 (already masked: 0 on padding slots)
    n,                     # |U| scalar — normalization only, never enters the kernel
    *,
    delta: str,
    v_max: int,
    n_bins: int,
    n_dec: int,
    bc: Optional[int] = None,
    bk: Optional[int] = None,
    bg: Optional[int] = None,
    selector: Optional[str] = None,
) -> jnp.ndarray:
    """Θ(D|R∪{a})[c] from the read-once slab operands (DESIGN.md §5.3).

    Semantics: ``fused_theta(r_ids[None]·V + x_t, ...)`` with the id-packing
    fused into the kernel and each granule tile loaded once per candidate
    *block* — ``packed [nc, G]`` never reaches HBM.  ``n_bins`` may be any
    §5.3 ladder rung ≥ K·V.  Default ``(bc, bk, bg)`` come from the shared
    selector, whose sweep cost model prices the BC× shared-operand reuse.
    """
    nc, g = x_t.shape
    m_pad = -(-n_dec // LANE) * LANE
    if bc is None or bk is None or bg is None:
        rc, rk, rg = resolve_tiles("sweep", nc=nc, g=g, n_bins=n_bins,
                                   m=m_pad, v_max=v_max, delta=delta,
                                   selector=selector)
        bc = rc if bc is None else bc
        bk = rk if bk is None else bk
        bg = rg if bg is None else bg
    return _sweep_theta_jit(x_t, r_ids, d, w, n, delta=delta, v_max=v_max,
                            n_bins=n_bins, n_dec=n_dec, bc=bc, bk=bk, bg=bg)


@partial(jax.jit, static_argnames=("delta", "v_max", "n_bins", "n_dec", "bc",
                                   "bk", "bg"))
def _sweep_theta_jit(x_t, r_ids, d, w, n, *, delta, v_max, n_bins, n_dec,
                     bc, bk, bg):
    wd, _ = _lane_padded_wd(w, d, n_dec)
    raw = sweep_theta_pallas(
        x_t, r_ids, wd, v_max=v_max, n_bins=n_bins, delta=delta, bc=bc,
        bk=bk, bg=bg)
    return theta_scale(delta, raw, n)
