"""Pallas TPU kernel: batched one-hot contingency reduction (MXU strategy).

The PLAR hot-spot is the paper's ``reduceByKey``: grouping granule weights by
(class-id, decision) for *every candidate attribute at once*.  After the
incremental id-packing of :mod:`repro.core.plan`, every key is a compact
integer ``p ∈ [0, K·V)``, so the grouped count is the contraction

    counts[c, k, j] = Σ_g w_g · 1[packed[c,g] = k] · 1[d_g = j]
                    = Σ_g OneHot(packed)[g, k] · WD[g, j]

i.e. an ``[BK, BG] @ [BG, M]`` matmul per tile — exactly what the MXU runs at
peak.  The GPU analogue would be atomic scatter-adds; TPU has no fast atomics,
so the one-hot-matmul formulation *is* the hardware adaptation (DESIGN.md §2).

Tiling (VMEM working set, per grid step):

    packed tile  [1, BG]           int32     (4·BG bytes; ``packed`` is viewed
                                              as [nc, 1, G] so the block's last
                                              two dims are Mosaic-legal)
    wd tile      [BG, M]           float32   (4·BG·M)
    out tile     [1, BK, M]        float32   (4·BK·M, resident across the
                                              G-axis grid walk)

Grid = (nc, n_bins/BK, G/BG); the G axis is innermost so each output tile is
initialized once (``pid_g == 0``) and accumulated in VMEM — no HBM round-trip
between partial sums.  ``M`` is the decision-class count padded to the 128
lane width by ``ops.py``; BK/BG default to 128/512 keeping the working set
< 0.5 MB, far under the ~16 MB/core VMEM budget, leaving room for
double-buffering of the streamed ``wd`` tiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import interpret_mode

from . import model

DEFAULT_BK = 128   # bin-tile (MXU sublane-aligned output rows)
DEFAULT_BG = 512   # granule-tile (contraction depth per step)


def _cost_estimate(cost: model.KernelCost) -> pl.CostEstimate:
    """Analytic cost (DESIGN.md §5.2) handed to the compiler's scheduler —
    the same closed form the tile selector ranks on."""
    return pl.CostEstimate(
        flops=int(cost.flops),
        transcendentals=int(cost.transcendentals),
        bytes_accessed=int(cost.hbm_bytes),
    )


def _contingency_kernel(packed_ref, wd_ref, out_ref, *, bk: int):
    """One (candidate, bin-tile, granule-tile) grid step."""
    pid_k = pl.program_id(1)
    pid_g = pl.program_id(2)

    p = packed_ref[...]                                    # [1, BG] int32
    bins = pid_k * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, p.shape[1]), 0)
    onehot = (p == bins).astype(jnp.float32)                # [BK, BG]
    acc = jnp.dot(onehot, wd_ref[...], preferred_element_type=jnp.float32)  # [BK, M]

    @pl.when(pid_g == 0)
    def _init():
        out_ref[0, :, :] = acc

    @pl.when(pid_g != 0)
    def _accum():
        out_ref[0, :, :] += acc


@functools.partial(
    jax.jit,
    static_argnames=("n_bins", "bk", "bg", "interpret"),
)
def contingency_pallas(
    packed: jnp.ndarray,   # [nc, G] int32
    wd: jnp.ndarray,       # [G, M] float32 — w ⊙ one-hot(d), M lane-padded
    *,
    n_bins: int,
    bk: int = DEFAULT_BK,
    bg: int = DEFAULT_BG,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """counts[c, k, m] for compact integer keys; see module docstring.

    ``interpret=None`` compiles on a TPU and interprets elsewhere
    (:func:`repro.kernels.platform.interpret_mode`).
    """
    nc, g = packed.shape
    m = wd.shape[1]

    # Pad shapes up to tile multiples (padding granules carry w = 0 and a
    # sentinel key outside [0, n_bins), contributing 0 to every bin).
    g_pad = -(-g // bg) * bg
    k_pad = -(-n_bins // bk) * bk
    if g_pad != g:
        packed = jnp.pad(packed, ((0, 0), (0, g_pad - g)), constant_values=-1)
        wd = jnp.pad(wd, ((0, g_pad - g), (0, 0)))

    grid = (nc, k_pad // bk, g_pad // bg)

    out = pl.pallas_call(
        functools.partial(_contingency_kernel, bk=bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, bg), lambda c, k, g_: (c, 0, g_)),
            pl.BlockSpec((bg, m), lambda c, k, g_: (g_, 0)),
        ],
        out_specs=pl.BlockSpec((1, bk, m), lambda c, k, g_: (c, k, 0)),
        out_shape=jax.ShapeDtypeStruct((nc, k_pad, m), jnp.float32),
        cost_estimate=_cost_estimate(
            model.contingency_cost(nc, g, n_bins, m, bk, bg)),
        interpret=interpret_mode(interpret),
    )(packed.reshape(nc, 1, g_pad), wd)
    return out[:, :n_bins, :]
