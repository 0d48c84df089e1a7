"""Pallas TPU kernel: multi-candidate sweep contingency→Θ (DESIGN.md §5.3).

The fused kernel (``fused.py``) evaluates one candidate per grid row and
takes pre-packed ids, so the greedy sweep stages ``packed [nc, G]`` — nc
redundant arithmetic copies of ``r_ids`` — through HBM every iteration, and
every candidate re-streams the granule-resident operands (``r_ids``, ``wd``)
from scratch.  The sweep kernel removes both redundancies:

* **Read-once granule tiles.**  The grid is ``(nc/BC, K/BK, G/BG)`` with G
  innermost and a *block of BC candidates* per grid row.  Each ``r_ids`` and
  ``wd`` tile is DMA'd into VMEM once per (block, bin-tile) and reused by all
  BC candidates of the block — the per-candidate HBM read traffic for the
  shared operands drops by BC×.
* **In-register packing.**  The kernel takes the pre-transposed candidate
  slab ``x_t [nc, G]`` (hoisted out of the greedy loop by the §3.5 engine)
  and the shared ``r_ids [G]``, and computes ``p = r·V + v`` on the tile in
  VMEM — ``packed [nc, G]`` never exists in HBM.

Per-candidate compute is the same one-hot matmul as §5.1/§5.2 (``[BK, BG] @
[BG, M]`` on the MXU), and the θ' epilogue at ``pid_g == nG−1`` is the fused
kernel's, applied per candidate of the block in ascending bin-tile order —
the tile order the §5.3 bin ladder's bit-parity argument relies on.

Padding contract: padded candidate rows are sliced off by the wrapper;
padded granule slots carry ``wd = 0`` rows (zero contribution to every count
and every θ') plus a sentinel key outside every bin; padded bin tiles hold
all-zero rows with θ' = 0.

VMEM working set per grid step: the fused kernel's tiles + a ``[BC, BK, M]``
accumulator (BC× the fused kernel's scratch) — 512 KB at the BC = 8,
BK = 128, M = 128 defaults, 1 MB at BK = 256.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.measures import RAW_ROWS as EPILOGUES

from repro.kernels.platform import interpret_mode

from . import model
from .kernel import _cost_estimate
from .model import LANE

DEFAULT_BC = 8     # candidate block (shared-operand reuse factor)
DEFAULT_BK = 128   # bin-tile (MXU sublane-aligned output rows)
DEFAULT_BG = 256   # granule-tile (contraction depth per step)


def _sweep_kernel(xt_ref, r_ref, wd_ref, out_ref, acc_ref, *, bc: int,
                  bk: int, v_max: int, delta: str):
    """One (candidate-block, bin-tile, granule-tile) grid step."""
    pid_k = pl.program_id(1)
    pid_g = pl.program_id(2)
    n_g = pl.num_programs(2)

    r = r_ref[...]                                          # [1, BG] int32
    wd = wd_ref[...]                                        # [BG, M] f32
    bins = pid_k * bk + jax.lax.broadcasted_iota(
        jnp.int32, (bk, r.shape[1]), 0)

    # The candidate loop is a static unroll: r/wd stay resident in VMEM and
    # are reused by every candidate of the block (the read-once property).
    for c in range(bc):
        p = r * v_max + xt_ref[pl.ds(c, 1), :]              # in-register pack
        onehot = (p == bins).astype(jnp.float32)            # [BK, BG]
        acc = jnp.dot(onehot, wd, preferred_element_type=jnp.float32)

        @pl.when(pid_g == 0)
        def _init(acc=acc, c=c):
            acc_ref[c] = acc

        @pl.when(pid_g != 0)
        def _accum(acc=acc, c=c):
            acc_ref[c] += acc

    @pl.when(pid_g == n_g - 1)
    def _epilogue():
        # Candidate c's scalar Θ' partial fills row c of a [BC, 128] tile:
        # Mosaic stores vectors, never scalars, into VMEM.
        row = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 0)
        partials = jnp.zeros(out_ref.shape, jnp.float32)
        for c in range(bc):
            partials = jnp.where(
                row == c,
                EPILOGUES[delta](acc_ref[c], keepdims=True).sum(
                    axis=0, keepdims=True),
                partials)

        @pl.when(pid_k == 0)
        def _first_tile():
            out_ref[...] = partials

        @pl.when(pid_k != 0)
        def _later_tiles():
            out_ref[...] += partials


@functools.partial(
    jax.jit,
    static_argnames=("v_max", "n_bins", "delta", "bc", "bk", "bg",
                     "interpret"),
)
def sweep_theta_pallas(
    x_t: jnp.ndarray,      # [nc, G] int32 — pre-transposed candidate slab
    r_ids: jnp.ndarray,    # [G]     int32 — shared class ids of U/R
    wd: jnp.ndarray,       # [G, M] float32 — w ⊙ one-hot(d), M lane-padded
    *,
    v_max: int,
    n_bins: int,
    delta: str,
    bc: int = DEFAULT_BC,
    bk: int = DEFAULT_BK,
    bg: int = DEFAULT_BG,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Unnormalized Θ' partials [nc]; see module docstring for the schedule.

    The caller applies the measure's sign/|U| normalization
    (``ops.sweep_theta``).  On a TPU ``bc`` must be a multiple of 8 (the
    candidate axis is the block's sublane axis) or cover every candidate;
    ``interpret=None`` compiles on a TPU and interprets elsewhere.
    """
    if delta not in EPILOGUES:
        raise ValueError(f"unknown measure: {delta}")
    nc, g = x_t.shape
    m = wd.shape[1]

    c_pad = -(-nc // bc) * bc
    g_pad = -(-g // bg) * bg
    k_pad = -(-n_bins // bk) * bk
    if c_pad != nc:
        x_t = jnp.pad(x_t, ((0, c_pad - nc), (0, 0)))
    if g_pad != g:
        # Sentinel pack on padding granules: r = -1 puts p = -V + v below
        # every bin for any v ∈ [0, V); their wd rows are zero anyway.
        x_t = jnp.pad(x_t, ((0, 0), (0, g_pad - g)))
        r_ids = jnp.pad(r_ids, ((0, g_pad - g),), constant_values=-1)
        wd = jnp.pad(wd, ((0, g_pad - g), (0, 0)))

    grid = (c_pad // bc, k_pad // bk, g_pad // bg)

    out = pl.pallas_call(
        functools.partial(_sweep_kernel, bc=bc, bk=bk, v_max=v_max,
                          delta=delta),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bc, bg), lambda b, k, g_: (b, g_)),
            pl.BlockSpec((1, bg), lambda b, k, g_: (0, g_)),
            pl.BlockSpec((bg, m), lambda b, k, g_: (g_, 0)),
        ],
        out_specs=pl.BlockSpec((bc, LANE), lambda b, k, g_: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((c_pad, LANE), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bc, bk, m), jnp.float32)],
        cost_estimate=_cost_estimate(
            model.sweep_cost(nc, g, n_bins, m, bc, bk, bg, v_max=v_max,
                             delta=delta)),
        interpret=interpret_mode(interpret),
    )(x_t, r_ids.reshape(1, -1), wd)
    return out[:nc, 0]
