"""Pallas TPU kernel: fused contingency→Θ reduction (DESIGN.md §5.2).

The unfused pipeline (``kernel.py`` → ``core.measures.evaluate``) materializes
the full ``[nc, K, M]`` contingency tensor in HBM even though every measure
(PR/SCE/LCE/CCE, paper Table 1/2) only needs a *per-row* sub-evaluation θ that
is then summed:  Θ(D|B) = Σ_i θ(S_i).  Because θ is row-separable and each
contingency row is complete once the G-axis grid walk of its ``[BK, M]`` tile
finishes, the θ epilogue can run inside the kernel — the contingency tensor
never leaves VMEM and the kernel's HBM output shrinks from O(nc·K·M) to
O(nc).

Schedule (grid = (nc, K/BK, G/BG), G innermost, same as the unfused kernel):

    pid_g == 0        init the VMEM accumulator tile with the one-hot matmul
    0 < pid_g         accumulate partial counts (MXU, [BK,BG] @ [BG,M])
    pid_g == nG - 1   EPILOGUE: θ per row of the finished [BK, M] tile,
                      Σ over BK rows, accumulate the scalar into out[c]

The four epilogues are branch-free (``jnp.where`` only, selected statically by
``delta``) and compute the *unnormalized* per-row sub-evaluation; the single
measure-dependent normalization by |U| (and the sign convention Θ_PR = -γ) is
one scalar multiply applied by the caller (``ops.fused_theta``) — keeping the
kernel free of scalar operands.  Padding is self-cancelling end to end:
padding granules carry a sentinel key outside every bin, padding bins are
all-zero rows, and θ of an all-zero row is exactly 0 for all four measures
(0·log 0 ≝ 0 — the ``where(c > 0, ·, 0)`` guards below).

VMEM working set per grid step: the unfused kernel's tiles plus the same
``[BK, M]`` accumulator it already kept resident — the fusion is free in VMEM
and removes the ``[nc, K, M]`` HBM round-trip from the hot path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The epilogues are the measures' own unnormalized row functions — one source
# of truth: plain branch-free jnp, so they trace inside the kernel unchanged.
from repro.core.measures import RAW_ROWS as EPILOGUES

from repro.kernels.platform import interpret_mode

from . import model
from .kernel import DEFAULT_BG, DEFAULT_BK, _cost_estimate
from .model import LANE


def _fused_kernel(packed_ref, wd_ref, out_ref, acc_ref, *, bk: int, delta: str):
    """One (candidate, bin-tile, granule-tile) grid step with θ epilogue."""
    pid_k = pl.program_id(1)
    pid_g = pl.program_id(2)
    n_g = pl.num_programs(2)

    p = packed_ref[...]                                     # [1, BG] int32
    bins = pid_k * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, p.shape[1]), 0)
    onehot = (p == bins).astype(jnp.float32)                # [BK, BG]
    acc = jnp.dot(onehot, wd_ref[...], preferred_element_type=jnp.float32)  # [BK, M]

    @pl.when(pid_g == 0)
    def _init():
        acc_ref[...] = acc

    @pl.when(pid_g != 0)
    def _accum():
        acc_ref[...] += acc

    @pl.when(pid_g == n_g - 1)
    def _epilogue():
        # The scalar Θ partial is stored broadcast over a [1, 128] lane row:
        # Mosaic stores vectors, never scalars, into VMEM.
        partial = jnp.broadcast_to(
            EPILOGUES[delta](acc_ref[...], keepdims=True).sum(
                axis=0, keepdims=True), out_ref.shape)

        @pl.when(pid_k == 0)
        def _first_tile():
            out_ref[...] = partial

        @pl.when(pid_k != 0)
        def _later_tiles():
            out_ref[...] += partial


@functools.partial(
    jax.jit,
    static_argnames=("n_bins", "delta", "bk", "bg", "interpret"),
)
def fused_theta_pallas(
    packed: jnp.ndarray,   # [nc, G] int32
    wd: jnp.ndarray,       # [G, M] float32 — w ⊙ one-hot(d), M lane-padded
    *,
    n_bins: int,
    delta: str,
    bk: int = DEFAULT_BK,
    bg: int = DEFAULT_BG,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Unnormalized Θ partials [nc]; see module docstring for the epilogue math.

    The caller applies the measure's sign/|U| normalization (``ops.fused_theta``).
    ``interpret=None`` compiles on a TPU and interprets elsewhere.
    """
    if delta not in EPILOGUES:
        raise ValueError(f"unknown measure: {delta}")
    nc, g = packed.shape
    m = wd.shape[1]

    # Same padding contract as the unfused kernel: padding granules carry a
    # sentinel key matching no bin; padding bins are all-zero rows with θ = 0.
    g_pad = -(-g // bg) * bg
    k_pad = -(-n_bins // bk) * bk
    if g_pad != g:
        packed = jnp.pad(packed, ((0, 0), (0, g_pad - g)), constant_values=-1)
        wd = jnp.pad(wd, ((0, g_pad - g), (0, 0)))

    grid = (nc, k_pad // bk, g_pad // bg)

    out = pl.pallas_call(
        functools.partial(_fused_kernel, bk=bk, delta=delta),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, bg), lambda c, k, g_: (c, 0, g_)),
            pl.BlockSpec((bg, m), lambda c, k, g_: (g_, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, LANE), lambda c, k, g_: (c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nc, 1, LANE), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bk, m), jnp.float32)],
        cost_estimate=_cost_estimate(
            model.fused_cost(nc, g, n_bins, m, bk, bg, delta=delta)),
        interpret=interpret_mode(interpret),
    )(packed.reshape(nc, 1, g_pad), wd)
    return out[:, 0, 0]
