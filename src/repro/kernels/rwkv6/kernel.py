"""Pallas TPU kernel: RWKV6 linear recurrence, chunk-streamed through VMEM.

The recurrence is O(T) — the whole point of the attention-free architecture —
but a naive per-token HBM loop is memory-bound at one [D,D] state round-trip
per token.  This kernel restores arithmetic intensity by *chunking*:

    grid = (B·H, T / L):   chunk axis innermost ⇒ sequential on TPU,
    state scratch S [D, D] lives in VMEM across the whole chunk walk,
    r/k/v/w chunk tiles [L, D] are streamed (double-buffered) from HBM.

Per chunk the state is updated token-by-token *inside VMEM* (a fori_loop of
rank-1 updates — VPU work), so HBM traffic is exactly one read of r/k/v/w and
one write of o per token: the memory-roofline optimum for this op.  The decay
is applied in linear space per token (no log-space pairwise matrices), which
keeps the kernel *unconditionally* stable for any w ∈ (0,1) — the fully
matmul'd chunk formulation (FLA-style) overflows for small w and is noted in
EXPERIMENTS.md §Perf as the rejected alternative.

Dh for rwkv6-3b is 64 ⇒ the [64, 64] f32 state is one MXU-aligned tile
(16 KiB), and [L=128, 64] streams align the lane dimension.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode

DEFAULT_CHUNK = 128


def _rwkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sfin_ref, s_scr, *, chunk: int):
    ic = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0]

    r = r_ref[0].astype(jnp.float32)   # [L, D]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)   # [D]

    def step(t, carry):
        s, o = carry
        r_t = jax.lax.dynamic_slice_in_dim(r, t, 1, 0)      # [1, D]
        k_t = jax.lax.dynamic_slice_in_dim(k, t, 1, 0)
        v_t = jax.lax.dynamic_slice_in_dim(v, t, 1, 0)
        w_t = jax.lax.dynamic_slice_in_dim(w, t, 1, 0)
        kv = k_t.T @ v_t                                     # [D, D] rank-1
        o_t = r_t @ (s + u[None, :].T * kv)                  # [1, D]
        s = w_t.T * s + kv
        o = jax.lax.dynamic_update_slice_in_dim(o, o_t, t, 0)
        return s, o

    s, o = jax.lax.fori_loop(0, chunk, step, (s_scr[...], jnp.zeros_like(r)))
    s_scr[...] = s
    o_ref[0] = o.astype(o_ref.dtype)

    @pl.when(ic == nc - 1)
    def _final():
        sfin_ref[0] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan(
    r: jnp.ndarray,  # [B, H, T, D]
    k: jnp.ndarray,
    v: jnp.ndarray,
    w: jnp.ndarray,  # decay in (0, 1)
    u: jnp.ndarray,  # [H, D]
    init_state: Optional[jnp.ndarray] = None,  # [B, H, D, D]
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    b, h, t, d = r.shape
    l = min(chunk, t)
    t_pad = -(-t // l) * l
    bh = b * h

    def flat(x):
        x = x.reshape(bh, t, d)
        if t_pad != t:
            x = jnp.pad(x, ((0, 0), (0, t_pad - t), (0, 0)))
        return x

    r_, k_, v_ = flat(r), flat(k), flat(v)
    w_ = flat(w)
    if t_pad != t:
        # Padding decays must be 1 (identity) so the final state is exact.
        pad_mask = (jnp.arange(t_pad) < t)[None, :, None]
        w_ = jnp.where(pad_mask, w_, 1.0)
    u_ = jnp.broadcast_to(u[None], (b, h, d)).reshape(bh, d)
    s0 = (jnp.zeros((bh, d, d), jnp.float32) if init_state is None
          else init_state.reshape(bh, d, d).astype(jnp.float32))

    grid = (bh, t_pad // l)
    o, s_fin = pl.pallas_call(
        functools.partial(_rwkv6_kernel, chunk=l),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, l, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, l, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, l, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, l, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, d), lambda i, c: (i, 0)),
            pl.BlockSpec((1, d, d), lambda i, c: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, l, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, d, d), lambda i, c: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t_pad, d), r.dtype),
            jax.ShapeDtypeStruct((bh, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(r_, k_, v_, w_, u_, s0)
    return o[:, :t].reshape(b, h, t, d), s_fin.reshape(b, h, d, d)
