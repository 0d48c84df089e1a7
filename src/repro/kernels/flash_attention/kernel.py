"""Pallas TPU kernel: flash attention forward (GQA, causal, sliding window).

The LM substrate's dominant compute hot-spot.  Online-softmax streaming over
key/value tiles keeps the working set in VMEM regardless of sequence length:

    q tile    [BQ, Dh]      (resident across the kv walk)
    k,v tiles [BKV, Dh]     (streamed, double-buffered by the pipeline)
    scratch   m [BQ], l [BQ], acc [BQ, Dh]

Grid = (B, Hq, Sq/BQ, Skv/BKV) with the kv axis innermost: scratch persists
across the kv walk of one (b, h, iq) cell (TPU grid steps are sequential) and
the output tile is written once at the last kv step.  GQA is expressed in the
k/v BlockSpec index maps (query head h reads kv head h // group) — no repeated
kv materialization in HBM.

The two matmuls per step are [BQ,Dh]@[Dh,BKV] and [BQ,BKV]@[BKV,Dh]; with
BQ = BKV = 128 and Dh ∈ {64, 128, 256} every MXU dim is 128-aligned.
Numerics follow the standard streaming-softmax recurrence in f32; fully
masked tiles are handled by zeroing probabilities (never exp of a sentinel).

Positions are aligned to the *ends* of q/kv (decode convention): query i has
absolute position skv - sq + i.  Causal skip of fully-masked tiles is a
masking no-op here (interpret-mode correctness target); on hardware the same
grid supports `pltpu.emit_pipeline`-style early-exit — see EXPERIMENTS.md
§Perf for how we count the causal/window FLOP discount in the roofline.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import interpret_mode

DEFAULT_BQ = 128
DEFAULT_BKV = 128
NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref,
    m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, window: Optional[int],
    bq: int, bkv: int, sq: int, skv: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                       # [BQ, Dh]
    k = k_ref[0, 0].astype(jnp.float32)                       # [BKV, Dh]
    v = v_ref[0, 0].astype(jnp.float32)                       # [BKV, Dh]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                                 # [BQ, BKV]

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0) + (skv - sq)
    kpos = ik * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = kpos < skv  # padded kv tail is never attendable
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_new)                           # exp(-inf - -inf) guarded below
    alpha = jnp.where(m_prev == NEG_INF, 0.0, alpha)
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(mask, p, 0.0)

    l_new = alpha * l_scr[...] + p.sum(axis=-1)
    acc_new = alpha[:, None] * acc_scr[...] + jnp.dot(
        p, v, preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "bq", "bkv", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,  # [B, Hq, Sq, Dh]
    k: jnp.ndarray,  # [B, Hkv, Skv, Dh]
    v: jnp.ndarray,  # [B, Hkv, Skv, Dh]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    bq: int = DEFAULT_BQ,
    bkv: int = DEFAULT_BKV,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    b, hq, sq, dh = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    if scale is None:
        scale = dh ** -0.5

    bq_ = min(bq, sq)
    bkv_ = min(bkv, skv)
    # Pad sequence dims to tile multiples; padded kv keys are masked off via
    # positions (kpos >= skv never satisfies kpos <= qpos for real queries).
    sq_pad = -(-sq // bq_) * bq_
    skv_pad = -(-skv // bkv_) * bkv_
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_pad - sq), (0, 0)))
    if skv_pad != skv:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, skv_pad - skv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, skv_pad - skv), (0, 0)))

    grid = (b, hq, sq_pad // bq_, skv_pad // bkv_)
    kernel = functools.partial(
        _flash_kernel,
        scale=scale, causal=causal, window=window,
        bq=bq_, bkv=bkv_, sq=sq, skv=skv,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq_, dh), lambda b_, h, iq, ik: (b_, h, iq, 0)),
            pl.BlockSpec((1, 1, bkv_, dh), lambda b_, h, iq, ik, g=group: (b_, h // g, ik, 0)),
            pl.BlockSpec((1, 1, bkv_, dh), lambda b_, h, iq, ik, g=group: (b_, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq_, dh), lambda b_, h, iq, ik: (b_, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq_pad, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_, dh), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
    return out[:, :, :sq, :]
