"""Jit'd public wrappers for flash attention.

``mha`` dispatches between the Pallas kernel (train/prefill hot path) and a
plain XLA fallback.  ``flash_attention_diff`` wraps the kernel in a
``custom_vjp``: Pallas forward, reference-VJP backward (the TPU production
path would pair it with a flash backward kernel; on this CPU target the
backward recompute goes through the jnp oracle — documented in DESIGN.md).
"""
from __future__ import annotations

import functools
from functools import partial
from typing import Optional

import jax

from .kernel import flash_attention
from .ref import attention_ref


@partial(jax.jit, static_argnames=("causal", "window", "scale", "impl", "interpret"))
def mha(
    q, k, v,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    scale: Optional[float] = None,
    impl: str = "pallas",
    interpret: Optional[bool] = None,
):
    """Multi-head attention [B, H, S, Dh] with GQA kv broadcast."""
    if impl == "pallas":
        return flash_attention(
            q, k, v, causal=causal, window=window, scale=scale, interpret=interpret
        )
    if impl == "xla":
        return attention_ref(q, k, v, causal=causal, window=window, scale=scale)
    raise ValueError(f"unknown attention impl: {impl}")


@functools.lru_cache(maxsize=None)
def _diff_attention(causal: bool, window: Optional[int], scale: Optional[float],
                    bq: int, bkv: int):
    @jax.custom_vjp
    def f(q, k, v):
        return flash_attention(q, k, v, causal=causal, window=window, scale=scale,
                               bq=bq, bkv=bkv)

    def fwd(q, k, v):
        return f(q, k, v), (q, k, v)

    def bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(
            lambda q_, k_, v_: attention_ref(q_, k_, v_, causal=causal,
                                             window=window, scale=scale),
            q, k, v,
        )
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def flash_attention_diff(q, k, v, *, causal=True, window=None, scale=None,
                         bq=128, bkv=128):
    """Differentiable flash attention: Pallas fwd, reference-VJP bwd."""
    return _diff_attention(causal, window, scale, bq, bkv)(q, k, v)
