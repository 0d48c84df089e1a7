"""Jit'd public wrappers for the RWKV6 recurrence kernel."""
from __future__ import annotations

import functools
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .kernel import rwkv6_scan
from .ref import rwkv6_ref


@partial(jax.jit, static_argnames=("impl", "chunk", "interpret"))
def rwkv6(
    r, k, v, w, u,
    init_state: Optional[jnp.ndarray] = None,
    *,
    impl: str = "pallas",
    chunk: int = 128,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """RWKV6 recurrence over [B, H, T, D]; returns (outputs, final state)."""
    if impl == "pallas":
        return rwkv6_scan(r, k, v, w, u, init_state, chunk=chunk, interpret=interpret)
    if impl == "xla":
        return rwkv6_ref(r, k, v, w, u, init_state)
    raise ValueError(f"unknown rwkv6 impl: {impl}")


@functools.lru_cache(maxsize=None)
def _diff_rwkv6(chunk: int):
    @jax.custom_vjp
    def f(r, k, v, w, u, s0):
        return rwkv6_scan(r, k, v, w, u, s0, chunk=chunk)

    def fwd(r, k, v, w, u, s0):
        return f(r, k, v, w, u, s0), (r, k, v, w, u, s0)

    def bwd(res, g):
        _, vjp = jax.vjp(lambda *a: rwkv6_ref(*a), *res)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


def rwkv6_diff(r, k, v, w, u, s0, *, chunk: int = 128):
    """Differentiable RWKV6: Pallas fwd, reference-VJP bwd."""
    return _diff_rwkv6(chunk)(r, k, v, w, u, s0)
