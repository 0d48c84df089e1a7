r"""The four significance measures (PR, SCE, LCE, CCE) of PLAR Table 1/2.

Every measure factors over equivalence classes (paper §3.2):

    Θ(D|B) = Σ_i θ(S_i),     S_i = (E_i, D)

and every θ needs only the *contingency row* of the class: the counts
``|D_ij| = |E_i ∩ D_j|`` (and their sum ``|E_i|``).  This module computes θ/Θ
from a contingency table ``cont[..., K, m]`` (float32 counts, padding rows are
all-zero and contribute exactly 0 to every measure).

Sign convention (paper, below Table 1): ``Θ_PR(D|B) ≝ -γ_B(D)``, so for all
four measures *smaller Θ is better* and both significances are non-negative:

    Sig_inner(a, B) = Θ(D|B\{a}) - Θ(D|B)
    Sig_outer(a, B) = Θ(D|B)     - Θ(D|B∪{a})
"""
from __future__ import annotations

from typing import Callable, Dict

import jax.numpy as jnp

__all__ = [
    "MEASURES", "RAW_ROWS", "theta_rows", "theta_scale", "evaluate",
    "sig_inner", "sig_outer", "argmin_with_ties", "f32_threshold",
]


# Every measure factors as  θ(S_i) = scale(n) · θ'(row_i)  with θ' depending
# on the counts only.  The split is load-bearing: the fused Pallas kernel
# (DESIGN.md §5.2) runs θ' as its epilogue with no scalar operands, and the
# fused distributed schedule psums raw partials before scaling (linearity).
# θ' of an all-zero row is exactly 0 for all four measures.
#
# The row functions reduce the decision axis with keepdims and drop it only
# at the end: the same values either way, and with ``keepdims=True`` the
# result stays a [..., K, 1] column — the 2-D layout a Mosaic kernel
# epilogue needs (it cannot relayout a 1-D row vector).


def _rows_pr(cont: jnp.ndarray, keepdims: bool = False) -> jnp.ndarray:
    """θ'_PR = |E_i|·1[|E_i/D|=1]  (class is pure → counts toward POS)."""
    e = cont.sum(axis=-1, keepdims=True)
    pure = (cont.max(axis=-1, keepdims=True) == e) & (e > 0)
    out = e * pure.astype(cont.dtype)
    return out if keepdims else out[..., 0]


def _rows_sce(cont: jnp.ndarray, keepdims: bool = False) -> jnp.ndarray:
    """θ'_SCE = Σ_j |D_ij|·log(|D_ij|/|E_i|), with 0·log0 = 0."""
    e = cont.sum(axis=-1, keepdims=True)
    safe_c = jnp.where(cont > 0, cont, 1.0)
    safe_e = jnp.where(e > 0, e, 1.0)
    logs = jnp.log(safe_c) - jnp.log(safe_e)
    return jnp.where(cont > 0, cont * logs, 0.0).sum(axis=-1, keepdims=keepdims)


def _rows_lce(cont: jnp.ndarray, keepdims: bool = False) -> jnp.ndarray:
    """θ'_LCE = Σ_j |D_ij|·(|E_i| - |D_ij|)."""
    e = cont.sum(axis=-1, keepdims=True)
    return (cont * (e - cont)).sum(axis=-1, keepdims=keepdims)


def _rows_cce(cont: jnp.ndarray, keepdims: bool = False) -> jnp.ndarray:
    """θ'_CCE = |E_i|²(|E_i|-1) - Σ_j |D_ij|²(|D_ij|-1).

    Follows Definition 2.9 literally: (|E|/n)·C²_|E|/C²_n = e²(e-1)/(n²(n-1))
    after scaling.  (The paper's Table 2 denominator ``|U|·C²_|U|`` is 2×
    this — a factor that cancels in all significance comparisons; we keep the
    Def-2.9 scale so the brute-force oracle and the decomposed path agree
    bit-for-bit.)
    """
    e = cont.sum(axis=-1, keepdims=True)
    pos = e * e * jnp.maximum(e - 1.0, 0.0)
    neg = (cont * cont * jnp.maximum(cont - 1.0, 0.0)).sum(
        axis=-1, keepdims=True)
    out = pos - neg
    return out if keepdims else out[..., 0]


RAW_ROWS: Dict[str, Callable[[jnp.ndarray], jnp.ndarray]] = {
    "PR": _rows_pr,
    "SCE": _rows_sce,
    "LCE": _rows_lce,
    "CCE": _rows_cce,
}


def theta_scale(delta: str, raw: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """Normalize unnormalized θ' values: the sign/|U| factor of each measure.

    Linear in ``raw``, so it commutes with every summation — per-row, per
    bin-tile, and per-shard raw partials may be summed/psum'd first and
    scaled once.
    """
    n = jnp.asarray(n, jnp.float32)
    if delta in ("PR", "SCE"):
        return -raw / n
    if delta == "LCE":
        return raw / (n * n)
    if delta == "CCE":
        return raw / jnp.maximum(n * n * (n - 1.0), 1.0)
    raise ValueError(f"unknown measure: {delta}")


def _make_theta(delta: str):
    def theta(cont: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
        return theta_scale(delta, RAW_ROWS[delta](cont), n)

    theta.__name__ = f"_theta_{delta.lower()}"
    theta.__doc__ = f"θ_{delta}(S_i) = theta_scale({delta!r}, θ'_{delta}, n)."
    return theta


MEASURES: Dict[str, Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]] = {
    delta: _make_theta(delta) for delta in RAW_ROWS
}


def theta_rows(delta: str, cont: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """Per-class sub-evaluation θ(S_i): cont [..., K, m] → [..., K]."""
    cont = cont.astype(jnp.float32)
    n = jnp.asarray(n, jnp.float32)
    return MEASURES[delta](cont, n)


def evaluate(delta: str, cont: jnp.ndarray, n: jnp.ndarray) -> jnp.ndarray:
    """Θ(D|B) = Σ_i θ(S_i): cont [..., K, m] → [...] (the paper's sum() action).

    PR is computed as a single integer-exact count sum followed by one
    division, so Θ_PR is bit-identical across summation orders (paths/shards)
    whenever |U| < 2²⁴ — which makes tie-breaking deterministic.
    """
    if delta == "PR":
        cont = cont.astype(jnp.float32)
        n = jnp.asarray(n, jnp.float32)
        e = cont.sum(axis=-1)
        pure = (cont.max(axis=-1) == e) & (e > 0)
        pos = (e * pure.astype(cont.dtype)).sum(axis=-1)
        return -pos / n
    return theta_rows(delta, cont, n).sum(axis=-1)


def f32_threshold(base, tol) -> float:
    """``base + tol`` rounded exactly as the device engine's f32 arithmetic.

    Every host-side comparison that must agree with an in-loop f32 compare
    (the argmin tie band, both drivers' stopping thresholds) goes through
    this one helper: the engine='host' vs 'device' bit-identical contract
    rests on the threshold arithmetic matching, so it must not be re-derived
    ad hoc at call sites.
    """
    import numpy as np

    return float(np.float32(np.float32(base) + np.float32(tol)))


def argmin_with_ties(values, tol: float = 1e-5) -> int:
    """Lowest index whose value is within ``tol`` of the minimum.

    Greedy selection must break Θ ties identically across float32 summation
    orders (incremental vs spark vs distributed) and vs the float64 oracle;
    a tolerance band + lowest-index rule does that.  The band edge
    ``min + tol`` is :func:`f32_threshold` to mirror the device engine's
    in-loop argmin bit-for-bit (engine.py pick_greedy): the candidate values
    are f32-representable on every path, so equal thresholds ⇒ equal bands.
    """
    import numpy as np

    v = np.asarray(values, np.float64)
    return int(np.nonzero(v <= f32_threshold(v.min(), tol))[0][0])


def sig_inner(theta_without: jnp.ndarray, theta_with: jnp.ndarray) -> jnp.ndarray:
    r"""Sig^inner = Θ(D|B\{a}) - Θ(D|B)."""
    return theta_without - theta_with


def sig_outer(theta_base: jnp.ndarray, theta_added: jnp.ndarray) -> jnp.ndarray:
    """Sig^outer = Θ(D|B) - Θ(D|B∪{a})."""
    return theta_base - theta_added
