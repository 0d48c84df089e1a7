"""Roofline terms from compiled dry-run artifacts (no hardware required).

Hardware constants live in one table, :data:`PEAKS`, keyed by the
``device_kind`` JAX reports.  The analytic kernel-cost selector
(:mod:`repro.kernels.contingency.model`) ranks candidate tilings on the
same :func:`roofline_terms` bound.  On a TPU the running chip's row is
used and a kind missing from the table is an error; on any other platform
(the CPU and the Pallas interpreter) tiles are ranked against the v5e row,
named explicitly by :data:`REFERENCE_KIND`.

Three terms per (arch × shape × mesh), all in seconds-per-step:

    compute    = HLO_FLOPs_per_device / peak FLOP/s
    memory     = HLO_bytes_per_device / peak HBM bytes/s
    collective = wire_bytes_per_device / ICI bytes/s per link

FLOPs/bytes come from ``compiled.cost_analysis()``.  CAVEAT (measured, see
EXPERIMENTS.md §Dry-run): XLA's cost analysis counts a ``while`` body ONCE,
so the scanned-layer production artifact under-reports by ~n_layers×.  The
driver therefore lowers two *unrolled probe* configs (1 and 2 periods) and
extrapolates linearly:

    total(P) = cost(p1) + (P - 1) · (cost(p2) - cost(p1))

which is exact for a layer-homogeneous stack (embed/logits cancel in the
difference).  Collectives are parsed from the probes' post-SPMD HLO text the
same way and extrapolated with the same rule.

Wire bytes use the ring model per op kind (n = collective group size):
    all-reduce       2·(n-1)/n · bytes
    all-gather         (n-1)/n · bytes(result)
    reduce-scatter     (n-1)   · bytes(result)     (input = n · result)
    all-to-all         (n-1)/n · bytes
    collective-permute          bytes
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Tuple

# Published per-chip peaks.  Source: Google Cloud documentation, "TPU v5e"
# (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of inter-chip
# interconnect over 4 links = 50 GB/s per link).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
# The row that non-TPU platforms (CPU, the Pallas interpreter) rank against.
REFERENCE_KIND = "TPU v5 lite"


def chip_peaks(device=None) -> Dict[str, float]:
    """Peaks of ``device`` (default: the first JAX device).

    A TPU whose kind is not in :data:`PEAKS` raises: ranking a chip against
    another chip's roofline would be silently wrong.  Other platforms get
    the :data:`REFERENCE_KIND` row.
    """
    if device is None:
        import jax

        device = jax.devices()[0]
    if device.platform != "tpu":
        return PEAKS[REFERENCE_KIND]
    if device.device_kind not in PEAKS:
        raise ValueError(
            f"no roofline peaks for TPU kind {device.device_kind!r}; add its "
            f"published peaks to PEAKS (known: {', '.join(sorted(PEAKS))})")
    return PEAKS[device.device_kind]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

_COLL_RE = re.compile(
    r"=\s*(?P<result>\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(?P<kind>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")


def _shape_bytes(result: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(result):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]
    result_bytes: Dict[str, int]     # Σ result sizes per kind
    wire_bytes: Dict[str, float]     # ring-model per-device wire bytes per kind

    @property
    def total_wire_bytes(self) -> float:
        return sum(self.wire_bytes.values())

    def as_dict(self):
        return {"counts": self.counts, "result_bytes": self.result_bytes,
                "wire_bytes": self.wire_bytes,
                "total_wire_bytes": self.total_wire_bytes}


_CONVERT_RE = re.compile(
    r"= ([a-z0-9]+)\[([0-9,]*)\][^ ]* (convert|bitcast-convert|copy)\("
)


def parse_convert_bytes(hlo_text: str) -> int:
    """Result bytes of dtype-convert/copy ops (CPU bf16-emulation artifacts).

    XLA:CPU emulates bf16 arithmetic by converting to f32 and back; those
    converts are absent on TPU (native bf16 MXU/VPU).  The §Roofline memory
    term is reported both raw and convert-corrected (raw − 2×convert bytes):
    the corrected value is the TPU expectation.
    """
    total = 0
    for m in _CONVERT_RE.finditer(hlo_text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts: Dict[str, int] = {}
    rbytes: Dict[str, int] = {}
    wbytes: Dict[str, float] = {}
    for m in _COLL_RE.finditer(hlo_text):
        kind = m.group("kind")
        result = m.group("result")
        size = _shape_bytes(result)
        # group size: look ahead in the same line
        line_end = hlo_text.find("\n", m.end())
        line = hlo_text[m.start(): line_end if line_end > 0 else len(hlo_text)]
        n = None
        g = _GROUPS_BRACE_RE.search(line)
        if g:
            n = len(g.group(1).split(","))
        else:
            g = _GROUPS_IOTA_RE.search(line)
            if g:
                n = int(g.group(2))
        if n is None or n <= 1:
            n = 2  # conservative default if groups elided
        if kind == "all-reduce":
            wire = 2.0 * (n - 1) / n * size
        elif kind == "all-gather":
            wire = (n - 1) / n * size
        elif kind == "reduce-scatter":
            wire = float(n - 1) * size
        elif kind == "all-to-all":
            wire = (n - 1) / n * size
        else:  # collective-permute
            wire = float(size)
        counts[kind] = counts.get(kind, 0) + 1
        rbytes[kind] = rbytes.get(kind, 0) + size
        wbytes[kind] = wbytes.get(kind, 0.0) + wire
    return CollectiveStats(counts, rbytes, wbytes)


def extrapolate(p1: float, p2: float, n_periods: int) -> float:
    """total(P) = p1 + (P-1)·(p2-p1); clamps tiny negative diffs to 0."""
    delta = max(p2 - p1, 0.0)
    return p1 + (n_periods - 1) * delta


def extrapolate_collectives(s1: CollectiveStats, s2: CollectiveStats,
                            n_periods: int) -> Dict[str, float]:
    kinds = set(s1.wire_bytes) | set(s2.wire_bytes)
    out = {}
    for k in kinds:
        out[k] = extrapolate(s1.wire_bytes.get(k, 0.0), s2.wire_bytes.get(k, 0.0),
                             n_periods)
    return out


# ---------------------------------------------------------------------------
# analytic model FLOPs (the "useful work" yardstick)
# ---------------------------------------------------------------------------


def model_flops(cfg, shape) -> float:
    """6·N_active·D for training; 2·N_active·D + attention reads for serving.

    Attention scores/values add 4·B·Hq·Dh·S_q·S_kv per attention layer
    (halved for causal).  This is the textbook MFU numerator — compiled
    FLOPs above this are remat/padding/capacity waste.
    """
    n_active = cfg.active_param_count()
    b, s = shape.global_batch, shape.seq_len
    kinds = cfg.block_kinds()
    n_attn = sum(1 for k in kinds if k == "attn")
    if cfg.is_encdec:
        n_attn = cfg.enc_layers + 2 * cfg.dec_layers

    if shape.kind == "train":
        if cfg.is_encdec:
            dec = s // 4
            tokens = b * (s + dec)
            attn = 4 * b * cfg.n_heads * cfg.hd * (
                cfg.enc_layers * s * s
                + cfg.dec_layers * dec * dec * 0.5
                + cfg.dec_layers * dec * s
            )
        else:
            tokens = b * s
            attn = 2 * b * cfg.n_heads * cfg.hd * n_attn * (
                min(s, cfg.window or s) * s
            )  # causal ⇒ ×1/2 of 4·S² (window caps the span)
        return 6.0 * n_active * tokens + 3.0 * attn

    if shape.kind == "prefill":
        if cfg.is_encdec:
            tokens = b * s
            attn = 4 * b * cfg.n_heads * cfg.hd * cfg.enc_layers * s * s
        else:
            tokens = b * s
            attn = 2 * b * cfg.n_heads * cfg.hd * n_attn * min(s, cfg.window or s) * s
        return 2.0 * n_active * tokens + attn

    # decode: one token per sequence
    span = min(s, cfg.window or s)
    if cfg.is_encdec:
        attn = 4 * b * cfg.n_heads * cfg.hd * cfg.dec_layers * (s + 1)
        return 2.0 * n_active * b + attn
    attn = 4 * b * cfg.n_heads * cfg.hd * n_attn * span
    return 2.0 * n_active * b + attn


def roofline_terms(flops_dev: float, bytes_dev: float, wire_bytes_dev: float):
    """Seconds per step under each of this chip's peaks (:func:`chip_peaks`)."""
    peaks = chip_peaks()
    compute = flops_dev / peaks["flops"]
    memory = bytes_dev / peaks["hbm_bw"]
    collective = wire_bytes_dev / peaks["ici_bw"]
    dominant = max(
        [("compute", compute), ("memory", memory), ("collective", collective)],
        key=lambda kv: kv[1],
    )[0]
    return {
        "compute_s": compute,
        "memory_s": memory,
        "collective_s": collective,
        "dominant": dominant,
        "bound_s": max(compute, memory, collective),
    }
