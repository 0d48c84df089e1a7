"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  Source: Google Cloud documentation, "TPU v5e":
197 TFLOP/s in bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s,
1,600 Gbit/s of chip-to-chip interconnect.  A kind that is not here is an
error, never a default."""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9, "ici_bw": 200e9},
}


def peaks(kind: str) -> dict:
    if kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {kind!r} "
                       f"(known: {', '.join(sorted(PEAKS))})")
    return PEAKS[kind]
