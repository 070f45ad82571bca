"""Per-chip peaks, keyed by ``jax.Device.device_kind`` — roofline
denominators.

Source of every number in ``PEAKS``: Google Cloud documentation,
"TPU v5e" (system architecture table): 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect
over 4 ICI links.  A device kind missing from the table is an error,
never a default: add its row with its own source.

``DCN_BW`` has no published per-chip figure; it is an assumption of
the multi-pod cost model (``repro.launch.federated``), not a peak.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float       # FLOP/s per chip
    ops_int8: float         # OP/s per chip
    hbm_bw: float           # bytes/s per chip
    hbm_bytes: int          # HBM capacity per chip
    ici_bw: float           # bytes/s per ICI link


V5E = "TPU v5 lite"         # what JAX reports as a v5e's device_kind

PEAKS = {
    V5E: ChipPeaks(flops_bf16=197e12, ops_int8=393e12, hbm_bw=819e9,
                   hbm_bytes=16 * 2**30, ici_bw=1600e9 / 8 / 4),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The peak row of ``device_kind``; raises for an unknown kind."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak table for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


DCN_BW = 25e9               # bytes/s per chip across pods (assumed)

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}
