"""Arithmetic shared by the per-layer metric readers: each reader in
``bench/metrics/`` is a few lines over these."""
from __future__ import annotations

from typing import Optional

from lib.peaks import peaks


def peak_flops(ctx) -> float:
    return peaks(ctx.counters["device_kind"])["flops_bf16"]


def idle_share(red) -> Optional[float]:
    """Per cent of the traced window in which no operation ran on the
    device; nothing where no trace was read."""
    if not red or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])


def train_flops(ctx) -> float:
    """Model FLOPs of the window's client training: forward and
    backward (3 x 2 x MACs) per image."""
    c = ctx.counters
    return 6.0 * c["macs"] * c["images"]

