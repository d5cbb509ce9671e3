"""Arithmetic the per-layer readers share."""
from __future__ import annotations

from chipbench import peaks as peaks_mod
from chipbench import trace_reduce


def roofline_percent(data, kernels) -> float | None:
    """Σ each kernel's least time (its operations and bytes at the chip's
    peaks) over Σ its device time in the trace, in percent; None where
    the trace shows none of these kernels."""
    if data.reduction is None:
        return None
    secs = trace_reduce.kernel_seconds(
        data.reduction, {k: data.kernel_patterns[k] for k in kernels
                         if k in data.kernel_patterns})
    least = sum(peaks_mod.least_seconds(*data.work[k], data.peaks)
                for k in secs if k in data.work)
    device = sum(secs[k] for k in secs if k in data.work)
    if device <= 0 or least <= 0:
        return None
    return 100.0 * least / device


def idle_percent(data) -> float | None:
    if data.reduction is None or data.reduction.window_s <= 0:
        return None
    return 100.0 * data.reduction.idle_share
