"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A kind that is not in the table is an error:
a roofline share against a guessed peak would be a guess."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_bf16: float       # FLOP/s, dense bf16 matrix units
    hbm_bytes_s: float      # bytes/s, HBM bandwidth
    hbm_bytes: int          # bytes of HBM per chip
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        flops_bf16=197e12, hbm_bytes_s=819e9, hbm_bytes=16 * 10**9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"have {sorted(PEAKS)}") from None


def least_seconds(flops: float, bytes_moved: float, peaks: Peaks) -> float:
    """The least time the chip could take for the work: the larger of
    operations over peak FLOP/s and bytes over peak bandwidth."""
    return max(flops / peaks.flops_bf16, bytes_moved / peaks.hbm_bytes_s)
