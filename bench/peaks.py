"""Published per-chip peaks, keyed by ``jax.Device.device_kind``: the
benchmark's own copy (the yardstick does not move when the program's
``repro.core.peaks`` does).

Every roofline and utilization the benchmark reports reads this table.
A kind that is not here raises: a peak is never guessed from another
chip.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s, and
1,600 Gbit/s of inter-chip interconnect per chip, i.e. 200 GB/s over
its 4 ICI links (50 GB/s each).
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ChipPeaks:
    bf16_flops: float     # FLOP/s
    int8_ops: float       # OP/s
    hbm_bytes: float      # device memory, bytes
    hbm_bw: float         # bytes/s
    ici_link_bw: float    # bytes/s per inter-chip link
    source: str


V5E = ChipPeaks(bf16_flops=197e12, int8_ops=393e12, hbm_bytes=16e9,
                hbm_bw=819e9, ici_link_bw=50e9,
                source='Google Cloud documentation, "TPU v5e"')

#: device_kind as JAX reports it -> peaks.
PEAKS: dict[str, ChipPeaks] = {
    "TPU v5 lite": V5E,
    "TPU v5e": V5E,
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip of this kind; KeyError for a kind not in the
    table (add it with its source rather than borrowing another's)."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
