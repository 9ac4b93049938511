"""Published peaks of each accelerator the benchmark may run on, keyed by the
``device_kind`` JAX reports.

Source: Google Cloud documentation, "TPU v5e" (system architecture page):
197 TFLOP/s bf16 (393 TOP/s int8), 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI.
A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

from typing import NamedTuple


class Peaks(NamedTuple):
    flops_bf16: float        # FLOP/s
    hbm_bytes_per_s: float   # bytes/s
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(197e12, 819e9, "Google Cloud documentation, TPU v5e"),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; raises ``KeyError`` for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
