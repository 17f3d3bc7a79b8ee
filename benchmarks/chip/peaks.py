"""Device peaks, keyed by ``device_kind``, and the byte count of one
candidate slot of a grid round: the yardstick for bandwidth shares.

A device that is not in the table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e" (system architecture page):
    # 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
    "TPU v5 lite": {
        "bf16_flop_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table entry of ``device_kind``; raises KeyError for a
    device the table does not list."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks recorded for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}"
        ) from None


def slot_bytes(d: int) -> int:
    """Bytes a grid round gathers from HBM for one candidate slot it scores:
    the candidate's ``d`` float32 coordinates and its int32 point id.
    A lower bound: the cell-coordinate match and bucket reads are not
    counted, so a share computed from it never overstates the bandwidth."""
    if d < 1:
        raise ValueError(f"dimension must be positive, got {d}")
    return 4 * d + 4
