"""Peak rates per chip, keyed by JAX's ``device_kind``, and the roofline
share computed against them.

A device kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
    # at 819 GB/s per chip
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def roofline_share(device_kind: str, *, flops: float, bytes_moved: float,
                   kernel_s: float) -> float:
    """Percent of the roofline a kernel reached: the least time the chip
    could take for ``flops`` and ``bytes_moved`` (the larger of the two
    bounds) over the kernel's measured device time."""
    if kernel_s <= 0:
        raise ValueError("kernel time must be positive")
    p = peak(device_kind)
    least = max(flops / p["flops_per_s"], bytes_moved / p["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
