"""Published peaks per chip, keyed by JAX's `device_kind`.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16,
393 TOP/s int8, 16 GB HBM2 at 819 GB/s). A kind that is not here is an
error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def for_kind(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known: {sorted(PEAKS)}") from None


def roofline_share(kind: str, flops: float, bytes_moved: float,
                   seconds: float) -> float:
    """Percent of the roofline: the least time the chip could take for the
    work (the larger of flops at the bf16 peak and bytes at the HBM peak)
    over the time it took."""
    p = for_kind(kind)
    least = max(flops / p["bf16_flops_per_s"],
                bytes_moved / p["hbm_bytes_per_s"])
    return 100.0 * least / seconds
