"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not in the table is an
error, never a default: a share of a peak is only as good as the peak.
"""
from __future__ import annotations

__all__ = ["PEAKS", "peaks_for"]

PEAKS = {
    "TPU v5 lite": {
        "name": "TPU v5e",
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e "
        "(https://cloud.google.com/tpu/docs/v5e)",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table entry of ``device_kind``; raises ``KeyError`` for a
    kind the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None
