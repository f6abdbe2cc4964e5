"""Published peaks of each accelerator, keyed by the ``device_kind`` JAX
reports. A kind that is not in the table is an error, never a default."""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s per chip.
_V5E = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes": 16e9,
        "hbm_bytes_s": 819e9, "source": "Google Cloud, TPU v5e"}

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises ``KeyError`` for a kind that
    has no published entry here."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
