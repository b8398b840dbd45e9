"""Published peaks of each chip the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind not listed here is an error.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s
bf16, 393 TOP/s int8, 16 GiB HBM at 819 GB/s).  JAX names that chip
"TPU v5 lite".
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def for_kind(kind: str) -> dict:
    """The peaks of ``kind``; raises ``KeyError`` for an unknown chip."""
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
