"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
393 TOP/s in int8, 16 GB of HBM at 819 GB/s per chip.  JAX reports a
v5e chip as ``"TPU v5 lite"``.  A kind that is not listed is an error,
never a default.
"""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}
SOURCE = 'Google Cloud documentation, "TPU v5e"'


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)} ({SOURCE})") from None
