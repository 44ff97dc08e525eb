"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports. A chip that is not here is an error, never a
default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""

from __future__ import annotations

TPU_V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
           "hbm_bytes": 16e9, "source": "Google Cloud, TPU v5e"}

PEAKS = {
    "TPU v5 lite": TPU_V5E,
    "TPU v5e": TPU_V5E,
}


def peak_of(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
