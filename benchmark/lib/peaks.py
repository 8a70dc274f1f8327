"""Peaks of the chips the benchmark knows, keyed by ``device_kind`` as JAX
reports it. A device that is not here is an error, not a default."""

from __future__ import annotations

#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s per chip
PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": 'Google Cloud documentation, "TPU v5e"'},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks on record for device_kind "
                       f"{device_kind!r}; add it to benchmark/lib/peaks.py "
                       f"with its source")
    return PEAKS[device_kind]
