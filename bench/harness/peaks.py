"""Published peaks of the devices the benchmark runs on, keyed by
``device_kind`` as JAX reports it.

Source: Google Cloud documentation, "TPU v5e" (system architecture
table): 16 GB of HBM at 819 GB/s per chip, 197 TFLOP/s bf16,
393 TOP/s int8. A device that is not in the table is an error, never a
default: a roofline share against a guessed peak means nothing.
"""
from __future__ import annotations

_V5E = {
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "source": "Google Cloud documentation, TPU v5e",
}

PEAKS = {
    "TPU v5 lite": _V5E,
    "TPU v5e": _V5E,
}


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to bench/harness/peaks.py with their source") from None
