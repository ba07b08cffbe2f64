"""Peaks of the chips a cell may run on, keyed by ``device_kind`` as JAX
reports it. A kind that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16 and 393 TOP/s int8 a chip, 16 GB of HBM2e at 819 GB/s,
1,600 Gbit/s of inter-chip interconnect a chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "cloud.google.com/tpu/docs/v5e",
    },
}


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            "no peaks for device kind %r: add it to benchmarks/harness/"
            "peaks.py with its source (known: %s)"
            % (device_kind, sorted(PEAKS))) from None
