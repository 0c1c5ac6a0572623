"""Published peaks of the accelerators the benchmark runs on.

Keyed by ``jax.Device.device_kind``. A kind that is not here is an error:
a roofline or utilization against a guessed peak means nothing.
"""
from __future__ import annotations

#: device_kind -> peaks. Source of the v5e row: Google Cloud documentation,
#: "TPU v5e" (system architecture): 197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB HBM at 819 GB/s, 1,600 Gbit/s inter-chip interconnect.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; raises on an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a "
            f"row with its source to bench/peaks.py") from None


def least_time_s(flops: float, nbytes: float, device_kind: str) -> float:
    """The least time a call can take on one chip: the larger of its
    operations over the bf16 peak and its bytes over the HBM bandwidth."""
    p = peaks(device_kind)
    return max(flops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
