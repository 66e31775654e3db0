"""Published peaks of each device kind the benchmark runs on, keyed by
`jax.Device.device_kind`. A kind that is not here is an error, never a
default. For a kernel's roofline share: the least time a call could take
is the larger of its operations over `flops_bf16` and its bytes over
`hbm_bytes_per_s`.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "flops_bf16": 197e12,
        "ops_int8": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to peaks.PEAKS") from None
