"""Published peaks per accelerator, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): per chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at
819 GB/s.  A device kind not listed here is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to bench/lib/peaks.py") from None


def roofline_s(flops: float, nbytes: float, device_kind: str):
    """(least seconds the chip could take, the bound that sets it).

    The work's operations over the bf16 peak, or its bytes over the HBM
    bandwidth, whichever is longer: attention over thousands of
    positions is bound by operations, one decode step by bytes."""
    p = peaks(device_kind)
    t_f = flops / p["bf16_flops"]
    t_b = nbytes / p["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "bytes")
