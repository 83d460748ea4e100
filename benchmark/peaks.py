"""Published peaks of the chips the benchmark may run on, by JAX's
`device_kind`. A kind that is not here is an error, never a default:
a share of a peak that was guessed means nothing."""
from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture page):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
_V5E = {
    "bf16_flops_per_s": 197e12,
    "int8_ops_per_s": 393e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
    "source": "Google Cloud documentation, TPU v5e: 197 TFLOP/s bf16, "
              "393 TOP/s int8, 16 GB HBM at 819 GB/s per chip",
}

PEAKS = {
    "TPU v5 lite": _V5E,      # what jax reports on a v5e (v5litepod)
    "TPU v5e": _V5E,
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; add "
            f"it to benchmark/peaks.py with its source "
            f"(known: {sorted(PEAKS)})") from None
