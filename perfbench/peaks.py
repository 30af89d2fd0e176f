"""Published peaks of each device kind the benchmark runs on.

Keyed by ``jax.Device.device_kind``.  A kind that is not here is an
error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 16 GiB of HBM at 819 GB/s per chip.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 2**30,
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; raises for an unknown
    kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks known for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
