"""Published per-chip peaks, keyed by the `device_kind` JAX reports.

ONE table for every place that sets a measured rate against what the
chip could do (bench.py, bench/suite.py). A device that is not in the
table is an error, never a default: dividing by another chip's peak is
how a record ends up claiming a multiple of the silicon.
"""

from __future__ import annotations

# Source: Google Cloud documentation, "TPU v5e" (per chip): 197 TFLOP/s
# bf16, 16 GB HBM at 819 GB/s. jax reports a v5e chip's device_kind as
# "TPU v5 lite".
PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0},
}


def peak_bf16_tflops(device_kind: str) -> float:
    """Peak bf16 TFLOP/s of one chip of `device_kind`; KeyError (naming
    the table) for a device the table does not list."""
    try:
        return PEAKS[device_kind]["bf16_tflops"]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r} in "
            f"actor_critic_tpu/utils/device_peaks.py (known: {sorted(PEAKS)})"
        ) from None
