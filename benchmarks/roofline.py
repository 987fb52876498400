"""Published per-chip peaks, keyed by ``jax.Device.device_kind``, for the
roofline joins of ``decode_microbench.py``."""

from __future__ import annotations

# Published per-chip peaks, keyed by the device_kind JAX reports.  Source:
# Google Cloud documentation, "TPU v5e" (system architecture table): 197
# TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
# inter-chip interconnect per chip over 4 ICI links (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; a device not in :data:`PEAKS`
    is an error, never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}: add them to roofline.PEAKS with "
                       "their source") from None
