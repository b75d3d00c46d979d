"""Published peaks of one chip, keyed by `jax.Device.device_kind`.

Source: Google Cloud TPU documentation, "TPU v5e" system architecture page
(197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM2e at 819 GB/s per chip).
A device that is not in the table is an error, not a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


class NoAccelerator(RuntimeError):
    """The measured path found no TPU, too few chips or an unlisted chip."""


def require_tpu(chips: int):
    """The devices a cell runs on and their peaks; raises NoAccelerator when
    JAX found another platform, fewer chips than the cell asks for or a
    `device_kind` the table does not list."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise NoAccelerator(
            f"the cell needs {chips} TPU chip(s); JAX found platform "
            f"{d.platform!r} ({d.device_kind})")
    if len(devices) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} chip(s); JAX found {len(devices)}")
    return devices[:chips], peaks_for(d.device_kind)


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise NoAccelerator(
            f"no peaks listed for device_kind {device_kind!r}: add it to "
            f"benchmarks/harness/peaks.py with its source")
    return PEAKS[device_kind]
