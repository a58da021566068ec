"""Peak rates of the accelerators this repo runs on, keyed by ``device_kind``.

One table for every roofline placement. A device that is not in it is an
error, never a default: a roofline share against the wrong chip's peaks
reads as a measurement and is not one.

Source: Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s in
bf16, HBM at 819 GB/s, and 1,600 Gbit/s of chip-to-chip
interconnect over four links (50 GB/s per link).
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Per-chip peak rates."""

    flops: float  # bf16 FLOP/s
    hbm_bw: float  # HBM bytes/s
    link_bw: float  # chip-to-chip bytes/s per link


PEAKS = {
    # jax.devices()[0].device_kind of a TPU v5e chip.
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, link_bw=50e9),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``; raises for a device the table lacks."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for device kind {device_kind!r} (known: "
            f"{sorted(PEAKS)}); add the chip's published peaks to "
            "repro.roofline.peaks.PEAKS"
        ) from None


def device_peaks(device=None) -> Peaks:
    """The peaks of ``device`` (default: JAX's first device)."""
    if device is None:
        import jax

        device = jax.devices()[0]
    return peaks_for(device.device_kind)
