"""Peak rates of the chips the benchmark runs on, keyed by ``device_kind``.

The benchmark's own copy, so that a change to the program cannot move
the yardstick. A device the table lacks is an error, never a default.

Source: Google Cloud documentation, "TPU v5e": per chip 197 TFLOP/s in
bf16, 393 TOP/s in int8, 16 GB of HBM at 819 GB/s, and 1,600 Gbit/s of
chip-to-chip interconnect (ICI).

The interconnect figure is one number per chip: the note gives neither a
count of links nor a split by link or by direction. The table takes it as
it stands, 1,600 Gbit/s = 200e9 B/s, for the chip's links all together.
A share of it is therefore a share of everything the chip can send over
the interconnect, whichever of its links a collective uses.
"""

from __future__ import annotations

PEAKS = {
    # jax.devices()[0].device_kind of a TPU v5e chip.
    "TPU v5 lite": {
        "flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bytes_per_s": 200e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of ``device_kind``; raises for a chip the table lacks."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for device kind {device_kind!r} (known: {sorted(PEAKS)})"
        ) from None


def least_seconds(flops: float, bytes_moved: float, device_kind: str) -> float:
    """The least time the chip needs for the work: the larger of the two bounds."""
    pk = peaks_for(device_kind)
    return max(flops / pk["flops"], bytes_moved / pk["hbm_bytes_per_s"])
