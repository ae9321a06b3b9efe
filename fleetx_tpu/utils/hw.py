"""Hardware peak-FLOPs lookup shared by MFU accounting everywhere.

One table (public spec sheets, dense bf16) so ``chip_smoke.py``'s
records, the Trainer's live ``mfu`` gauge/log-line, and any future
report all divide by the SAME peak — MFU numbers stay comparable across
surfaces. A device the table does not list is an error, never a default:
a utilization against a guessed peak is not a measurement (the CPU has no
row, so no CPU run can print an MFU).
"""

from __future__ import annotations

__all__ = ["PEAK_FLOPS", "UnknownDeviceKind", "peak_flops_per_chip"]

# Peak dense bf16 FLOP/s per chip by device kind (public spec sheets).
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "TPU v4 lite": 138e12,   # v4i
    "TPU v3": 123e12,
    "TPU v6 lite": 918e12,   # Trillium
    "TPU v6e": 918e12,
}


class UnknownDeviceKind(LookupError):
    """``device_kind`` has no row in :data:`PEAK_FLOPS`."""


def peak_flops_per_chip(device) -> float:
    """Peak dense bf16 FLOP/s for ``device`` (a jax Device or anything
    with ``device_kind``). Longest-prefix match so 'TPU v4 lite'
    resolves before 'TPU v4'; an unlisted kind raises
    :class:`UnknownDeviceKind`."""
    kind = device.device_kind
    for name in sorted(PEAK_FLOPS, key=len, reverse=True):
        if kind.startswith(name):
            return PEAK_FLOPS[name]
    raise UnknownDeviceKind(
        f"no peak FLOP/s on record for device kind {kind!r}; add its row "
        "to fleetx_tpu/utils/hw.py PEAK_FLOPS with the source")
