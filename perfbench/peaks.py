"""Published peaks of the chips the benchmark may run on, keyed by
``device_kind``. A kind that is not listed raises: a utilization against a
guessed peak is not a measurement."""

from __future__ import annotations

# source: Google Cloud documentation, "TPU v5e" system architecture page
# (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip).
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud TPU v5e",
    },
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


class UnknownDeviceKind(LookupError):
    """``device_kind`` has no row in :data:`PEAKS`."""


def peaks_for(device_kind: str) -> dict:
    """The peaks row of ``device_kind``; raises for a kind not listed."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDeviceKind(
            f"no peaks on record for device kind {device_kind!r}; add its "
            "row to perfbench/peaks.py with the source") from None
