"""The chip the benchmark runs on: its published peaks and the checks a run needs.

Peaks are published figures, keyed by the ``device_kind`` JAX reports.
A kind that is not in the table is an error: a roofline share against a
guessed peak means nothing.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["Peaks", "PEAKS", "peaks_for", "describe", "refuse_reason",
           "memory_peak_bytes"]


@dataclass(frozen=True)
class Peaks:
    """Published peaks of one chip.

    Attributes:
        bf16_flops: Dense bf16 matrix FLOP/s.
        hbm_bytes_s: HBM bandwidth, bytes/s.
        hbm_bytes: HBM capacity, bytes.
        source: Where the numbers are published.
    """

    bf16_flops: float
    hbm_bytes_s: float
    hbm_bytes: float
    source: str


PEAKS = {
    "TPU v5 lite": Peaks(
        bf16_flops=197e12,
        hbm_bytes_s=819e9,
        hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e' system architecture",
    ),
}


def peaks_for(device_kind: str) -> Peaks:
    """The peaks of ``device_kind``.

    Raises:
        KeyError: The kind is not in ``PEAKS``.
    """
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


def describe(devices) -> dict:
    """The ``device`` object of the result line, without memory."""
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def refuse_reason(devices, chips: int, interpret: bool) -> str | None:
    """Why this process may not measure a cell that needs ``chips``, or None."""
    platform = devices[0].platform
    if platform != "tpu":
        return f"needs a TPU; JAX found platform {platform!r}"
    if len(devices) < chips:
        return f"the cell needs {chips} chips; JAX found {len(devices)}"
    if interpret:
        return "Pallas is forced into interpret mode (REPRO_INTERPRET)"
    if devices[0].device_kind not in PEAKS:
        return f"no published peaks for device kind {devices[0].device_kind!r}"
    return None


def memory_peak_bytes(devices) -> int:
    """The peak of bytes in use on the fullest of ``devices``."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)
