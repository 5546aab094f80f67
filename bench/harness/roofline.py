"""Peak table lookup and roofline arithmetic."""

from __future__ import annotations

from typing import Dict, Optional


class UnknownDevice(KeyError):
    """The device kind has no row in ``bench/peaks.json``."""


def peak(table: Dict[str, dict], device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error, never a
    default."""
    if device_kind not in table:
        raise UnknownDevice(f"no peaks for device kind {device_kind!r} in "
                            f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


def roofline_share(seconds: float, peaks: dict, *, nbytes: float = 0.0,
                   flops: float = 0.0) -> Optional[float]:
    """Least time the chip could take (the larger of bytes over HBM
    bandwidth and flops over the bf16 peak) as a percentage of the time
    measured.  ``None`` when nothing was measured."""
    if seconds <= 0.0 or (nbytes <= 0 and flops <= 0):
        return None
    least = max(nbytes / peaks["hbm_bytes_per_s"],
                flops / peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds
