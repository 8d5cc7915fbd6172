"""The chip a run uses: the TPU check, the device record, peak memory."""
from __future__ import annotations

import sys


class NoChip(SystemExit):
    """Raised where JAX finds no TPU, or fewer chips than the cell asks
    for; the run then exits non-zero and prints no result."""

    def __init__(self, msg: str):
        print(f"chipbench: {msg}", file=sys.stderr, flush=True)
        super().__init__(2)


def require_tpu(chips: int) -> list:
    """JAX's devices, which must be at least ``chips`` TPUs."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no usable device: {e}") from None
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {len(devs)} {devs[0].platform} "
                     f"device(s)")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs


def record(devs: list) -> dict:
    """The ``device`` block of a result line, as JAX reports the chips."""
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(devs: list) -> int:
    """Peak bytes in use on the fullest chip since the process started."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)
