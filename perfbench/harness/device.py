"""The device this run is on, as JAX reports it, and its memory peak.

A run that finds no accelerator, or fewer chips than its cell asks
for, fails here: there is no CPU fallback, so a CPU number can never
appear under a device metric's name.
"""

from __future__ import annotations


class NoChip(RuntimeError):
    """JAX found no accelerator, or not the chips the cell asks for."""


def require_chips(chips: int) -> dict:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu":
        raise NoChip(
            f"this benchmark measures an accelerator; JAX found "
            f"platform=cpu ({len(devices)} devices)"
        )
    if len(devices) != chips:
        raise NoChip(
            f"the cell asks for {chips} chip(s); JAX found {len(devices)}"
        )
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes() -> int:
    """Peak on the fullest chip. This runtime counts live arrays under
    ``peak_bytes_in_use`` and the programs' temporaries under
    ``peak_bytes_reserved`` (PERF.md section 6, PR 21), so the peak is
    their sum."""
    import jax

    peak = 0
    for d in jax.devices():
        s = d.memory_stats() or {}
        peak = max(
            peak,
            int(s.get("peak_bytes_in_use", 0))
            + int(s.get("peak_bytes_reserved", 0)),
        )
    return peak
