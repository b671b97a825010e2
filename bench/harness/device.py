"""The device guard and the device record of the result line."""

from __future__ import annotations

import jax


class NoChip(SystemExit):
    pass


def require_chips(count: int) -> list:
    """The first `count` TPU devices, or exit non-zero naming what is
    missing.  Never falls back to another platform."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: no TPU: jax.devices()[0].platform is "
                     f"{devs[0].platform!r}; the benchmark measures only on "
                     f"the chip")
    if len(devs) < count:
        raise NoChip(f"bench: the cell needs {count} TPU chips, found "
                     f"{len(devs)}")
    return devs[:count]


def peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest of `devs` (0 where the backend
    keeps no statistics)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def record(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
