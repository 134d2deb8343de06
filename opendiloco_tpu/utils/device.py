"""Where a run executed, as every written artifact records it."""

from __future__ import annotations

from typing import Optional, Sequence

# the stamp of a run that does no device work at all (numpy payloads over
# sockets or loopback backends): its numbers are host numbers
HOST_ONLY = {"platform": "host", "device_kind": None, "device_count": 0}


def device_stamp(devices: Optional[Sequence] = None) -> dict:
    """``platform`` / ``device_kind`` / ``device_count`` of the devices a
    run used (default: all of ``jax.devices()``), so a number from a CPU
    run can never be read as a chip number."""
    if devices is None:
        import jax

        devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
