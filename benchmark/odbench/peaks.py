"""Published peaks of one chip, keyed by ``jax.Device.device_kind``.

Copied from ``opendiloco_tpu/obs/mfu.py:PEAK_BF16_FLOPS`` (PR 21) so that no
later PR to the program can move the yardstick, with the memory side added.
Source: Google Cloud TPU documentation, system-architecture pages "TPU v4",
"TPU v5e", "TPU v5p", "TPU v6e" (bf16 TFLOP/s, HBM GB/s, HBM capacity per
chip). Only the v5e ("TPU v5 lite") has ever run this code. A device that
is not in the table is an error, never a default: a share of the wrong peak
is a wrong number that looks right.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    bf16_flops: float  # FLOP/s
    hbm_bytes_per_s: float
    hbm_bytes: float


PEAKS = {
    "TPU v4": Peak(275e12, 1228e9, 32e9),
    "TPU v5 lite": Peak(197e12, 819e9, 16e9),
    "TPU v5e": Peak(197e12, 819e9, 16e9),
    "TPU v5": Peak(459e12, 2765e9, 95e9),
    "TPU v5p": Peak(459e12, 2765e9, 95e9),
    "TPU v6 lite": Peak(918e12, 1640e9, 32e9),
    "TPU v6e": Peak(918e12, 1640e9, 32e9),
}


def peak(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks on record for device kind {device_kind!r}; add it to "
            "benchmark/odbench/peaks.py with its source"
        ) from None
