"""Achieved-MFU estimation from the banked roofline numbers.

``AOT_ROOFLINE.json`` (repo root) carries, per model size, XLA's
executed-flops cost analysis (``multichip_rows[*].
executed_flops_per_device`` / ``tokens_per_step``). When a row matches
the configured model we use those flops/token; otherwise we fall back to
the standard ``6 * n_params`` analytic estimate. The device peak comes
from :data:`PEAK_BF16_FLOPS`, the one peaks table (bench.py and the
measuring scripts read it too). Everything is computed once at startup —
the per-step cost of the MFU gauge is one multiply.
"""
from __future__ import annotations

import json
import os
from typing import Optional

# bf16 peak FLOP/s of ONE chip, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud TPU documentation, the "TPU v4" / "TPU v5e" /
# "TPU v5p" / "TPU v6e" system-architecture pages (275 / 197 / 459 / 918
# TFLOP/s). Only v5e has ever run this code.
PEAK_BF16_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops(device_kind: str) -> float:
    """Per-chip bf16 peak of ``device_kind``. A device that is not in the
    table is an error, never a default: a utilization against the wrong
    peak is a wrong number that looks right."""
    try:
        return PEAK_BF16_FLOPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no bf16 peak on record for device kind {device_kind!r}; add "
            "it to opendiloco_tpu.obs.mfu.PEAK_BF16_FLOPS with its source"
        ) from None


def roofline_path() -> Optional[str]:
    override = os.environ.get("ODTP_ROOFLINE")
    if override:
        return override if os.path.exists(override) else None
    here = os.path.dirname(os.path.abspath(__file__))
    for _ in range(4):
        here = os.path.dirname(here)
        cand = os.path.join(here, "AOT_ROOFLINE.json")
        if os.path.exists(cand):
            return cand
    return None


def _model_key(path_model: str) -> str:
    base = os.path.basename(str(path_model).rstrip("/")).lower()
    if base.endswith(".json"):
        base = base[: -len(".json")]
    if base.startswith("config_"):
        base = base[len("config_"):]
    return base


def flops_per_token(
    path_model: str, n_params: Optional[int] = None
) -> "tuple[Optional[float], str]":
    """-> (total model flops per token or None, source)."""
    path = roofline_path()
    rows: list[dict] = []
    if path is not None:
        try:
            with open(path) as f:
                rows = json.load(f).get("multichip_rows") or []
        except (OSError, ValueError):
            rows = []
    key = _model_key(path_model)
    best: Optional[dict] = None
    for row in rows:
        if row.get("model") != key:
            continue
        if not row.get("executed_flops_per_device"):
            continue
        if not row.get("tokens_per_step"):
            continue
        # prefer the largest-scale measurement of this model
        if best is None or row.get("chips", 0) > best.get("chips", 0):
            best = row
    if best is not None:
        per_token = (
            float(best["executed_flops_per_device"])
            * float(best.get("chips", 1))
            / float(best["tokens_per_step"])
        )
        return per_token, "roofline"
    if n_params:
        return 6.0 * float(n_params), "analytic_6n"
    return None, "unavailable"


def mfu(
    tokens_per_second: float,
    model_flops_per_token: float,
    n_devices: int,
    peak_flops_per_device: float,
) -> float:
    """Model FLOPs utilization in [0, ~1] across ``n_devices`` chips."""
    achieved = model_flops_per_token * tokens_per_second
    return achieved / (peak_flops_per_device * max(1, n_devices))
