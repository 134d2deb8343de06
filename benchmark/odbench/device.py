"""The device a run is on, its compile cache, and what compiles when."""

from __future__ import annotations

import json
import os
import sys

from odbench import peaks

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class DeviceError(RuntimeError):
    """No accelerator, too few chips, or a chip with no peaks on record."""


def require(chips: int, allow_cpu: bool = False):
    """-> (devices used, facts for every line, the chip's peaks). Raises where
    JAX finds no accelerator or fewer chips than the cell asks for. ``allow_cpu`` is the rehearsal's
    switch (``--rehearse``): a run under it never prints a result line."""
    import jax

    found = jax.devices()
    platform = found[0].platform
    if platform == "cpu" and not allow_cpu:
        raise DeviceError(
            "JAX found no accelerator (platform 'cpu'): the benchmark has no "
            "CPU mode; rehearse with --rehearse"
        )
    if len(found) < chips:
        raise DeviceError(f"the cell asks for {chips} chips, JAX found {len(found)}")
    used = found[:chips]
    kind = used[0].device_kind
    facts = {"platform": platform, "kind": kind, "count": len(used)}
    peak = None if platform == "cpu" else peaks.peak(kind)
    return used, facts, peak


def enable_compile_cache(root: str) -> str:
    """JAX's persistent cache at a fixed path inside the checkout (the path
    is part of the key), unless ``JAX_COMPILATION_CACHE_DIR`` names one; and
    every program cached, however quick its compile."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


class CompileCounter:
    """Programs handed to the backend's compiler (a persistent-cache hit
    included: a new program inside the window is a fault either way), and the
    persistent cache's hits and misses, from JAX's own monitoring."""

    def __init__(self):
        import jax

        self.requests = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.requests += 1

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {
            "compile_requests": self.requests,
            "cache_hits": self.hits,
            "cache_misses": self.misses,
        }


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 where the backend
    reports none, as the CPU does)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Reporter:
    """Lines of one JSON object each; every line carries the device."""

    def __init__(self, device_facts: dict, cell: str, seed: int):
        self.device = device_facts
        self.base = {"cell": cell, "seed": seed}

    def line(self, what: str, **facts) -> None:
        print(
            json.dumps({"what": what, **self.base, **facts, "device": self.device}),
            flush=True,
        )

    def result(self, payload: dict) -> None:
        sys.stdout.flush()
        print(json.dumps(payload), flush=True)
