"""The program's own ``ODTP_OBS`` spans, read from outside it."""

from __future__ import annotations


def spans() -> list:
    """``[name, start, end]`` of every completed span the program's tracer
    holds, on the ``time.perf_counter`` clock; empty when it is not armed
    (``run.py`` arms it in traced runs only)."""
    from opendiloco_tpu import obs

    tracer = obs.tracer()
    if tracer is None:
        return []
    out = []
    for ev in list(tracer.events):
        if ev.get("ph") == "X":
            start = tracer.origin + ev["ts"] / 1e6
            out.append([ev["name"], start, start + ev["dur"] / 1e6])
    return out
