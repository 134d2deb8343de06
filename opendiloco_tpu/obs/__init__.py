"""Unified tracing + metrics plane (spans, counters, gauges, exporters).

Hook-site idiom, mirroring ``chaos.plane()``::

    from opendiloco_tpu import obs
    tr = obs.tracer()          # None when ODTP_OBS is unset (zero-cost)
    if tr is not None:
        t0 = tr.now()
        ...
        tr.add_span("outer/encode", t0, tr.now(), round=key, worker=r)

or, in plain synchronous code::

    with obs.span("outer/rendezvous", round=key):
        ...

See ``obs/trace.py`` for the env knobs, ``obs/export.py`` for the
Chrome-trace / Prometheus / JSONL exporters, and ``obs/capture.py`` for the
one switch that starts and stops profiler, tracer and request ring in a
running process (``obs.capture.start(dir)`` .. ``obs.capture.stop()``), and
``obs/programs.py`` for what a trace's device events are: each compiled
program's instructions by scope, pass and opcode (``obs.programs.tables()``).
"""
from opendiloco_tpu.obs.trace import (  # noqa: F401
    StageTimes,
    Tracer,
    count,
    enabled,
    gauge,
    span,
    tracer,
)
from opendiloco_tpu.obs import (  # noqa: F401
    anomaly,
    blackbox,
    capture,
    export,
    mfu,
    overseer,
    programs,
    reqtrace,
)
from opendiloco_tpu.obs import trace as _trace


def reset() -> None:
    """Drop every cached obs singleton (tests / env changes): tracer,
    flight recorder, request-trace ring, overseer, and watchdogs. An open
    capture is abandoned (its profiler session, if any, is stopped). The
    owners of compiled programs stay registered (``programs.reset`` forgets
    them): they outlive a tracer."""
    capture.abandon()
    anomaly.reset()
    blackbox.reset()
    reqtrace.reset()
    overseer.reset()
    _trace.reset()


__all__ = [
    "StageTimes",
    "Tracer",
    "anomaly",
    "blackbox",
    "capture",
    "count",
    "enabled",
    "export",
    "gauge",
    "mfu",
    "overseer",
    "programs",
    "reqtrace",
    "reset",
    "span",
    "tracer",
]
