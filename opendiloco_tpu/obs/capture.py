"""One switch for everything that records: ``start`` and ``stop`` the JAX
profiler, the span tracer (``obs/trace.py``) and the request-trace ring
(``obs/reqtrace.py``) together, in the running process that holds the chip.

``ODTP_OBS`` arms tracer and ring for a process's whole life; this arms the
same tracer and the same ring for a stretch of it (warm-up and the steady
state before it stay untraced), and starts the profiler with them::

    from opendiloco_tpu import obs
    obs.capture.start("/tmp/prof", ring_cap=4096)
    ...                                   # the steps or seconds to look at
    cap = obs.capture.stop()              # spans, counters, request traces
    cap.save("/tmp/prof/odtp_capture.json")
    obs.capture.last() is cap             # until the next stop, for a late reader

Where ``ODTP_OBS`` is set, the capture uses the operator's tracer and ring
and leaves them armed at ``stop``; where it is not, hook sites see a tracer
between ``start`` and ``stop`` and ``None`` before and after, exactly as if
the variable had been set and unset. Arming changes no program that runs on
the device (the tracer picks no jit variant), so nothing compiles between
``start`` and ``stop`` that would not have compiled anyway.

The anchor: every span is a pair of ``time.perf_counter`` stamps, many
recorded after the fact (``Tracer.add_span``), which a context-manager
annotation cannot do. So ``start`` writes one ``TraceAnnotation`` named
``odtp/capture`` whose ``pc`` stat is the ``perf_counter`` reading taken as
it is entered; a span at ``perf_counter`` t lies at the annotation's start
plus (t - pc) on the profiler's clock.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Optional

from opendiloco_tpu.obs import programs, reqtrace
from opendiloco_tpu.obs import trace as _trace

ANCHOR = "odtp/capture"
# the spec hook sites see while a capture has armed the plane itself
_SPEC = "capture"


@dataclasses.dataclass
class Capture:
    """What one ``start`` .. ``stop`` recorded."""

    spans: list  # [{"name", "t0", "t1", "tid", "args"}], perf_counter seconds
    counters: dict  # 'name{label=v}' -> increase between start and stop
    requests: list  # completed request traces begun inside the capture
    anchor_pc: float  # perf_counter at the anchor annotation's start
    t_stop: float
    dropped: int  # spans the tracer's capped buffer refused meanwhile

    def save(self, path: str) -> str:
        """Spans, counters and the anchor as JSON, to lie beside a trace."""
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(_trace._jsonable(dataclasses.asdict(self)), f)
            f.write("\n")
        return path


@dataclasses.dataclass
class _Open:
    own: bool  # this capture armed the plane (ODTP_OBS was unset)
    tracer: _trace.Tracer
    ring: reqtrace.RequestTraceRing
    ring_cap_before: int
    first_event: int
    dropped_before: int
    counters_before: dict
    anchor_pc: float
    profile_dir: Optional[str]


_lock = threading.Lock()
_open: Optional[_Open] = None
_last: Optional[Capture] = None


def start(profile_dir: Optional[str] = None, *, ring_cap: Optional[int] = None) -> None:
    """Arm tracer and ring (the request ring bounded by ``ring_cap``) and,
    with ``profile_dir``, start the JAX profiler into it: device operations
    and host annotations, Python call tracing off (it slows the host)."""
    global _open
    with _lock:
        if _open is not None:
            raise RuntimeError("a capture is already open in this process")
        own = not os.environ.get(_trace._ENV)
        if own:
            _trace._forced = _SPEC
        try:
            tr, rg = _trace.tracer(), reqtrace.ring()
            cap_before = rg.cap
            if ring_cap is not None:
                rg.cap = int(ring_cap)
            if profile_dir is not None:
                import jax

                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                options.host_tracer_level = 2
                jax.profiler.start_trace(profile_dir, profiler_options=options)
                anchor_pc = time.perf_counter()
                with jax.profiler.TraceAnnotation(ANCHOR, pc=repr(anchor_pc)):
                    pass
            else:
                anchor_pc = time.perf_counter()
        except BaseException:
            if own:
                _disarm()
            raise
        _open = _Open(
            own=own, tracer=tr, ring=rg, ring_cap_before=cap_before,
            first_event=len(tr.events), dropped_before=tr.dropped,
            counters_before=tr.counters(), anchor_pc=anchor_pc,
            profile_dir=profile_dir,
        )


def stop() -> Capture:
    """Stop what ``start`` started and hand over what was recorded. A
    plane this capture armed is disarmed (hook sites see ``None`` again);
    one that ``ODTP_OBS`` armed stays as it was."""
    global _open, _last
    with _lock:
        if _open is None:
            raise RuntimeError("no capture is open in this process")
        o, _open = _open, None
        # recording ends here: writing the profiler's trace can take seconds,
        # and what the process does meanwhile is not part of the capture
        out = _last = _recorded(o)
        # what the live owners of compiled programs would lower, for a reader
        # of this capture's trace that comes after they are gone
        programs.keep()
        o.ring.cap = o.ring_cap_before
        if o.own:
            _disarm()
        if o.profile_dir is not None:
            import jax

            jax.profiler.stop_trace()
    return out


def _recorded(o: _Open) -> Capture:
    tr, before = o.tracer, o.counters_before
    grown = {k: v - before.get(k, 0.0) for k, v in tr.counters().items()}
    return Capture(
        spans=tr.spans_since(o.first_event),
        counters=_trace._flat_metrics({k: v for k, v in grown.items() if v}),
        requests=[t for t in o.ring.traces() if t["t0"] >= o.anchor_pc],
        anchor_pc=o.anchor_pc,
        t_stop=time.perf_counter(),
        dropped=tr.dropped - o.dropped_before,
    )


def _disarm() -> None:
    _trace._forced = None
    # the accessors notice the change of spec and drop their singletons
    _trace.tracer()
    reqtrace.ring()


def last() -> Optional[Capture]:
    """What the newest ``stop`` returned, the object itself: for a reader
    that runs after whoever stopped the capture has let go of it. None
    before any, and after ``obs.reset()``."""
    return _last


def abandon() -> None:
    """Close an open capture and throw away what it recorded, and the last
    one kept (tests)."""
    global _last
    if _open is not None:
        try:
            stop()
        except Exception:
            pass
    _last = None
    programs.forget_kept()
