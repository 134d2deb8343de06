"""The program's own spans, read from outside it, and the program's capture
control (``opendiloco_tpu.obs.capture``) as a ``--trace 2`` run uses it."""

from __future__ import annotations

import shutil
import threading
import time

WINDOW = "bench/window"
# request traces the ring keeps during a traced stretch: more than any
# stretch completes
RING_CAP = 100000


def spans(capture=None) -> list:
    """``[name, start, end]`` of every completed span, on the
    ``time.perf_counter`` clock: those of ``capture`` (what the program's
    ``obs.capture.stop()`` returned), else those the program's tracer holds;
    empty when that is not armed (``run.py`` arms it in ``--trace 1`` runs)."""
    from opendiloco_tpu import obs

    if capture is not None:
        found = capture.spans
    else:
        tracer = obs.tracer()
        found = [] if tracer is None else tracer.spans_since(0)
    return [[s["name"], s["t0"], s["t1"]] for s in found]


def span_args(capture, name: str, t0: float, t1: float) -> list:
    """The attributes of ``capture``'s spans called ``name`` that started in
    ``[t0, t1)`` (``perf_counter``)."""
    return [s["args"] for s in capture.spans if s["name"] == name and t0 <= s["t0"] < t1]


def seconds_by_name(capture, prefix: str) -> dict:
    """Summed duration of ``capture``'s spans per name, those whose name
    starts with ``prefix``: what each piece of the traced stretch took as
    the program itself timed it."""
    total: dict = {}
    for s in capture.spans:
        if s["name"].startswith(prefix):
            total[s["name"]] = total.get(s["name"], 0.0) + s["t1"] - s["t0"]
    return total


def _first_start(trace_dir: str) -> None:
    """The profiler's first start in a process costs seconds: spend them on
    a capture that is thrown away."""
    from opendiloco_tpu import obs

    shutil.rmtree(trace_dir, ignore_errors=True)
    obs.capture.start(trace_dir)
    obs.capture.stop()
    shutil.rmtree(trace_dir, ignore_errors=True)


class Stretch:
    """The traced stretch of a ``--trace 2`` run: the program's capture
    control started once and thrown away (so that the first start falls into
    no number; ``meanwhile`` is called over and over while that lasts, for a
    serving cell to keep its clients sending), then started into an emptied
    ``trace_dir`` under the ``bench/window`` annotation that carries the
    ``perf_counter`` reading, for as long as the ``with`` block lasts::

        with program_obs.Stretch(trace_dir, compiles) as stretch:
            ...                  # the same traffic as the window's
        stretch.capture          # what the program recorded
        stretch.compiles         # programs handed to the compiler meanwhile
        stretch.cost             # seconds of the first start, the stop, the reduce
    """

    def __init__(self, trace_dir: str, compiles, meanwhile=None):
        self.trace_dir, self._compiles, self._meanwhile = trace_dir, compiles, meanwhile
        self.capture = None
        self.t0 = self.t1 = 0.0
        self.compiles = 0
        self.cost: dict = {}

    def __enter__(self) -> "Stretch":
        import jax

        from opendiloco_tpu import obs

        t_enter = time.perf_counter()
        if self._meanwhile is None:
            _first_start(self.trace_dir)
        else:
            first = threading.Thread(target=_first_start, args=(self.trace_dir,))
            first.start()
            while first.is_alive():
                self._meanwhile()
        self.cost["first_start_s"] = time.perf_counter() - t_enter
        self._before = self._compiles.requests
        obs.capture.start(self.trace_dir, ring_cap=RING_CAP)
        self.t0 = time.perf_counter()
        self._window = jax.profiler.TraceAnnotation(WINDOW, pc=repr(self.t0))
        self._window.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        from opendiloco_tpu import obs

        self._window.__exit__(None, None, None)
        self.t1 = time.perf_counter()
        self.capture = obs.capture.stop()
        self.cost["stop_s"] = time.perf_counter() - self.t1
        self.compiles = self._compiles.requests - self._before
        return False

    def reduce(self, rehearsal: bool) -> dict:
        """The stretch's trace reduced (``xplane.reduce``) with the
        program's spans placed on its clock; the trace is then deleted."""
        from odbench import xplane

        t = time.perf_counter()
        try:
            return xplane.reduce(self.trace_dir, spans(self.capture), rehearsal=rehearsal)
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            self.cost["reduce_s"] = time.perf_counter() - t
