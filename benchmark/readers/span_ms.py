"""Mean milliseconds a parent span of the program's own spans in the traced
stretch: what ``opendiloco_tpu.obs.capture.last()`` kept, the capture the
driver's ``program_obs.Stretch`` stopped. ``params``: ``parent``, the span
the mean is over (those that started inside the capture); ``inside``, the
names of the spans summed, each counted where it lies inside a parent and
nowhere else; ``stage``, the value of that attribute the summed spans must
carry (left out: any); ``rest`` true for the parent's own remainder, its
length less the summed spans, in place of their sum. Prints one line with the
sums and counts it divided. Nothing where the run traced nothing, the program
keeps no capture (``last`` came with the spans: a program without it has
neither), the capture holds no parent, or no summed span lies in one."""

import bisect

# a span's stamps come back from microseconds after the tracer's origin
EPS_S = 1e-6


def _last_capture():
    from opendiloco_tpu import obs as program

    last = getattr(program.capture, "last", None)
    return None if last is None else last()


def read(obs, params):
    if obs.get("trace") is None:
        return None
    capture = _last_capture()
    if capture is None:
        return None
    parents = sorted(
        (s["t0"], s["t1"]) for s in capture.spans
        if s["name"] == params["parent"] and s["t0"] >= capture.anchor_pc
    )
    starts = [t0 for t0, _ in parents]
    stage = params.get("stage")
    by_name = {name: 0.0 for name in params["inside"]}
    inside, outside = 0, 0
    for s in capture.spans:
        if s["name"] not in by_name:
            continue
        if stage is not None and s["args"].get("stage") != stage:
            continue
        at = bisect.bisect_right(starts, s["t0"] + EPS_S) - 1
        if at >= 0 and s["t1"] <= parents[at][1] + EPS_S:
            by_name[s["name"]] += s["t1"] - s["t0"]
            inside += 1
        else:
            outside += 1
    parent_s, inside_s = sum(t1 - t0 for t0, t1 in parents), sum(by_name.values())
    if obs.get("report") is not None:
        obs["report"].line(
            "span_ms", parent=params["parent"], stage=stage, rest=bool(params.get("rest")),
            parents=len(parents), parent_s=parent_s, inside_spans=inside, inside_s=inside_s,
            inside_s_by_name=by_name, outside_spans=outside,
        )
    if not parents or not inside:
        return None
    total = parent_s - inside_s if params.get("rest") else inside_s
    return total / len(parents) * 1e3
