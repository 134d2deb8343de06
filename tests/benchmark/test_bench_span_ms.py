"""The four per-layer metrics that read the program's own spans of the traced
stretch (``readers/span_ms.py`` over ``opendiloco_tpu.obs.capture.last()``):
their files against the manifest, their values on a capture made by hand, and
a ``--trace 2`` rehearsal of a cell that lists them."""

import json
import os
import subprocess
import sys

import pytest

from odbench import manifest
from opendiloco_tpu import obs as program

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
METRICS = ("decode_submit_ms", "prefill_submit_ms", "decode_fetch_ms", "loop_overhead_ms")
NEW_SPANS = {"serve_args", "serve_dispatch", "serve_fetch", "serve_batch", "serve_emit"}
CELLS = ["serve-360m-batch", "serve-glm-flash-agent"]


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(REPO, BENCH)


@pytest.fixture(autouse=True)
def _no_capture_kept():
    program.reset()
    yield
    program.reset()


@pytest.mark.parametrize("name", METRICS)
def test_metric_file_finds_the_reader_and_agrees_with_the_manifest(man, name):
    spec = man.metric_file(name)
    assert spec["reader"] == "span_ms" and "workloads" not in spec
    entry = next(m for m in man.raw["per_layer"] if m["name"] == name)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert entry["source"] == "program_span" and entry["layer"] == "serving engine"
    assert entry["unit"] == "ms" and entry["better"] == "lower"
    assert entry["moves"] == "tpot_p95_ms" and entry["workloads"] == CELLS
    # exactly the keys the entries before it have
    assert set(entry) == set(man.raw["per_layer"][0])
    read, params = man.reader(name)
    assert callable(read) and params["parent"].startswith("serve_")
    assert manifest.problems(man) == []


def _span(name, t0, t1, **args):
    return {"name": name, "t0": t0, "t1": t1, "tid": 1, "args": args}


def _call(parent, stage, t0, args, dispatch, fetch, rest, **attrs):
    """One engine call from ``t0``: its three phases and what is left."""
    a, d, f = t0 + args, t0 + args + dispatch, t0 + args + dispatch + fetch
    return [
        _span("serve_args", t0, a, stage=stage),
        _span("serve_dispatch", a, d, stage=stage),
        _span("serve_fetch", d, f, stage=stage),
        _span(parent, t0, f + rest, **attrs),
    ]


def _by_hand():
    """Two iterations inside the capture (an admission and a step; a step
    alone), and around them what must not count: an iteration that began
    before the capture, and a step whose iteration never closed."""
    spans = [
        # began before the anchor (100.0): not a parent, nor are its calls
        _span("serve_iteration", 99.990, 100.020, admitted=True, stepped=True),
        *_call("serve_prefill", "prefill", 99.991, 0.001, 0.001, 0.001, 0.0005, tokens=5),
        *_call("serve_decode", "decode", 99.995, 0.001, 0.001, 0.010, 0.001, rows=9, slots=3),
        # iteration 1, 40 ms: a prefill of 8 ms and a step of 20 ms inside
        *_call("serve_prefill", "prefill", 100.101, 0.002, 0.001, 0.004, 0.001, tokens=7),
        _span("serve_batch", 100.110, 100.112),
        *_call("serve_decode", "decode", 100.112, 0.001, 0.002, 0.016, 0.001, rows=9, slots=3),
        _span("serve_emit", 100.132, 100.139),
        _span("serve_iteration", 100.100, 100.140, admitted=True, stepped=True),
        # iteration 2, 30 ms: a step of 24 ms inside
        *_call("serve_decode", "decode", 100.202, 0.003, 0.002, 0.018, 0.001, rows=12, slots=3),
        _span("serve_iteration", 100.200, 100.230, admitted=False, stepped=True),
        # in flight at the stop: the step is recorded, its iteration is not
        *_call("serve_decode", "decode", 100.300, 0.001, 0.001, 0.010, 0.0, rows=15, slots=3),
    ]
    return program.capture.Capture(
        spans=spans, counters={}, requests=[], anchor_pc=100.0, t_stop=100.4, dropped=0
    )


class _Lines:
    def __init__(self):
        self.lines = []

    def line(self, what, **facts):
        self.lines.append({"what": what, **facts})


@pytest.mark.parametrize("name, want_ms", [
    # steps that began inside the capture: three; (1+2) + (3+2) + (1+1) ms
    ("decode_submit_ms", 10.0 / 3),
    ("decode_fetch_ms", (16.0 + 18.0 + 10.0) / 3),
    # the one prefill: 2 + 1
    ("prefill_submit_ms", 3.0),
    # two iterations: (40 - 8 - 20) and (30 - 24); the step outside both counts nowhere
    ("loop_overhead_ms", (12.0 + 6.0) / 2),
])
def test_each_metric_reads_the_value_worked_out_by_hand(man, monkeypatch, name, want_ms):
    read, params = man.reader(name)
    monkeypatch.setattr(program.capture, "_last", _by_hand())
    report = _Lines()
    got = read({"counters": {}, "trace": {"busy_s": 1.0}, "report": report}, params)
    assert got == pytest.approx(want_ms, rel=1e-9)
    (line,) = report.lines  # one line, with what was divided
    assert line["what"] == "span_ms" and line["parent"] == params["parent"]
    assert line["parents"] == {"serve_decode": 3, "serve_prefill": 1, "serve_iteration": 2}[
        params["parent"]
    ]
    assert line["outside_spans"] > 0  # the spans around the capture's edges
    assert list(line["inside_s_by_name"]) == params["inside"]
    assert sum(line["inside_s_by_name"].values()) == pytest.approx(line["inside_s"])
    value = line["parent_s"] - line["inside_s"] if line["rest"] else line["inside_s"]
    assert value / line["parents"] * 1e3 == pytest.approx(got)
    # with no report to print through, the same value
    assert read({"counters": {}, "trace": {}}, params) == pytest.approx(got)


@pytest.mark.parametrize("name", METRICS)
def test_nothing_to_read_reads_none(man, monkeypatch, name):
    read, params = man.reader(name)
    empty = {"counters": {}, "cell": man.cell(CELLS[0]), "peak": None}
    assert program.capture.last() is None
    assert read(empty, params) is None  # no trace, no capture
    assert read({**empty, "trace": {}}, params) is None  # a trace, and no capture kept
    monkeypatch.setattr(program.capture, "_last", _by_hand())
    assert read(empty, params) is None  # a capture, and the run traced nothing
    # a capture of a program without the spans (the parent's): nothing, not 0
    bare = _by_hand()
    bare.spans = [s for s in bare.spans if s["name"] not in NEW_SPANS]
    monkeypatch.setattr(program.capture, "_last", bare)
    if name != "loop_overhead_ms":  # whose spans the parent has
        assert read({**empty, "trace": {}}, params) is None
    # a program without ``capture.last`` at all
    monkeypatch.delattr(program.capture, "last")
    assert read({**empty, "trace": {}}, params) is None


def test_trace_2_rehearsal_reports_the_four_and_the_five_spans(man):
    cell = CELLS[0]
    env = {k: v for k, v in os.environ.items() if k not in ("ODTP_OBS", "ODTP_REQTRACE_CAP")}
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell, "--seed",
         "2147483659", "--rehearse", "--seconds", "6", "--trace", "2"],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env={**env, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines() if x.startswith("{")]
    by_what = {x.get("what", "result"): x for x in lines}
    out = by_what["rehearsal"]
    assert out["correct"] is True and "fault" not in by_what
    assert by_what["window"]["compiles_in_window"] == 0
    traced = by_what["traced"]
    assert traced["compiles_in_trace"] == 0 and traced["spans_dropped"] == 0
    assert NEW_SPANS <= set(traced["span_seconds"])
    assert set(METRICS) <= set(out["metrics"])
    value = {name: out["metrics"][name]["value"] for name in METRICS}
    assert all(v > 0 for v in value.values())
    # the two halves of a step lie inside the step the same stretch timed
    assert value["decode_submit_ms"] + value["decode_fetch_ms"] <= traced["decode_step_ms"]
    assert sum(1 for x in lines if x.get("what") == "span_ms") == len(METRICS)
    labels = {name for name, _ in out["breakdown"]["idle_gaps"]}
    assert labels & {"program/" + s for s in NEW_SPANS} and len(labels) <= 10
