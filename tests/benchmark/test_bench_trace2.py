"""``--trace 2`` (measure first, trace afterwards, in one process), the
manifest's ``trace_in_run`` key, and the readers of the metrics that are
read from inside the program."""

import json
import os
import subprocess
import sys
import time
import types

import pytest

from odbench import manifest, program_obs

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(REPO, BENCH)


def _broken(man, change):
    broken = manifest.Manifest(REPO, BENCH)
    broken.raw = json.loads(json.dumps(man.raw))
    change(broken.raw)
    return manifest.problems(broken)


def test_manifest_takes_trace_in_run_and_no_other_new_key(man):
    assert man.raw["trace_in_run"] is True and manifest.problems(man) == []
    assert _broken(man, lambda r: r.pop("trace_in_run")) == []
    assert _broken(man, lambda r: r.update(trace_in_run=False)) == []
    assert any("keys" in p for p in _broken(man, lambda r: r.update(trace_on_tuesdays=True)))
    assert any("true or false" in p for p in _broken(man, lambda r: r.update(trace_in_run=2)))


ROWS = [
    {"outer_step_s": 9.0, "outer_d2h_s": 5.0, "outer_allreduce_s": 1.0, "outer_apply_s": 2.5},
    {"outer_step_s": 8.0, "outer_d2h_s": 4.0, "outer_allreduce_s": 1.5, "outer_apply_s": 2.0},
    {"outer_step_s": 8.5, "outer_d2h_s": 4.5, "outer_allreduce_s": 0.5, "outer_apply_s": 3.0},
]


@pytest.mark.parametrize("name, want_ms", [
    ("boundary_d2h_ms", 4500.0),
    ("boundary_allreduce_ms", 1000.0),
    ("boundary_apply_ms", 2500.0),
])
def test_boundary_parts_are_medians_of_the_optimizers_rows(man, name, want_ms):
    read, params = man.reader(name)
    assert read({"counters": {"outer_rows": ROWS}}, params) == want_ms
    assert read({"counters": {"outer_rows": []}}, params) is None
    # a parent's rows, which lack the key: nothing, and nothing raised
    assert read({"counters": {"outer_rows": [{"outer_step_s": 9.0}]}}, params) is None
    spec = next(m for m in man.raw["per_layer"] if m["name"] == name)
    assert spec["source"] == "program_counter" and spec["layer"] == "outer plane"


def test_prefill_per_admission_through_the_stage_reader(man):
    # the reader the chat cell's metric uses, under the batch cell's name
    read, params = man.reader("prefill_ms.batch")
    assert read({"counters": {"prefill_s": 3.0, "admissions": 600}}, params) == 5.0
    assert read({"counters": {}}, params) is None
    spec = next(m for m in man.raw["per_layer"] if m["name"] == "prefill_ms.batch")
    assert spec["moves"] == "tpot_p95_ms" and spec["workloads"] == ["serve-360m-batch"]


def test_stretch_keeps_the_traffic_up_while_its_first_start_is_thrown_away(tmp_path):
    from opendiloco_tpu import obs

    trace_dir, pumped = str(tmp_path / "trace"), []
    compiles = types.SimpleNamespace(requests=3)
    with program_obs.Stretch(trace_dir, compiles, lambda: pumped.append(time.sleep(0.001))) as st:
        assert os.path.isdir(trace_dir) or not os.path.exists(trace_dir)  # emptied
        with obs.span("inside", k=1):
            pass
    assert pumped  # called while the thrown-away start ran
    assert [s["name"] for s in st.capture.spans] == ["inside"]  # none of the first start's
    assert st.compiles == 0 and st.t1 > st.t0
    assert set(st.cost) == {"first_start_s", "stop_s"} and obs.tracer() is None


def test_program_obs_hands_over_spans_and_their_attributes():
    capture = types.SimpleNamespace(spans=[
        {"name": "serve_decode", "t0": 10.0, "t1": 10.1, "tid": 1, "args": {"rows": 30, "slots": 3}},
        {"name": "serve_decode", "t0": 11.0, "t1": 11.1, "tid": 1, "args": {"rows": 33, "slots": 3}},
        {"name": "serve_prefill", "t0": 10.2, "t1": 10.3, "tid": 1, "args": {"tokens": 9}},
    ])
    assert program_obs.spans(capture)[0] == ["serve_decode", 10.0, 10.1]
    assert len(program_obs.spans(capture)) == 3
    assert program_obs.span_args(capture, "serve_decode", 10.5, 12.0) == [{"rows": 33, "slots": 3}]
    assert program_obs.spans() == []  # no capture, no armed tracer: nothing


def _lines(cell, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "ODTP_OBS": "", "ODTP_REQTRACE_CAP": ""}
    env = {k: v for k, v in env.items() if v != ""}
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell, "--seed", "11",
         "--rehearse", *extra],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(x) for x in proc.stdout.splitlines() if x.startswith("{")]
    return {x.get("what", "result"): x for x in lines}, lines


@pytest.mark.parametrize("cell, seconds, per_layer", [
    ("train-360m-h16", "1",
     {"boundary_ms", "inner_step_ms", "device_idle_share.train", "boundary_d2h_ms",
      "boundary_allreduce_ms", "boundary_apply_ms"}),
    ("serve-360m-batch", "6",
     {"decode_step_ms", "device_idle_share.serve", "prefill_ms.batch"}),
])
def test_trace_2_rehearsal_reports_both_kinds_of_metric(man, cell, seconds, per_layer):
    by_what, lines = _lines(cell, "--seconds", seconds, "--trace", "2")
    assert "result" not in by_what  # a rehearsal never prints a result line
    out = by_what["rehearsal"]
    assert out["correct"] is True and "fault" not in by_what
    end_to_end = {m["name"] for m in man.end_to_end(cell)}
    assert end_to_end | per_layer <= set(out["metrics"])
    assert out["breakdown"]["device_ops"]
    assert any(name.startswith("program/") for name, _ in out["breakdown"]["idle_gaps"])
    assert by_what["window"]["compiles_in_window"] == 0
    traced = by_what["window"] if "compiles_in_trace" in by_what["window"] else by_what["traced"]
    assert traced["compiles_in_trace"] == 0
    assert by_what["start"]["trace"] == 2
    assert {"first_start_s", "stop_s", "reduce_s"} <= set(by_what["trace_cost"])
    assert not os.path.exists(os.path.join(REPO, ".bench_trace", cell))  # reduced, deleted
