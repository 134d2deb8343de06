"""The nine per-layer metrics that read the device time of what the program
itself names (``readers/scope_ms.py`` over ``opendiloco_tpu.obs.programs``):
their files against the manifest, and the reader over a trace and a table made
by hand: each form of ``params``, the division by the capture's spans, pairs
that several programs hold, and nothing (never an exception) for every piece
that can be missing."""

import pytest

from odbench import manifest
from opendiloco_tpu import obs as program
from opendiloco_tpu.obs.programs import Instruction

import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
TRAIN = ["train-360m-h16", "train-1.7b-fsdp4-h8"]
SERVE = ["serve-360m-batch", "serve-glm-flash-agent", "serve-zaya1-reason"]
STEP, PREFILL = "train_tokens_per_s_per_chip", "tpot_p95_ms"
METRICS = {
    "step_attn_ms.train": ("inner step", STEP, TRAIN),
    "step_mlp_ms.train": ("inner step", STEP, TRAIN),
    "step_loss_ms.train": ("inner step", STEP, TRAIN),
    "step_optimizer_ms.train": ("inner step", STEP, TRAIN),
    "step_remat_ms.train": ("inner step", STEP, TRAIN),
    "step_unscoped_ms.train": ("inner step", STEP, TRAIN),
    "step_collective_ms.train": ("inner step", STEP, TRAIN[1:]),
    "prefill_device_ms.serve": ("serving engine", PREFILL, SERVE),
    "prefill_attn_device_ms.serve": ("serving engine", PREFILL, SERVE),
}


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(REPO, BENCH)


@pytest.fixture(autouse=True)
def _nothing_kept():
    program.reset()
    program.programs.reset()
    yield
    program.reset()
    program.programs.reset()


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_file_finds_the_reader_and_agrees_with_the_manifest(man, name):
    layer, moves, cells = METRICS[name]
    spec = man.metric_file(name)
    assert spec["reader"] == "scope_ms" and "workloads" not in spec
    assert set(spec) == {"name", "unit", "better", "source", "layer", "moves", "reader", "params"}
    entry = next(m for m in man.raw["per_layer"] if m["name"] == name)
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert (entry["unit"], entry["better"], entry["source"]) == ("ms", "lower", "device_trace")
    assert (entry["layer"], entry["moves"], entry["workloads"]) == (layer, moves, cells)
    # a layer the manifest had already
    assert layer in {m["layer"] for m in man.raw["per_layer"] if m["name"] not in METRICS}
    read, params = man.reader(name)
    assert callable(read)
    assert params["per"] == ("inner/dispatch" if moves == STEP else "serve_prefill")
    assert params["program"] == ("train_step" if moves == STEP else "prefill/")
    assert len({"scope", "pass", "opcodes", "unscoped"} & set(params)) <= 1
    assert manifest.problems(man) == []


def test_the_nine_come_last_and_nothing_else_of_the_manifest_moved(man):
    names = [m["name"] for m in man.raw["per_layer"]]
    assert names[-9:] == [
        "step_attn_ms.train", "step_mlp_ms.train", "step_loss_ms.train",
        "step_optimizer_ms.train", "step_remat_ms.train", "step_unscoped_ms.train",
        "step_collective_ms.train", "prefill_device_ms.serve", "prefill_attn_device_ms.serve"]
    assert len(set(names)) == len(names)


# ---------------------------------------------------------------------------
# the reader, over a trace and a table made by hand
# ---------------------------------------------------------------------------


def _ins(name, shape, opcode="fusion", path="", pass_="fwd"):
    return Instruction(name, shape, opcode, path.split("/")[-1] or None, path, pass_)


def _event(name, shape, start_us, dur_us, opcode="fusion"):
    """A device event as ``xplane.extract`` keeps it."""
    return [f"{name} {opcode}", start_us * 1e3, dur_us * 1e3, f"{shape}{{1,0}} {opcode}(%x), kind=kLoop"]


TABLES = {
    "train_step": [
        _ins("%while.1", "s32[]", "while"),
        _ins("%fusion.1", "bf16[8,64]", path="odtp_attention"),
        _ins("%odtp_flash_fwd.2", "bf16[8,64]", "tpu_custom_call", "odtp_attention/odtp_flash_fwd"),
        _ins("%fusion.3", "bf16[8,64]", path="odtp_attention", pass_="remat"),
        _ins("%fusion.4", "bf16[8,128]", path="odtp_mlp", pass_="bwd"),
        _ins("%fusion.5", "bf16[8,128]", path="odtp_mlp/odtp_router", pass_="remat"),
        _ins("%fusion.6", "f32[8,256]", path="odtp_lm_head_loss"),
        _ins("%fusion.7", "f32[64,64]", path="odtp_optimizer_update"),
        _ins("%fusion.8", "f32[8,64]"),  # under no scope
        _ins("%fusion.9", "bf16[16,64]", "all-reduce-scatter", "odtp_mlp", "bwd"),
        _ins("%all-gather-start.1", "bf16[4,64]", "all-gather-start"),
        _ins("%all-gather-done.1", "bf16[16,64]", "all-gather-done"),
        _ins("%fusion.20", "f32[64,64]", path="odtp_optimizer_update"),  # and outer/apply's
    ],
    "outer/apply": [_ins("%fusion.20", "f32[64,64]"), _ins("%fusion.21", "f32[64,64]")],
    "prefill/16": [
        _ins("%fusion.30", "bf16[16,64]", path="odtp_serve_prefill/odtp_attention"),
        _ins("%fusion.31", "bf16[16,64]", path="odtp_serve_prefill/odtp_mlp"),
        _ins("%fusion.32", "bf16[4,64]", path="odtp_serve_prefill"),  # and decode's
    ],
    "prefill/32": [
        _ins("%fusion.30", "bf16[32,64]", path="odtp_serve_prefill/odtp_mla"),
        _ins("%fusion.33", "bf16[32,64]", path="odtp_serve_prefill/odtp_mlp"),
    ],
    "decode": [_ins("%fusion.32", "bf16[4,64]", path="odtp_serve_decode")],
}


def _device(scale=1):
    """Two steps of the train program (a ``while`` of 100 us that holds the
    layer's operations), a boundary, two prefills and a decode step. Self
    microseconds, a step: attention 10 + 20 (kernel) + 5 (remat) = 35; mlp 8
    + 4 = 12; loss 6; optimizer 7; unscoped 3 + the while's own 100 - 70 = 33;
    collectives 9 + 2 + 6 = 17; the pair the boundary holds too: 11."""
    ops = []
    for step in (0, 1):
        t = step * 1000
        ops.append(_event("%while.1", "s32[]", t, 100 * scale, "while"))
        inside = [
            ("%fusion.1", "bf16[8,64]", 10), ("%odtp_flash_fwd.2", "bf16[8,64]", 20),
            ("%fusion.3", "bf16[8,64]", 5), ("%fusion.4", "bf16[8,128]", 8),
            ("%fusion.5", "bf16[8,128]", 4), ("%fusion.9", "bf16[16,64]", 9),
            ("%all-gather-start.1", "bf16[4,64]", 2), ("%all-gather-done.1", "bf16[16,64]", 6),
            ("%fusion.8", "f32[8,64]", 3), ("%unknown.1", "f32[2]", 3),
        ]
        at = t
        for name, shape, dur in inside:
            ops.append(_event(name, shape, at, dur * scale))
            at += dur * scale
        ops.append(_event("%fusion.6", "f32[8,256]", t + 200, 6 * scale))
        ops.append(_event("%fusion.7", "f32[64,64]", t + 300, 7 * scale))
        ops.append(_event("%fusion.20", "f32[64,64]", t + 400, 11 * scale))
    ops.append(_event("%fusion.21", "f32[64,64]", 2500, 40))
    for k in (0, 1):
        t = 3000 + 100 * k
        ops.append(_event("%fusion.30", "bf16[16,64]", t, 12))
        ops.append(_event("%fusion.31", "bf16[16,64]", t + 20, 18))
        ops.append(_event("%fusion.32", "bf16[4,64]", t + 40, 5))
    ops.append(_event("%fusion.30", "bf16[32,64]", 3300, 30))
    ops.append(_event("%fusion.33", "bf16[32,64]", 3340, 50))
    return ops


def _capture(steps=2, prefills=3):
    span = lambda name, t0, **args: {"name": name, "t0": t0, "t1": t0 + 0.001, "tid": 1, "args": args}
    spans = [span("inner/dispatch", 99.9, step=6, tokens=64, accum=1)]  # began before the capture
    spans += [span("inner/dispatch", 100.1 + k, step=7 + k, tokens=64, accum=1) for k in range(steps)]
    spans += [span("serve_prefill", 103.0 + k, tokens=9) for k in range(prefills)]
    return program.capture.Capture(
        spans=spans, counters={}, requests=[], anchor_pc=100.0, t_stop=110.0, dropped=0)


class _Lines:
    def __init__(self):
        self.lines = []

    def line(self, what, **facts):
        self.lines.append({"what": what, **facts})


def _obs(ops=None, report=None):
    ops = {"/device:TPU:0": _device()} if ops is None else ops
    return {"counters": {}, "trace": {"ops": ops, "busy_s_per_device": [1.0]}, "report": report}


@pytest.fixture
def stubbed(monkeypatch):
    found = program.programs.Tables()
    found.update(TABLES)
    monkeypatch.setattr(program.programs, "tables", lambda: found)
    monkeypatch.setattr(program.capture, "_last", _capture())
    return found


STEP_OF = lambda **f: {"program": "train_step", **f, "per": "inner/dispatch"}
PREFILL_OF = lambda **f: {"program": "prefill/", **f, "per": "serve_prefill"}
COLLECTIVES = ["all-gather", "all-reduce", "reduce-scatter", "collective-permute", "all-to-all"]


@pytest.mark.parametrize("params, want_us", [
    # a scope anywhere along the path: the kernel's own scope lies under attention's
    (STEP_OF(scope="odtp_attention"), 35.0),
    # a scope's operations leave the collectives out: the reduce-scatter under odtp_mlp
    (STEP_OF(scope="odtp_mlp"), 12.0),
    (STEP_OF(scope="odtp_lm_head_loss"), 6.0),
    # the update's %fusion.20 is the boundary's too, under no scope: left out
    (STEP_OF(scope="odtp_optimizer_update"), 7.0),
    (STEP_OF(**{"pass": "remat"}), 9.0),  # across the scopes
    (STEP_OF(unscoped=True), 33.0),  # the while is charged its own time, not its body's
    (STEP_OF(opcodes=COLLECTIVES), 17.0),  # the fusion named for its reduce-scatter among them
    (STEP_OF(), 35.0 + 12 + 6 + 7 + 33 + 17),  # the whole program: what the six tile
    # per admission (three spans): both buckets' operations, less the pair decode holds too
    (PREFILL_OF(), (2 * (12 + 18) + 30 + 50) / 3),
    (PREFILL_OF(scope=["odtp_attention", "odtp_mla", "odtp_cca"]), (2 * 12 + 30) / 3),
    ({"program": "outer/", "per": "inner/dispatch"}, 40.0 / 2),
])
def test_each_form_of_params_reads_the_value_worked_out_by_hand(man, stubbed, params, want_us):
    read, _ = man.reader("step_attn_ms.train")
    report = _Lines()
    assert read(_obs(report=report), params) == pytest.approx(want_us / 1e3, rel=1e-9)
    whole, line = report.lines  # the join's line once, then the reading's
    assert whole["what"] == "scope_seconds" and line["what"] == "scope_ms"
    assert line["params"] == params and line["units"] == (2 if "inner" in params["per"] else 3)
    assert line["self_s_per_device"] == [pytest.approx(want_us * line["units"] / 1e6)]
    if params.get("opcodes"):  # what was summed, by opcode: [events, seconds]
        assert {k: v[0] for k, v in line["opcodes"].items()} == {
            "all-reduce-scatter": 2, "all-gather-start": 2, "all-gather-done": 2}


def test_the_whole_joins_line_tiles_the_first_devices_self_time(man, stubbed):
    read, params = man.reader("step_unscoped_ms.train")
    report = _Lines()
    obs = _obs(report=report)
    read(obs, params)
    read(obs, STEP_OF(scope="odtp_mlp"))
    whole = [l for l in report.lines if l["what"] == "scope_seconds"]
    assert len(whole) == 1  # once a run, however many readings
    (whole,) = whole
    step = whole["by_program"]["train_step"]
    assert step["self_s"] == pytest.approx(2 * 110e-6)
    assert step["unscoped_s"] == pytest.approx(2 * 33e-6)
    assert step["collective_s"] == pytest.approx(2 * 17e-6)
    assert step["by_scope_pass"]["odtp_attention/odtp_flash_fwd|fwd"] == pytest.approx(40e-6)
    assert step["by_scope_pass"]["odtp_mlp/odtp_router|remat"] == pytest.approx(8e-6)
    assert step["by_opcode"]["tpu_custom_call"] == pytest.approx(40e-6)
    assert whole["by_program"]["outer"]["self_s"] == pytest.approx(40e-6)
    assert whole["by_program"]["prefill"]["self_s"] == pytest.approx(140e-6)
    # counted under neither: what two programs hold under another scope; what no table holds
    assert whole["ambiguous"] == {"seconds": pytest.approx(32e-6), "events": 4, "pairs": 2}
    assert whole["unmatched"] == {"seconds": pytest.approx(6e-6), "events": 2, "pairs": 1}
    parts = sum(p["self_s"] for p in whole["by_program"].values())
    assert parts + whole["ambiguous"]["seconds"] + whole["unmatched"]["seconds"] == pytest.approx(
        whole["self_s"])
    assert whole["spans"] == {"inner/dispatch": 2, "serve_prefill": 3} and whole["steps"] == [7, 8]
    assert whole["naming_s"] >= 0 and whole["programs"]["train_step"] == 13
    assert whole["ambiguous_pairs_in_tables"] == 2
    # the operations with most self time, each with program, scope, pass and opcode
    assert whole["top"][0] == ["%while.1", "s32[]", pytest.approx(60e-6), "train_step", "-", "fwd", "while"]
    assert ["%fusion.33", "bf16[32,64]", pytest.approx(50e-6), "prefill",
            "odtp_serve_prefill/odtp_mlp", "fwd", "fusion"] in whole["top"]
    assert ["%fusion.20", "f32[64,64]", pytest.approx(22e-6), "ambiguous"] in whole["top"]
    assert len(whole["top"]) <= 20


def test_the_mean_is_over_the_traced_devices_and_each_is_printed(man, stubbed):
    read, params = man.reader("step_collective_ms.train")
    report = _Lines()
    ops = {"/device:TPU:0": _device(), "/device:TPU:1": _device(scale=3)}
    assert read(_obs(ops, report), params) == pytest.approx((17 + 51) / 2 / 1e3)
    line = report.lines[-1]
    assert line["ms_per_unit_per_device"] == [pytest.approx(0.017), pytest.approx(0.051)]
    assert line["events"] == 12 and line["instructions"] == 3


def test_nothing_and_no_exception_for_every_missing_piece(man, stubbed, monkeypatch):
    read, params = man.reader("step_attn_ms.train")
    cell = man.cell("train-360m-h16")
    # what test_readers_return_nothing_when_there_is_nothing_to_read hands a reader
    assert read({"counters": {}, "cell": cell, "peak": None}, params) is None
    assert read({"counters": {}, "trace": None, "report": None}, params) is None
    assert read({"counters": {}, "trace": {"busy_s": 1.0}, "report": _Lines()}, params) is None
    assert read(_obs({}), params) is None  # a trace of no device
    assert read(_obs(), params) == pytest.approx(0.035)  # and with no report: no line, a value
    # no event of the scope (a warm cache can hide an added scope): missing, never 0 ms
    report = _Lines()
    assert read(_obs(report=report), STEP_OF(scope="odtp_final_norm")) is None
    assert report.lines[-1]["instructions"] == 0 and report.lines[-1]["value"] is None
    assert read(_obs({"/device:TPU:0": _device()[-2:]}), params) is None  # instructions, no event
    # no span to divide by: the capture holds none, or there is no capture
    monkeypatch.setattr(program.capture, "_last", _capture(steps=0))
    assert read(_obs(), params) is None
    monkeypatch.setattr(program.capture, "_last", None)
    assert read(_obs(), params) is None
    monkeypatch.setattr(program.capture, "_last", _capture())
    # no program could be lowered; tables() itself raising; a program without obs.programs
    monkeypatch.setattr(program.programs, "tables", lambda: program.programs.Tables())
    report = _Lines()
    assert read(_obs(report=report), params) is None
    assert [l["what"] for l in report.lines] == ["scope_seconds"]  # says what it found: nothing
    monkeypatch.setattr(program.programs, "tables", lambda: 1 / 0)
    report = _Lines()
    assert read(_obs(report=report), params) is None
    assert "ZeroDivisionError" in report.lines[-1]["error"]
    monkeypatch.delattr(program, "programs")
    assert read(_obs(report=_Lines()), params) is None


def test_a_second_owners_program_of_the_same_name_counts_with_the_first(man, stubbed, monkeypatch):
    """Two trainers in one process run the same program: an event is either's,
    and both are ``train_step``."""
    found = program.programs.Tables()
    found.update({"train_step": TABLES["train_step"], "train_step#2": TABLES["train_step"]})
    monkeypatch.setattr(program.programs, "tables", lambda: found)
    read, params = man.reader("step_mlp_ms.train")
    assert read(_obs(), params) == pytest.approx(0.012)
