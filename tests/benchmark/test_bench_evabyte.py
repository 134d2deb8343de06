"""The EvaByte cell's benchmark files (ISSUE 40): the manifest's soundness
with the cell in it, the configuration file against the catalog row, the cost
functions against hand counts, the roofline reader on a synthetic trace, the
driver's four functions, its refusal of a program without EVA attention and
its ``correct`` (the cell reports tokens per second and no tail: its window
ends fewer requests than the harness's p95 rule wants), and the cell's
rehearsal. CPU only."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from odbench import costs, costs_evabyte, manifest, peaks, stats

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
CELL = "serve-evabyte-complete"
CONFIG = "evabyte-6.5b"
# the catalog row's config (model-configs guide, EvaByte), key for key
PUBLISHED = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16, "fp32_ln": False,
    "fp32_logits": True, "fp32_skip_add": True, "hidden_act": "silu", "hidden_size": 4096,
    "init_cutoff_factor": None, "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768, "max_seq_length": 32768,
    "mixedp_attn": True, "model_type": "evabyte", "norm_add_unit_offset": True,
    "num_attention_heads": 32, "num_chunks": None, "num_hidden_layers": 32,
    "num_key_value_heads": 32, "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320, "window_size": 2048,
}
NEW_METRICS = {"eva_attn_roofline.serve", "prefill_ms.complete"}
A_LAYER = 4 * 4096**2 + 3 * 4096 * 11008 + 2 * 4096 + 2 * 32 * 128


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(REPO, BENCH)


def test_the_cell_reports_no_tail_and_why():
    """A p95 is reported with ten samples beyond it: 200 requests. The cell's
    45 s window ended 135 to 147 at the issue's traffic (PERF.md, PR 40), so it
    is on the list of tokens per second and not on the tail's."""
    assert not stats.supported(147, 95.0) and stats.samples_beyond(147, 95.0) == 7
    assert stats.supported(200, 95.0) and not stats.supported(199, 95.0)
    assert stats.highest_supported(135) == 90.0
    raw = manifest.Manifest(REPO, BENCH).raw
    e2e = {m["name"]: m for m in raw["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    assert CELL not in e2e["tpot_p95_ms"]["workloads"]
    for p in raw["per_layer"]:  # a metric that moves the tail is not the cell's
        if CELL in p.get("workloads", []):
            assert p["moves"] == "serve_tokens_per_s", p["name"]
    with open(os.path.join(BENCH, "workloads", f"{CELL}.json")) as f:
        assert "manifest" not in json.load(f)  # the entries are in BENCHMARK.json


@pytest.mark.parametrize("check_ok, failed, loop_error, want", [
    (True, 0, None, True), (False, 0, None, False), (True, 1, None, False),
    (True, 0, RuntimeError("loop"), False),
])
def test_correct_is_the_harness_rule_without_the_tail_sample(man, check_ok, failed, loop_error, want):
    """The driver's ``finish``: everything ``serve_cell.finish`` asks of a
    run but ``p95_supported``, with 140 requests in the window."""
    driver = man.driver("closed_loop_evabyte")
    lines, seen = [], {}
    loop = types.SimpleNamespace(run=lambda **kw: None)
    load, manifest.load_module = manifest.load_module, lambda path: loop
    try:
        driver.run(report=types.SimpleNamespace(line=lambda what, **kw: lines.append((what, kw))))
    finally:
        manifest.load_module = load
    snap = {"decode_s": 0.0, "prefill_s": 0.0, "decode_steps": 0, "new_tokens": 0,
            **{name: 0 for name in (*driver.COUNTERS, driver.RESIDENT)},
            **{f"decode_{phase}_s": 0.0 for phase in driver.PHASES}}
    after = {**snap, "decode_s": 30.0, "decode_steps": 1500, "decode_fetch_s": 27.0,
             "eva_local_rows_read": 3000, "eva_window_restarts": 70}
    req = types.SimpleNamespace(error=None, t_done=1.0, t_first=0.5)
    bad = types.SimpleNamespace(error="boom", t_done=None, t_first=None)
    reqs = [(0.0, req)] * (140 - failed) + [(0.0, bad)] * failed
    out = loop.serve_cell.finish(
        cell=man.cell(CELL), peak=None, engine=types.SimpleNamespace(num_slots=24),
        batcher=types.SimpleNamespace(loop_error=loop_error), before=snap, after=after,
        window_s=45.0, reqs_due=reqs, in_window=0, check_ok=check_ok, e2e={},
        tail_facts={"p95_supported": False}, trace=0, tracer=None, instrument=None,
    )
    assert out["correct"] is want and out["failed"] == failed and out["attempted"] == 140
    counters = out["observations"]["counters"]
    assert counters["eva_window_restarts"] == 70 and counters["decode_steps"] == 1500
    (what, line), = lines
    assert what == "window_counters" and line["decode_step_ms"] == pytest.approx(20.0)
    assert line["decode_fetch_ms_per_step"] == pytest.approx(18.0)
    assert line["local_rows_per_step"] == pytest.approx(2.0)


def test_manifest_is_sound_with_the_cell(man):
    """Properties, none of a moment (where the cell stands in a list is none):
    a later cell or metric joins without touching any of this."""
    assert manifest.problems(man) == []
    entry = next(w for w in man.raw["workloads"] if w["name"] == CELL)
    conf = next(c for c in man.raw["configs"] if c["name"] == entry["config"])
    assert entry["chips"] == 1 and entry["traffic"] == "complete-evabyte"
    assert conf["name"] == CONFIG and conf["reduced"] == ["num_hidden_layers"]
    assert conf["source"] == "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
    assert len(entry["why"]) <= 200 and len(conf["why"]) <= 200
    assert {m["name"] for m in man.end_to_end(CELL)} == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in man.per_layer(CELL)}
    assert per_layer == NEW_METRICS
    by_name = {p["name"]: p for p in man.raw["per_layer"]}
    assert len(by_name) == len(man.raw["per_layer"])
    for name in NEW_METRICS:
        p = by_name[name]
        assert p["workloads"] == [CELL] and p["moves"] == "serve_tokens_per_s"
        with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
            assert {k: v for k, v in json.load(f).items() if k in p} == {
                k: v for k, v in p.items() if k != "workloads"}
        assert set(p) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert by_name["eva_attn_roofline.serve"]["layer"] == by_name["paged_attn_roofline.serve"]["layer"]
    assert by_name["prefill_ms.complete"]["layer"] == by_name["prefill_ms.batch"]["layer"]
    # metrics that read what this configuration has not: the cell stays off them
    for name, p in by_name.items():
        if name.startswith(("moe_", "ssm_", "mla_", "cca_", "paged_attn_roofline")):
            assert CELL not in p["workloads"], name
    for m in [*man.raw["end_to_end"], *man.raw["per_layer"]]:
        names = m.get("workloads", [])
        assert len(set(names)) == len(names)
    assert sum(w["chips"] == 4 for w in man.raw["workloads"]) <= max(1, len(man.raw["workloads"]) // 4)
    assert len(json.dumps(man.raw)) < 64 * 1024


def test_configuration_file_holds_the_published_numbers(man):
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
        raw = json.load(f)
    for key, value in PUBLISHED.items():
        if key == "num_hidden_layers":
            assert raw[key] == 8 and raw["published"] == {key: value}
        else:
            assert raw[key] == value, key
    assert raw["reduced"] == ["num_hidden_layers"] and raw["source"].endswith("EvaByte/blob/main/config.json")
    assert "four pipeline stages" in raw["stands_for"] and len(raw["assumed"]) >= 8
    joined = " ".join(raw["assumed"])
    for what in ("softmax", "WINDOW", "RoPE", "head-major", "init_std", "fp32_ln", "bfloat16", "384"):
        assert what in joined, what
    p = raw["parameters"]
    assert p["a_layer"] == A_LAYER == 202_391_552
    assert p["published"] == 32 * A_LAYER + 320 * 4096 + 4096 * 2560 + 4096 == 6_488_330_240
    assert p["as_run"] == 8 * A_LAYER + 320 * 4096 + 4096 * 2560 + 4096 == 1_630_932_992
    assert p["as_run_bytes_bf16"] == 3_261_865_984
    assert p["cache_bytes_per_slot"] == 268_435_456 + 50_331_648 + 264_192
    cell = man.cell(CELL)
    assert cell.config["num_hidden_layers"] == 8 and cell.config["window_size"] == 2048


def test_costs_by_hand(man):
    cfg = man.cell(CELL).config
    assert costs_evabyte.layer_param_count(cfg) == A_LAYER
    assert costs_evabyte.param_count(cfg) == 1_630_932_992
    assert costs_evabyte.param_count({**cfg, "num_hidden_layers": 32}) == 6_488_330_240
    assert costs_evabyte.row_bytes(cfg) == 2 * 32 * 128 * 2 == 16_384
    assert costs_evabyte.pooled_rows(cfg, 4608) == 3 * 128
    assert costs_evabyte.pooled_rows(cfg, 4096) == 2 * 128
    held = costs_evabyte.cache_bytes_per_slot(cfg, 4608)
    assert held == {"window_ring": 2048 * 16384 * 8, "pooled_ring": 384 * 16384 * 8,
                    "pooling_stats": 8 * 32 * 258 * 4, "all": 319_031_296}
    # a slot at position 4,000 in 8 layers: 1,953 window rows and 128 pooled rows a layer
    local, pooled = 8 * (4000 % 2048 + 1), 8 * 128
    flops, nbytes = costs_evabyte.eva_decode_cost(cfg, local, pooled)
    assert nbytes == (local + pooled) * 16384
    assert flops == 4 * 128 * 32 * (local + pooled)
    peak = peaks.peak("TPU v5 lite")
    assert costs.roofline_seconds(flops, nbytes, peak)[1] == "memory"


OPS = [
    ["%odtp_paged_decode_attn.11 custom-call:tpu_custom_call", 0.0, 6e6, "(bf16[24,32,128]"],
    ["%odtp_eva_pooled_attn.11 custom-call:tpu_custom_call", 7e6, 2e6, "(bf16[24,32,128]"],
    ["%odtp_flash_fwd.8 custom-call:tpu_custom_call", 10e6, 7e6, "bf16[2,32,2048,128]"],
    ["%fusion.7 fusion", 18e6, 1e6, "bf16[24,4096]{1,0} fusion("],
]


def _obs(man, counters, ops=None, peak="TPU v5 lite"):
    lines = []
    report = types.SimpleNamespace(line=lambda what, **kw: lines.append((what, kw)))
    obs = {"counters": counters, "cell": man.cell(CELL),
           "peak": peaks.peak(peak) if peak else None, "report": report}
    if ops is not None:
        obs["trace"] = {"ops": {"/device:TPU:0": ops}, "busy_s": 1.0, "window_s": 2.0}
    return obs, lines


def test_eva_attn_roofline_reader(man):
    read, params = man.reader("eva_attn_roofline.serve")
    assert params == {"needles": ["odtp_paged_decode_attn", "odtp_eva_pooled_attn"]}
    calls = [[8 * 24 * 1500, 8 * 24 * 128], [8 * 24 * 10, 8 * 24 * 256]]
    obs, lines = _obs(man, {"traced_eva_calls": calls}, OPS)
    least = sum(
        costs.roofline_seconds(*costs_evabyte.eva_decode_cost(obs["cell"].config, a, b), obs["peak"])[0]
        for a, b in calls
    )
    want = 100.0 * least / 8e-3  # the two kernels' events: 6 ms + 2 ms, not the prefill's
    assert read(obs, params) == pytest.approx(want) and 0 < want < 100
    (what, line), = lines
    assert what == "eva_attn_roofline" and line["kernel_events"] == 2
    assert line["steps_by_bound"] == {"compute": 0, "memory": 2}
    assert line["local_rows"] == calls[0][0] + calls[1][0]
    # nothing to read: a program whose spans carry no eva rows (the parent), no such
    # event, no trace, no peak: nothing, and nothing raised
    assert read(_obs(man, {"traced_eva_calls": []}, OPS)[0], params) is None
    assert read(_obs(man, {}, OPS)[0], params) is None
    assert read(_obs(man, {"traced_eva_calls": calls}, OPS[2:])[0], params) is None
    assert read(_obs(man, {"traced_eva_calls": calls})[0], params) is None
    assert read(_obs(man, {"traced_eva_calls": calls}, OPS, peak=None)[0], params) is None


def test_the_data_only_metric(man):
    read, params = man.reader("prefill_ms.complete")
    assert params == {"seconds": "prefill_s", "count": "admissions"}
    obs, _ = _obs(man, {"prefill_s": 2.5, "admissions": 20})
    assert read(obs, params) == pytest.approx(125.0)
    assert read(_obs(man, {"prefill_s": 0.0, "admissions": 0})[0], params) is None


def test_driver_replaces_four_functions_and_refuses_a_program_without_eva(man):
    from odbench import traffic

    cell = man.cell(CELL)
    assert set(cell.traffic) == {"kind", "prompt_tokens", "output_tokens"}
    driver = man.driver("closed_loop_evabyte")
    seen = {}
    loop = types.SimpleNamespace(run=lambda **kw: seen.update(kw) or "ran")
    load, manifest.load_module = manifest.load_module, lambda path: loop
    try:
        assert driver.run(report=None, cell=cell) == "ran"
    finally:
        manifest.load_module = load
    assert loop.serve_cell.start is driver.start and loop.serve_cell.snapshot is driver.snapshot
    assert loop.serve_cell.traced_stretch is driver.traced_stretch
    assert loop.serve_cell.warm_up is driver.serve_cell.warm_up  # the rest is shared
    # the traffic: prompts uniform 3,584-4,096 in the one bucket, outputs constant, and
    # every request inside the context the pooled ring is sized for
    engine = cell.options["engine"]
    assert engine == {"num_slots": 24, "max_context": 4608, "prefill_buckets": [4096]}
    reqs = traffic.requests(cell.traffic, 8192, cell.config["vocab_size"], 2147483659)
    lens = np.array([len(a.prompt) for a in reqs])
    outs = {a.max_new_tokens for a in reqs}
    assert lens.min() == 3584 and lens.max() == 4096 and outs == {256}  # ISSUE 40's traffic
    assert lens.max() + max(outs) <= engine["max_context"]
    assert max(max(a.prompt) for a in reqs[:64]) < 320
    check = cell.options["check"]
    assert check["prompt_tokens"] == [4090, 3700] and check["decode_steps"] == 24
    assert 4090 < 4096 < 4090 + 24 and max(check["prompt_tokens"]) + 25 <= check["pad_to"]
    # a program that reads no attention_class (the parent): refused before anything is built
    import opendiloco_tpu.models.llama as llama

    class Old:
        @staticmethod
        def from_dict(raw):
            return types.SimpleNamespace()

    class Older:
        @staticmethod
        def from_dict(raw):
            raise TypeError("unexpected keyword 'attention_class'")

    report = types.SimpleNamespace(line=lambda what, **kw: None)
    real = llama.LlamaConfig
    try:
        for old in (Old, Older):
            llama.LlamaConfig = old
            with pytest.raises(RuntimeError, match="cannot run evabyte-6.5b.*no EVA attention"):
                driver.start(cell, None, 0, 0, report, 0.0)
    finally:
        llama.LlamaConfig = real
    assert 0 < driver.LOGITS_REL_L2 < 4e-2  # under the chunk-visible reading (PERF.md, PR 40)


def test_reference_sees_the_mask_on_the_tiny_preset(man):
    """The rehearsal's configuration through the reference: pooled rows
    readable from their chunk's end on move the logits by far more than the
    driver's limit, a sequence inside its first window by nothing."""
    import jax

    from odbench import reference_evabyte
    from opendiloco_tpu.models.llama import LlamaConfig, init_params

    cfg = man.cell(CELL, rehearse=True).config
    params = init_params(jax.random.key(0), LlamaConfig.from_dict(cfg))
    ids = np.random.default_rng(0).integers(1, cfg["vocab_size"], (1, 72))
    sound = np.asarray(reference_evabyte.forward(params, ids, cfg))
    early = np.asarray(reference_evabyte.forward(params, ids, cfg, visible="chunk"))
    assert sound.shape == (1, 72, cfg["num_pred_heads"] * cfg["vocab_size"])
    rel = lambda a, b: float(np.linalg.norm(a - b) / np.linalg.norm(b))
    driver = man.driver("closed_loop_evabyte")
    assert rel(early[:, cfg["window_size"] :], sound[:, cfg["window_size"] :]) > driver.LOGITS_REL_L2
    assert rel(early[:, : cfg["chunk_size"]], sound[:, : cfg["chunk_size"]]) < 1e-6
    part = np.asarray(reference_evabyte.forward(params, ids, cfg, rows=(40, 7)))
    np.testing.assert_allclose(part, sound[:, 40:47], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("trace", [2])  # a ``--trace 2`` run is a ``--trace 0`` run until its window closes
def test_rehearsal_of_the_cell(man, trace):
    env = {k: v for k, v in os.environ.items() if k not in ("ODTP_OBS", "ODTP_REQTRACE_CAP")}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2147483659",
         "--seconds", "3", "--rehearse", "--trace", str(trace)],
        capture_output=True, text=True, timeout=900, env={**env, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    by_what = {x.get("what", "result"): x for x in
               (json.loads(line) for line in proc.stdout.splitlines() if line.startswith("{"))}
    out = by_what["rehearsal"]
    assert "result" not in by_what and "fault" not in by_what
    assert out["failed"] == 0 and by_what["check"]["ok"]
    check = by_what["check"]
    assert check["reference"] == "reference_evabyte" and check["heads"] == 2
    assert check["rows_compared"] == 2 * 7 and check["eva_window_restarts"] == 1  # 62 -> 64
    eva = by_what["evabyte"]
    assert eva["window"] == 32 and eva["chunk"] == 4
    assert eva["forms"] == {"decode": "xla", "prefill": "xla"} and eva["decode_kernel"] == "xla"
    held = eva["cache_bytes_per_slot"]
    assert held["window_ring"] == 3 * 32 * 2 * 64 * 2 and held["pooled_ring"] == 3 * 32 * 2 * 64 * 2
    assert eva["window_ring_bytes"] == 4 * held["window_ring"]
    assert eva["eva_cache_resident_bytes"] == 4 * (held["pooled_ring"] + held["pooling_stats"])
    assert {m["name"] for m in man.end_to_end(CELL)} <= set(out["metrics"])
    assert "tpot_p95_ms" not in out["metrics"]
    assert out["correct"] and "tpot_p95_ms" in by_what["tails"]  # a reading, not a metric
    window = by_what["window_counters"]
    assert window["eva_local_rows_read"] > 0 and window["eva_pooled_rows_read"] > 0
    assert window["eva_window_restarts"] > 0 and window["eva_chunks_pooled"] > 0
    assert window["eva_cache_resident_bytes"] == eva["eva_cache_resident_bytes"]
    assert window["local_rows_per_step"] > window["pooled_rows_per_step"] > 0
    assert by_what["window"]["compiles_in_window"] == 0
    if trace:
        # no peak on the CPU: the roofline share is left out, the rest is there
        assert "prefill_ms.complete" in out["metrics"]
        assert "eva_attn_roofline.serve" not in out["metrics"]
        traced = by_what["traced_eva"]
        assert traced["decode_steps"] == by_what["traced"]["traced_decode_steps"] > 0
        assert traced["local_rows"] > 0 and traced["pooled_rows"] > 0
        assert by_what["traced"]["compiles_in_trace"] == 0
