"""The Laguna-S-2.1 cell's benchmark files (ISSUE 56): the manifest's
soundness with the cell in it, the configuration file against the catalog row,
the cost functions against hand counts, the roofline reader on a synthetic
trace, the driver's own functions, its refusal of a program without sliding
grouped-query layers and its ``correct`` (the cell reports tokens per second
and no tail), the reference on the tiny preset, and the cell's rehearsal. CPU
only."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from odbench import costs, costs_laguna, manifest, peaks, stats

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
CELL = "serve-laguna-repoedit"
CONFIG = "laguna-s-2.1"
REDUCED = {"num_hidden_layers": 8, "num_local_experts": 64, "vocab_size": 25088}
# the catalog row's numbers (model-configs guide, Laguna-S-2.1), key for key
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072, "intermediate_size": 12288,
    "num_hidden_layers": 48, "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 1048576, "attention_bias": False, "rms_norm_eps": 1e-06,
    "num_experts": 256, "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
    "shared_expert_intermediate_size": 1024, "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [0], "tie_word_embeddings": False, "gating": "per-head",
    "sliding_window": 512, "moe_apply_router_weight_on_input": False,
    "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
    "rope_parameters": {
        "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                           "original_max_position_embeddings": 8192, "beta_slow": 1, "beta_fast": 32,
                           "attention_factor": 1.4852030263919618, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
    },
}
NEW_METRICS = {
    "swa_attn_roofline.laguna", "full_attn_roofline.laguna", "prefill_chunk_device_ms.laguna",
    "prefill_ms.laguna", "moe_ffn_roofline.laguna", "moe_max_over_mean_pairs.laguna",
}
OPS = [
    ["%odtp_paged_decode_attn.5 custom-call:tpu_custom_call", 0.0, 9e6, "(bf16[12,1,48,128]"],
    ["%odtp_paged_decode_attn.9 custom-call:tpu_custom_call", 9e6, 2e6, "(bf16[12,1,72,128]"],
    ["%while.3 while", 12e6, 3e6, "(f32[8,9,512,128], s32[]) while("],
    ["%fusion.77 fusion", 19e6, 9e6, "bf16[2048,3072]{1,0} fusion("],
]
NAMED = {
    "odtp_full_attn": [["%odtp_paged_decode_attn.5", "bf16[12,1,48,128]"]],
    "odtp_swa": [["%odtp_paged_decode_attn.9", "bf16[12,1,72,128]"], ["%while.3", "f32[8,9,512,128]"]],
    "odtp_serve_prefill": [["%fusion.77", "bf16[2048,3072]"]],
}


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(REPO, BENCH)


def _obs(man, counters, ops=None, peak="TPU v5 lite"):
    lines = []
    report = types.SimpleNamespace(line=lambda what, **kw: lines.append((what, kw)))
    obs = {"counters": counters, "cell": man.cell(CELL),
           "peak": peaks.peak(peak) if peak else None, "report": report}
    if ops is not None:
        obs["trace"] = {"ops": {"/device:TPU:0": ops}, "busy_s": 1.0, "window_s": 2.0}
    return obs, lines


def _driver_with_fake_loop(man):
    driver = man.driver("closed_loop_laguna")
    lines, seen = [], {}
    loop = types.SimpleNamespace(run=lambda **kw: seen.update(kw) or "ran", POOL=8192)
    load, manifest.load_module = manifest.load_module, lambda path: loop
    try:
        report = types.SimpleNamespace(line=lambda what, **kw: lines.append((what, kw)))
        assert driver.run(report=report, cell=man.cell(CELL)) == "ran"
    finally:
        manifest.load_module = load
    return driver, loop, lines


def test_manifest_is_sound_with_the_cell(man):
    """Properties, none of a moment: a later cell or metric joins without
    touching any of this."""
    assert manifest.problems(man) == []
    entry = next(w for w in man.raw["workloads"] if w["name"] == CELL)
    conf = next(c for c in man.raw["configs"] if c["name"] == entry["config"])
    assert entry["chips"] == 1 and entry["traffic"] == "repoedit-laguna"
    assert conf["name"] == CONFIG and conf["reduced"] == list(REDUCED)
    assert conf["source"] == "https://huggingface.co/poolside/Laguna-S-2.1/blob/main/config.json"
    assert len(entry["why"]) <= 200 and len(conf["why"]) <= 200
    assert {m["name"] for m in man.end_to_end(CELL)} == {"serve_tokens_per_s", "setup_s"}
    assert {m["name"] for m in man.per_layer(CELL)} == NEW_METRICS
    e2e = {m["name"]: m for m in man.raw["end_to_end"]}
    assert CELL not in e2e["tpot_p95_ms"]["workloads"]  # a window ends some 70 requests
    assert not stats.supported(90, 95.0)
    by_name = {p["name"]: p for p in man.raw["per_layer"]}
    assert len(by_name) == len(man.raw["per_layer"])
    for name in NEW_METRICS:
        p = by_name[name]
        assert p["workloads"] == [CELL] and p["moves"] == "serve_tokens_per_s"
        with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
            assert {k: v for k, v in json.load(f).items() if k in p} == {
                k: v for k, v in p.items() if k != "workloads"}
        assert set(p) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert by_name["swa_attn_roofline.laguna"]["layer"] == by_name["swa_attn_roofline.notes"]["layer"]
    assert by_name["moe_max_over_mean_pairs.laguna"]["layer"] == by_name["moe_max_over_mean_pairs.glm"]["layer"]
    for name, p in by_name.items():  # the cell stays off every other metric
        if name not in NEW_METRICS:
            assert CELL not in p.get("workloads", []), name
    assert sum(w["chips"] == 4 for w in man.raw["workloads"]) == 1
    assert len(json.dumps(man.raw)) < 64 * 1024
    for name in ("train-360m-h16", "serve-360m-batch", "train-1.7b-fsdp4-h8", "serve-olmoe-fewshot",
                 "serve-granite-h-docqa", "serve-glm-flash-agent", "serve-zaya1-reason",
                 "serve-evabyte-complete", "serve-keye-videoqa", "serve-dots3-notes"):
        assert any(w["name"] == name for w in man.raw["workloads"]), name


def test_configuration_file_holds_the_published_numbers(man):
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
        raw = json.load(f)
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert raw[key] == REDUCED[key] and raw["published"][key] == value, key
        else:
            assert raw[key] == value, key
    assert raw["num_local_experts"] == 64 and raw["published"]["num_local_experts"] == 256
    # the per-layer lists whole, as published: the program runs their leading entries
    assert len(raw["layer_types"]) == len(raw["mlp_layer_types"]) == len(raw["gating_types"]) == 48
    assert raw["layer_types"][:5] == ["full_attention", *["sliding_attention"] * 3, "full_attention"]
    assert raw["layer_types"].count("full_attention") == 12
    assert raw["num_attention_heads_per_layer"][:5] == [48, 72, 72, 72, 48]
    assert set(raw["gating_types"]) == {"per_head"} and raw["mlp_layer_types"].count("dense") == 1
    assert raw["reduced"] == list(REDUCED) and len(raw["assumed"]) >= 10
    assert "six pipeline stages" in raw["stands_for"] and "q_chunk_size" not in raw
    assert raw["parameters"]["as_run"] == costs_laguna.param_count(raw) == 5_034_052_608
    assert costs_laguna.published_param_count(raw) == 117_561_953_280


def test_costs_by_hand(man):
    cfg = man.cell(CELL).config
    assert (costs_laguna.heads(cfg, "full"), costs_laguna.heads(cfg, "sliding")) == (48, 72)
    assert costs_laguna.attention_param_count(cfg, "full") == 44_187_648
    assert costs_laguna.attention_param_count(cfg, "sliding") == 63_135_744
    assert [costs_laguna.layer_param_count(cfg, k) for k in ("dense", "full", "sliding")] == [
        157_440_000, 658_397_184, 677_345_280]
    assert costs_laguna.layer_kinds(cfg) == ["dense", *["sliding"] * 3, "full", *["sliding"] * 3]
    assert costs_laguna.layer_kinds(cfg, 48).count("sliding") == 36
    assert costs_laguna.row_bytes(cfg) == 4_096
    rings = costs_laguna.ring_bytes(cfg, 12, 18_432, 4_096)
    assert rings == {"full": 1_811_939_328, "sliding": 1_207_959_552, "all": 3_019_898_880}
    peak = peaks.peak("TPU v5 lite")
    # a decode step, 12 slots at 14,000 rows: a query a slot, its pairs are its rows
    window = 6 * 12 * 512
    flops, nbytes = costs_laguna.window_attn_cost(cfg, window, window)
    assert flops == 4 * 72 * 128 * window and nbytes == window * 4_096
    assert costs.roofline_seconds(flops, nbytes, peak)[1] == "memory"
    live = 2 * 12 * 14_001
    flops, nbytes = costs_laguna.full_attn_cost(cfg, live, live)
    assert flops == 4 * 48 * 128 * live and nbytes == live * 4_096
    assert costs.roofline_seconds(flops, nbytes, peak)[1] == "memory"
    # a chunk of 2,048 queries behind 8,192 rows: its queries share the rows
    pairs = 6 * 2_048 * 512
    flops, nbytes = costs_laguna.window_attn_cost(cfg, pairs, 6 * (511 + 2_048))
    assert nbytes == 6 * 2_559 * 4_096 and costs.roofline_seconds(flops, nbytes, peak)[1] == "compute"


@pytest.mark.parametrize("metric, cost, scope, columns, seconds", [
    ("swa_attn_roofline.laguna", "window_attn_cost", "odtp_swa", [0, 1], 5e-3),
    ("full_attn_roofline.laguna", "full_attn_cost", "odtp_full_attn", [2, 3], 9e-3),
])
def test_the_roofline_reader(man, metric, cost, scope, columns, seconds):
    read, params = man.reader(metric)
    assert params == {"scope": scope, "costs": "costs_laguna", "cost": cost, "columns": columns}
    step = [6 * 12 * 512, 6 * 12 * 512, 2 * 12 * 14_001, 2 * 12 * 14_001, 1]
    at = 8_192 + 1 + np.arange(2_048)
    chunk = [6 * int(np.minimum(at, 512).sum()), 6 * 2_559, 2 * int(at.sum()), 2 * 10_240, 0]
    calls = [step, chunk]
    obs, lines = _obs(man, {"traced_kind_calls": calls, "dsa_ops": NAMED}, OPS)
    pairs, rows = columns
    least = sum(
        costs.roofline_seconds(*getattr(costs_laguna, cost)(obs["cell"].config, c[pairs], c[rows]),
                               obs["peak"])[0] for c in calls)
    want = 100.0 * least / seconds
    assert read(obs, params) == pytest.approx(want) and 0 < want < 100
    (what, line), = lines
    assert what == "kind_roofline" and line["scope"] == scope and line["calls"] == 2
    # nothing to read (the parent's program, no named instruction, no event, no trace, no peak)
    assert read(_obs(man, {"traced_kind_calls": [], "dsa_ops": NAMED}, OPS)[0], params) is None
    assert read(_obs(man, {}, OPS)[0], params) is None
    assert read(_obs(man, {"traced_kind_calls": calls, "dsa_ops": {}}, OPS)[0], params) is None
    assert read(_obs(man, {"traced_kind_calls": calls, "dsa_ops": NAMED}, OPS[3:])[0], params) is None
    assert read(_obs(man, {"traced_kind_calls": calls, "dsa_ops": NAMED})[0], params) is None
    assert read(_obs(man, {"traced_kind_calls": calls, "dsa_ops": NAMED}, OPS, peak=None)[0], params) is None


def test_the_readers_that_were_there(man):
    read, params = man.reader("prefill_chunk_device_ms.laguna")
    assert params == {"scope": "odtp_serve_prefill"}
    step, chunk = [1, 1, 1, 1], [1, 1, 1, 0]
    obs, _ = _obs(man, {"traced_dsa_calls": [step, chunk, chunk], "dsa_ops": NAMED}, OPS)
    assert read(obs, params) == pytest.approx(9.0 / 2)
    assert read(_obs(man, {}, OPS)[0], params) is None
    read, params = man.reader("prefill_ms.laguna")
    assert read(_obs(man, {"prefill_s": 2.8, "admissions": 2})[0], params) == pytest.approx(1400.0)
    read, params = man.reader("moe_max_over_mean_pairs.laguna")
    assert params == {"held_key": "num_local_experts"}
    assert read(_obs(man, {"moe_pairs": 6400, "moe_max_pairs": 150})[0], params) == pytest.approx(1.5)
    read, params = man.reader("moe_ffn_roofline.laguna")
    assert params == {"needles": ["%ragged-dot"], "width_key": "moe_intermediate_size"}
    assert read(_obs(man, {"traced_moe_calls": []}, OPS)[0], params) is None


def test_the_spans_become_calls_by_kind(man):
    """``kind_calls``: a decode step's pairs are its rows; a chunk's queries
    share the slot's rows, each reading up to its own row (full) and at most
    the window's (sliding)."""
    driver = man.driver("closed_loop_laguna")
    spans = {
        "serve_decode": [{"swa_rows": 6 * 700, "full_rows": 2 * 9_000, "moe_pairs": 7, "moe_experts_hit": 5}],
        "serve_prefill": [{"swa_rows": 6 * 2_559, "full_rows": 2 * 4_096, "rows_before": 2_048,
                           "tokens": 2_048, "moe_pairs": 9_000, "moe_experts_hit": 448}, {"tokens": 3}],
    }
    real = driver.program_obs.span_args
    driver.program_obs.span_args = lambda capture, name, t0, t1: spans[name]
    try:
        calls, routed = driver.kind_calls(man.cell(CELL), None, 0.0, 1.0)
    finally:
        driver.program_obs.span_args = real
    at = 2_048 + 1 + np.arange(2_048)
    assert calls == [
        [6 * 2_048 * 512, 6 * 2_559, 2 * int(at.sum()), 2 * 4_096, 0],
        [6 * 700, 6 * 700, 2 * 9_000, 2 * 9_000, 1],
    ]
    assert routed == [[9_000, 448], [7, 5]]


@pytest.mark.parametrize("check_ok, failed, loop_error, want", [
    (True, 0, None, True), (False, 0, None, False), (True, 1, None, False),
    (True, 0, RuntimeError("loop"), False),
])
def test_correct_is_the_harness_rule_without_the_tail_sample(man, check_ok, failed, loop_error, want):
    driver, loop, lines = _driver_with_fake_loop(man)
    snap = {name: 0 for name in driver.COUNTERS}
    snap.update(decode_s=0.0, prefill_s=0.0, decode_steps=0)
    seen = {}
    real = driver.serve_cell.finish
    driver.serve_cell.finish = lambda **kw: seen.update(kw) or {"correct": False, "failed": failed}
    try:
        out = loop.serve_cell.finish(
            before=snap, after={**snap, "decode_steps": 4, "prefill_chunks": 3},
            check_ok=check_ok, batcher=types.SimpleNamespace(loop_error=loop_error))
    finally:
        driver.serve_cell.finish = real
    assert out["correct"] is want
    assert seen["extra_counters"]["prefill_chunks"] == 3
    assert [what for what, _ in lines] == ["window_counters"]
    assert driver.verdict(driver.LOGITS_REL_L2 * 0.9)[0]
    ok, limits, not_met = driver.verdict(driver.LOGITS_REL_L2 * 1.1)
    assert not ok and not_met == ["logits_rel_l2"] and list(limits) == ["logits_rel_l2"]
    assert not driver.verdict(float("nan"))[0]


def test_driver_replaces_five_functions_and_refuses_a_program_without_the_kinds(man):
    from odbench import traffic

    cell = man.cell(CELL)
    driver, loop, _ = _driver_with_fake_loop(man)
    for name in ("start", "warm_up", "snapshot", "traced_stretch"):
        assert getattr(loop.serve_cell, name) is getattr(driver, name), name
    assert loop.POOL == 256
    # the traffic: ISSUE 56's, and every request inside its slot's ring of whole chunks
    engine = cell.options["engine"]
    assert engine == {"num_slots": 12, "max_context": 18_432, "prefill_buckets": [], "prefill_chunk": 2_048}
    assert engine["max_context"] % engine["prefill_chunk"] == 0
    assert cell.traffic["prompt_tokens"] == {"dist": "uniform", "min": 12_288, "max": 16_384}
    reqs = traffic.requests(cell.traffic, 64, cell.config["vocab_size"], 2900000017)
    lens = np.array([len(a.prompt) for a in reqs])
    assert lens.min() >= 12_288 and lens.max() <= 16_384 and {a.max_new_tokens for a in reqs} == {256}
    assert lens.max() + 256 <= engine["max_context"]  # the full layers' rings do not wrap
    assert max(max(a.prompt) for a in reqs[:4]) < cell.config["vocab_size"] == 25_088
    check = cell.options["check"]
    assert [n % 2_048 == 0 for n in check["prompt_tokens"]] == [True, False]  # one ends inside a chunk
    assert all(12_288 <= n <= 16_384 for n in check["prompt_tokens"])
    assert max(check["prompt_tokens"]) + check["decode_steps"] <= check["pad_to"] <= engine["max_context"]
    # a program that knows no sliding grouped-query layers (the parent): refused before anything is built
    import opendiloco_tpu.models.llama as llama

    class Old:
        @staticmethod
        def from_dict(raw):
            return types.SimpleNamespace()

    class Older:
        @staticmethod
        def from_dict(raw):
            raise ValueError("layer_types must name 8 layers, each one of ('attention', 'mamba')")

    report = types.SimpleNamespace(line=lambda what, **kw: None)
    real = llama.LlamaConfig
    try:
        for old in (Old, Older):
            llama.LlamaConfig = old
            with pytest.raises(RuntimeError, match=f"cannot run {CONFIG}.*no sliding grouped-query"):
                driver.start(cell, None, 0, 0, report, 0.0)
    finally:
        llama.LlamaConfig = real


def test_reference_sees_the_faults_on_the_tiny_preset(man):
    import jax

    from odbench import reference_laguna
    from opendiloco_tpu.models.llama import LlamaConfig, init_params

    cfg = man.cell(CELL, rehearse=True).config
    params = init_params(jax.random.key(1), LlamaConfig.from_dict(cfg))
    for stack in params["layers"].values():  # scores of order one: a rotation that matters
        stack["q_proj"], stack["k_proj"] = stack["q_proj"] * 6.0, stack["k_proj"] * 6.0
    ids = np.asarray(jax.random.randint(jax.random.key(2), (1, 48), 3, cfg["vocab_size"]))
    sound = np.asarray(reference_laguna.forward(params, ids, cfg))
    for fault in ("no_gate", "no_scaling", "window_minus", "swap_rope", "no_factor"):
        broken = np.asarray(reference_laguna.forward(params, ids, cfg, faults=(fault,)))
        assert np.linalg.norm(broken - sound) > 5e-3 * np.linalg.norm(sound), fault
    rows, branches = reference_laguna.forward(params, ids, cfg, rows=(40, 7), branches=True)
    np.testing.assert_allclose(np.asarray(rows), sound[:, 40:47], rtol=1e-5, atol=1e-6)
    assert branches.shape == (5, 7, 64)


def test_rehearsal_of_the_cell(man):
    env = {k: v for k, v in os.environ.items() if k not in ("ODTP_OBS", "ODTP_REQTRACE_CAP")}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2900000017",
         "--seconds", "3", "--rehearse", "--trace", "2"],
        capture_output=True, text=True, timeout=900, env={**env, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    by_what = {x.get("what", "result"): x for x in
               (json.loads(line) for line in proc.stdout.splitlines() if line.startswith("{"))}
    out = by_what["rehearsal"]
    assert "result" not in by_what and "fault" not in by_what
    assert out["failed"] == 0 and out["correct"]
    check = by_what["check"]
    assert check["ok"] and check["reference"] == "reference_laguna" and check["rows_compared"] == 14
    assert check["prompts"] == [62, 41] and check["prefill_chunks"] == 8 + 6
    forms = by_what["laguna"]["kind_forms"]
    assert forms["sliding"]["chunk"] == "banded-xla" and forms["full"]["chunk"] == "tiled-xla"
    assert by_what["laguna"]["sliding_ring_rows"] == 16 and by_what["laguna"]["chunk"] == 8
    counted = by_what["window_counters"]
    assert counted["prefill_chunks"] > 0 and counted["swa_rows_read"] > 0 and counted["full_rows_read"] > 0
    assert by_what["traced_laguna"]["chunks"] > 0 and by_what["traced_laguna"]["window_pairs"] > 0
    named = by_what["traced_laguna"]["instructions_named"]
    assert all(named[scope] > 0 for scope in ("odtp_swa", "odtp_full_attn", "odtp_attn_gate"))
    assert set(out["metrics"]) >= {"serve_tokens_per_s", "setup_s", "prefill_ms.laguna",
                                   "moe_max_over_mean_pairs.laguna"}
