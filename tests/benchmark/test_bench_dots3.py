"""The dots3-note-prev cell's benchmark files (ISSUE 54): the manifest's
soundness with the cell in it, the configuration file against the catalog row,
the cost functions against hand counts, the roofline reader on a synthetic
trace, the driver's own functions, its refusal of a program without sliding
latent layers and its ``correct`` (the cell reports tokens per second and no
tail), the reference on the tiny preset, and the cell's rehearsal. CPU only."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from odbench import costs, costs_dots3, manifest, peaks, stats

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
CELL = "serve-dots3-notes"
CONFIG = "dots3-note-prev"
REDUCED = {"num_hidden_layers": 5, "num_local_experts": 32, "vocab_size": 19008}
# the catalog row's numbers (model-configs guide, dots3-note-prev), key for key
PUBLISHED = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False, "attention_gate_type": "headwise",
    "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824, "kv_lora_rank": 512,
    "max_position_embeddings": 524288, "model_type": "dots3_note", "moe_intermediate_size": 1536,
    "moe_layer_freq": 1, "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8, "num_hidden_layers": 46,
    "num_key_value_heads": 128, "q_lora_rank": 1024, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 80000000,
    "routed_scaling_factor": 1, "scoring_func": "sigmoid", "sliding_window_size": 513,
    "swa_attention_gate_type": "headwise", "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64,
    "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024, "swa_qk_nope_head_dim": 192,
    "swa_qk_rope_head_dim": 64, "swa_rope_theta": 50000, "swa_v_head_dim": 128,
    "tie_word_embeddings": False, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 152064,
}
NEW_METRICS = {
    "dsa_index_roofline.notes", "dsa_attn_roofline.notes", "swa_attn_roofline.notes",
    "prefill_chunk_device_ms.notes", "prefill_ms.notes", "moe_ffn_roofline.dots3",
    "moe_max_over_mean_pairs.dots3",
}
OPS = [
    ["%fusion.12 fusion", 0.0, 4e6, "f32[12,64,25088]{2,1,0} fusion("],
    ["%while.3 while", 5e6, 3e6, "(u32[12], s32[]) while("],
    ["%odtp_mla_decode_attn.5 custom-call:tpu_custom_call", 9e6, 6e6, "(bf16[12,128,512]"],
    ["%odtp_mla_decode_attn.9 custom-call:tpu_custom_call", 16e6, 2e6, "(bf16[12,64,1024]"],
    ["%fusion.77 fusion", 19e6, 9e6, "bf16[512,5120]{1,0} fusion("],
]
NAMED = {
    "odtp_dsa_index": [["%fusion.12", "f32[12,64,25088]"], ["%while.3", "u32[12]"]],
    "odtp_dsa_attn": [["%odtp_mla_decode_attn.5", "bf16[12,128,512]"]],
    "odtp_swa": [["%odtp_mla_decode_attn.9", "bf16[12,64,1024]"]],
    "odtp_serve_prefill": [["%fusion.77", "bf16[512,5120]"]],
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
    driver = man.driver("closed_loop_dots3")
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
    assert entry["chips"] == 1 and entry["traffic"] == "notes-dots3"
    assert conf["name"] == CONFIG and conf["reduced"] == list(REDUCED)
    assert conf["source"] == "https://huggingface.co/dots-studio/dots3-note-prev/blob/main/config.json"
    assert len(entry["why"]) <= 200 and len(conf["why"]) <= 200
    assert {m["name"] for m in man.end_to_end(CELL)} == {"serve_tokens_per_s", "setup_s"}
    assert {m["name"] for m in man.per_layer(CELL)} == NEW_METRICS
    e2e = {m["name"]: m for m in man.raw["end_to_end"]}
    assert CELL not in e2e["tpot_p95_ms"]["workloads"]  # a window ends a dozen or two requests
    assert not stats.supported(30, 95.0)
    by_name = {p["name"]: p for p in man.raw["per_layer"]}
    assert len(by_name) == len(man.raw["per_layer"])
    for name in NEW_METRICS:
        p = by_name[name]
        assert p["workloads"] == [CELL] and p["moves"] == "serve_tokens_per_s"
        with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
            assert {k: v for k, v in json.load(f).items() if k in p} == {
                k: v for k, v in p.items() if k != "workloads"}
        assert set(p) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    for twin in ("dsa_index_roofline", "dsa_attn_roofline"):
        assert by_name[f"{twin}.notes"]["layer"] == by_name[f"{twin}.serve"]["layer"]
    assert by_name["moe_max_over_mean_pairs.dots3"]["layer"] == by_name["moe_max_over_mean_pairs.glm"]["layer"]
    for name, p in by_name.items():  # the cell stays off every other metric
        if name not in NEW_METRICS:
            assert CELL not in p.get("workloads", []), name
    assert sum(w["chips"] == 4 for w in man.raw["workloads"]) == 1
    assert len(json.dumps(man.raw)) < 64 * 1024
    for name in ("train-360m-h16", "serve-360m-batch", "train-1.7b-fsdp4-h8", "serve-olmoe-fewshot",
                 "serve-granite-h-docqa", "serve-glm-flash-agent", "serve-zaya1-reason",
                 "serve-evabyte-complete", "serve-keye-videoqa"):
        assert any(w["name"] == name for w in man.raw["workloads"]), name


def test_configuration_file_holds_the_published_numbers(man):
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
        raw = json.load(f)
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert raw[key] == REDUCED[key] and raw["published"][key] == value, key
        else:
            assert raw[key] == value, key
    assert raw["num_local_experts"] == 32 and raw["published"]["num_local_experts"] == 256
    assert raw["layer_types"][:6] == ["full_attention", "full_attention", "sliding_attention",
                                      "sliding_attention", "sliding_attention", "full_attention"]
    assert len(raw["layer_types"]) == 46 and raw["layer_types"].count("full_attention") == 13
    assert raw["reduced"] == list(REDUCED) and len(raw["assumed"]) >= 10
    assert "eight pipeline stages" in raw["stands_for"]
    assert raw["parameters"]["as_run"] == costs_dots3.param_count(raw) == 4_087_154_176


def test_costs_by_hand(man):
    cfg = man.cell(CELL).config
    assert costs_dots3.attention_param_count(cfg, "full") == 134_678_016
    assert costs_dots3.indexer_param_count(cfg) == 9_371_904
    assert costs_dots3.attention_param_count(cfg, "sliding") == 90_834_944
    assert [costs_dots3.layer_param_count(cfg, k) for k in ("dense", "full", "sliding")] == [
        356_396_800, 923_938_816, 870_723_840]
    assert costs_dots3.layer_kinds(cfg) == ["dense", "full", "sliding", "sliding", "sliding"]
    assert costs_dots3.layer_kinds(cfg, 46).count("sliding") == 33
    assert (costs_dots3.index_row_bytes(cfg), costs_dots3.latent_row_bytes(cfg, "full"),
            costs_dots3.latent_row_bytes(cfg, "sliding")) == (256, 1_152, 2_176)
    rings = costs_dots3.ring_bytes(cfg, 12, 25_088, 1_024)
    assert rings["full"] + rings["index"] == 847_773_696 and rings["sliding"] == 80_216_064
    peak = peaks.peak("TPU v5 lite")
    # a decode step, 12 slots at 24,000 rows, the two full layers: its queries are one a slot
    scored = 2 * 12 * 24_000
    flops, nbytes = costs_dots3.index_cost(cfg, scored, scored)
    assert flops == (2 * 64 * 128 + 2 * 64) * scored and nbytes == scored * 256
    assert costs.roofline_seconds(flops, nbytes, peak)[1] == "memory"
    chosen = 2 * 12 * 2_048
    flops, nbytes = costs_dots3.sparse_mla_cost(cfg, chosen, scored)
    assert flops == 2 * 128 * (576 + 512) * chosen and nbytes == chosen * 1_152
    window = 3 * 12 * 513
    flops, nbytes = costs_dots3.window_mla_cost(cfg, window, window)
    assert flops == 2 * 64 * (1_088 + 1_024) * window and nbytes == window * 2_176
    assert costs.roofline_seconds(flops, nbytes, peak)[1] == "memory"
    # a chunk of 512 queries behind 20,480 rows: its queries share the rows
    rows = 2 * (20_480 + 512)
    flops, nbytes = costs_dots3.sparse_mla_cost(cfg, 2 * 512 * 2_048, rows)
    assert nbytes == rows * 1_152 and costs.roofline_seconds(flops, nbytes, peak)[1] == "compute"


@pytest.mark.parametrize("metric, cost, scope, seconds", [
    ("dsa_index_roofline.notes", "index_cost", "odtp_dsa_index", 7e-3),
    ("dsa_attn_roofline.notes", "sparse_mla_cost", "odtp_dsa_attn", 6e-3),
    ("swa_attn_roofline.notes", "window_mla_cost", "odtp_swa", 2e-3),
])
def test_the_roofline_reader(man, metric, cost, scope, seconds):
    read, params = man.reader(metric)
    assert params == {"scope": scope, "cost": cost}
    step = [2 * 12 * 24_000, 2 * 12 * 2_048, 2 * 12 * 24_000, 1, 3 * 12 * 513, 3 * 12 * 513]
    chunk = [2 * sum(8_192 + i + 1 for i in range(512)), 2 * 512 * 2_048, 2 * 8_704, 0,
             3 * 512 * 513, 3 * 1_024]
    calls = [step, chunk]
    obs, lines = _obs(man, {"traced_dots3_calls": calls, "dsa_ops": NAMED}, OPS)
    pairs, rows = {"index_cost": (0, 2), "sparse_mla_cost": (1, 2), "window_mla_cost": (4, 5)}[cost]
    least = sum(
        costs.roofline_seconds(*getattr(costs_dots3, cost)(obs["cell"].config, c[pairs], c[rows]),
                               obs["peak"])[0] for c in calls)
    want = 100.0 * least / seconds
    assert read(obs, params) == pytest.approx(want) and 0 < want < 100
    (what, line), = lines
    assert what == "dots3_roofline" and line["scope"] == scope and line["decode_steps"] == 1
    # nothing to read (the parent's program, no named instruction, no event, no trace, no peak)
    assert read(_obs(man, {"traced_dots3_calls": [], "dsa_ops": NAMED}, OPS)[0], params) is None
    assert read(_obs(man, {}, OPS)[0], params) is None
    assert read(_obs(man, {"traced_dots3_calls": calls, "dsa_ops": {}}, OPS)[0], params) is None
    assert read(_obs(man, {"traced_dots3_calls": calls, "dsa_ops": NAMED}, OPS[4:])[0], params) is None
    assert read(_obs(man, {"traced_dots3_calls": calls, "dsa_ops": NAMED})[0], params) is None
    assert read(_obs(man, {"traced_dots3_calls": calls, "dsa_ops": NAMED}, OPS, peak=None)[0], params) is None


def test_the_readers_that_were_there(man):
    read, params = man.reader("prefill_chunk_device_ms.notes")
    assert params == {"scope": "odtp_serve_prefill"}
    step, chunk = [1, 1, 1, 1], [1, 1, 1, 0]
    obs, _ = _obs(man, {"traced_dsa_calls": [step, chunk, chunk], "dsa_ops": NAMED}, OPS)
    assert read(obs, params) == pytest.approx(9.0 / 2)
    assert read(_obs(man, {}, OPS)[0], params) is None
    read, params = man.reader("prefill_ms.notes")
    assert read(_obs(man, {"prefill_s": 2.8, "admissions": 2})[0], params) == pytest.approx(1400.0)
    read, params = man.reader("moe_max_over_mean_pairs.dots3")
    assert params == {"held_key": "num_local_experts"}
    assert read(_obs(man, {"moe_pairs": 3200, "moe_max_pairs": 150})[0], params) == pytest.approx(1.5)
    read, params = man.reader("moe_ffn_roofline.dots3")
    assert params == {"needles": ["%ragged-dot"], "width_key": "moe_intermediate_size"}
    assert read(_obs(man, {"traced_moe_calls": []}, OPS)[0], params) is None


@pytest.mark.parametrize("check_ok, failed, loop_error, want", [
    (True, 0, None, True), (False, 0, None, False), (True, 1, None, False),
    (True, 0, RuntimeError("loop"), False),
])
def test_correct_is_the_harness_rule_without_the_tail_sample(man, check_ok, failed, loop_error, want):
    driver, loop, lines = _driver_with_fake_loop(man)
    snap = {name: 0 for name in (*driver.COUNTERS, *driver.RESIDENT)}
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


def test_driver_replaces_five_functions_and_refuses_a_program_without_sliding_layers(man):
    from odbench import traffic

    cell = man.cell(CELL)
    driver, loop, _ = _driver_with_fake_loop(man)
    for name in ("start", "warm_up", "snapshot", "traced_stretch"):
        assert getattr(loop.serve_cell, name) is getattr(driver, name), name
    assert loop.POOL == 256
    # the traffic: ISSUE 54's, and every request inside its slot's ring of whole chunks
    engine = cell.options["engine"]
    assert engine == {"num_slots": 12, "max_context": 25_088, "prefill_buckets": []}
    assert engine["max_context"] % cell.config["q_chunk_size"] == 0
    assert cell.traffic["prompt_tokens"] == {"dist": "uniform", "min": 22_528, "max": 24_576}
    reqs = traffic.requests(cell.traffic, 64, cell.config["vocab_size"], 2900000017)
    lens = np.array([len(a.prompt) for a in reqs])
    assert lens.min() >= 22_528 and lens.max() <= 24_576 and {a.max_new_tokens for a in reqs} == {512}
    assert lens.max() + 512 <= engine["max_context"]  # the full layers' ring does not wrap
    assert max(max(a.prompt) for a in reqs[:4]) < cell.config["vocab_size"] == 19_008
    check = cell.options["check"]
    assert all(n % 512 and 22_528 <= n <= 24_576 for n in check["prompt_tokens"])
    assert max(check["prompt_tokens"]) + check["decode_steps"] <= check["pad_to"] <= engine["max_context"]
    # a program that knows no sliding latent layers (the parent): refused before anything is built
    import opendiloco_tpu.models.llama as llama

    class Old:
        @staticmethod
        def from_dict(raw):
            return types.SimpleNamespace()

    class Older:
        @staticmethod
        def from_dict(raw):
            raise ValueError("layer_types must name 5 layers, each 'attention' or 'mamba'")

    report = types.SimpleNamespace(line=lambda what, **kw: None)
    real = llama.LlamaConfig
    try:
        for old in (Old, Older):
            llama.LlamaConfig = old
            with pytest.raises(RuntimeError, match=f"cannot run {CONFIG}.*no sliding latent layers"):
                driver.start(cell, None, 0, 0, report, 0.0)
    finally:
        llama.LlamaConfig = real


def test_the_verdict_holds_each_limit(man):
    driver = man.driver("closed_loop_dots3")
    cell = man.cell(CELL)
    zeros = [np.zeros((9, 2)), np.zeros((9, 2))]
    sound = driver.exchanged(cell, [z.astype(int) for z in zeros], zeros)
    assert driver.verdict(driver.LOGITS_REL_L2 * 0.9, sound)[0]
    ok, limits, failed = driver.verdict(driver.LOGITS_REL_L2 * 1.1, sound)
    assert not ok and failed == ["logits_rel_l2"] and len(limits) == 6
    assert not driver.verdict(float("nan"), sound)[0]
    # the full layers read apart: the engine's own readings (0.015 and 1.16) pass, layer by layer
    own = [np.tile([0.015, 1.16], (9, 1))] * 2
    assert driver.verdict(5.8e-2, driver.exchanged(cell, [np.full((9, 2), 100)] * 2, own))[0]
    # one prompt's second layer exchanged far apart: its part's limit, and no other's
    far = [np.tile([0.0, 3.0], (9, 1)), np.zeros((9, 2))]
    chose = driver.exchanged(cell, [np.full((9, 2), 300), np.zeros((9, 2), int)], far)
    ok, _, failed = driver.verdict(1e-3, chose)
    assert not ok and failed == ["median_exchange_distance_of_a_layer_in_a_prompt"]
    assert chose["rows_differing_share_by_layer"] == [round(150 / 2048, 5)] * 2
    # the last chunks' selection alone (row 0 of each prompt)
    last = [np.vstack([[0.0, 4.0], np.zeros((8, 2))])] * 2
    ok, _, failed = driver.verdict(1e-3, driver.exchanged(cell, [np.zeros((9, 2), int)] * 2, last))
    assert not ok and failed == ["median_exchange_distance_of_a_layer_last_tokens"]


def test_reference_sees_the_faults_on_the_tiny_preset(man):
    import jax

    from odbench import reference_dots3
    from opendiloco_tpu.models.llama import LlamaConfig, init_params

    cfg = man.cell(CELL, rehearse=True).config
    params = init_params(jax.random.key(1), LlamaConfig.from_dict(cfg))
    ids = np.asarray(jax.random.randint(jax.random.key(2), (1, 48), 3, cfg["vocab_size"]))
    sound = np.asarray(reference_dots3.forward(params, ids, cfg))
    for fault in ("no_gate", "no_rescale", "window_minus", "index_rotate_whole"):
        broken = np.asarray(reference_dots3.forward(params, ids, cfg, faults=(fault,)))
        assert np.linalg.norm(broken - sound) > 1e-2 * np.linalg.norm(sound), fault
    out = reference_dots3.forward(params, ids, cfg, rows=(40, 7), with_choices=True)
    np.testing.assert_allclose(np.asarray(out[0]), sound[:, 40:47], rtol=1e-5, atol=1e-6)
    assert out[1].shape == (7, 2, 48) and not np.asarray(out[2]).any()  # its own sets: nothing differs


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
    assert check["ok"] and check["reference"] == "reference_dots3" and check["limits_not_met"] == []
    assert check["rows_compared"] == 2 * 7 and check["sets_compared"] == 2 * 7 * 2
    assert check["prefill_chunks"] == 8 + 6 and check["prefill_chunk_tokens"] == 62 + 41
    assert check["swa_rows_read"] > 0 and check["latent_rows_read"] > 0
    built, dots3 = by_what["built"], by_what["dots3"]
    assert built["weights_adopted"] > 0 and "one copy" in built["weight_format"]
    assert dots3["sliding_ring_rows"] == 16 and dots3["window"] == 5 and dots3["chunk"] == 8
    assert dots3["full_ring_bytes"] == dots3["ring_bytes_by_shapes"]["full"]
    assert dots3["sliding_ring_bytes"] == dots3["ring_bytes_by_shapes"]["sliding"]
    assert dots3["index_cache_resident_bytes"] == dots3["ring_bytes_by_shapes"]["index"]
    assert {m["name"] for m in man.end_to_end(CELL)} <= set(out["metrics"])
    assert "tpot_p95_ms" not in out["metrics"] and "tpot_p95_ms" in by_what["tails"]
    window = by_what["window_counters"]
    assert window["prefill_chunks"] > 0 and window["dsa_rows_scored"] > window["dsa_rows_selected"] > 0
    assert window["moe_pairs_all"] > window["moe_pairs"] > 0 and window["swa_rows_read"] > 0
    assert 0.2 < window["chunks_per_step"] < 1.2  # a chunk an iteration, between two steps
    assert by_what["window"]["compiles_in_window"] == 0
    # no peak on the CPU: the roofline shares are left out, the rest is there
    assert {"prefill_ms.notes", "moe_max_over_mean_pairs.dots3"} <= set(out["metrics"])
    traced = by_what["traced_dots3"]
    assert traced["calls"] > traced["chunks"] > 0 and traced["window_pairs"] > 0
    assert all(traced["instructions_named"][scope] > 0
               for scope in ("odtp_dsa_index", "odtp_dsa_attn", "odtp_swa", "odtp_serve_prefill"))
    assert by_what["traced"]["compiles_in_trace"] == 0
