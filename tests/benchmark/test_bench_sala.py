"""The MiniCPM-SALA cell's benchmark files (ISSUE 61): the manifest's soundness
with the cell in it, the configuration file against the catalog row, the cost
functions against hand counts, the roofline reader (``laguna_roofline``, which
serves the three new shares from ``costs_sala``) on a synthetic trace, the
driver's own functions, its refusal of a program without lightning layers or a
selection by blocks, its limits and its ``correct`` (the cell reports tokens
per second and no tail), the reference's faults on the tiny preset, and the
cell's rehearsal. CPU only."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from odbench import costs, costs_sala, manifest, peaks, stats

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
CELL = "serve-sala-longdoc"
CONFIG = "minicpm-sala"
# the catalog row's keys (model-configs guide, MiniCPM-SALA), key for key
PUBLISHED = {
    "attention_bias": False, "attn_use_rope": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 4096, "intermediate_size": 16384, "lightning_head_dim": 128, "lightning_nh": 32,
    "lightning_nkv": 32, "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "max_position_embeddings": 524288, "model_type": "minicpm_sala", "num_attention_heads": 32,
    "num_hidden_layers": 32, "num_key_value_heads": 2, "qk_norm": True, "rand_init": False,
    "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000, "scale_emb": 12,
    "scale_depth": 1.4, "mup_denominator": 32, "dim_model_base": 256, "tie_word_embeddings": False,
    "use_output_gate": True, "use_output_norm": True, "attn_use_output_gate": True,
}
NEW_METRICS = {
    "lightning_roofline.sala", "block_select_roofline.sala", "block_attn_roofline.sala",
    "prefill_chunk_device_ms.sala", "prefill_ms.sala",
}
OPS = [
    ["%fusion.11 fusion", 0.0, 4e6, "f32[14,12,32,128,128]{4,3,2,1,0} fusion("],
    ["%odtp_block_decode_attn.5 custom-call:tpu_custom_call", 4e6, 2e6, "(f32[12,2,16,128]"],
    ["%while.3 while", 6e6, 6e6, "(f32[2,16,2048,128], s32[]) while("],
    ["%fusion.21 fusion", 12e6, 3e6, "f32[2,2048,544]{2,1,0} fusion("],
    ["%fusion.77 fusion", 19e6, 9e6, "bf16[2048,4096]{1,0} fusion("],
]
NAMED = {
    "odtp_lightning": [["%fusion.11", "f32[14,12,32,128,128]"]],
    "odtp_block_attn": [["%odtp_block_decode_attn.5", "f32[12,2,16,128]"], ["%while.3", "f32[2,16,2048,128]"]],
    "odtp_block_select": [["%fusion.21", "f32[2,2048,544]"]],
    "odtp_serve_prefill": [["%fusion.77", "bf16[2048,4096]"]],
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
    driver = man.driver("closed_loop_sala")
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
    assert entry["chips"] == 1 and entry["traffic"] == "longdoc-sala"
    assert conf["name"] == CONFIG and conf["reduced"] == ["num_hidden_layers"]
    assert conf["source"] == "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json"
    assert len(entry["why"]) <= 200 and len(conf["why"]) <= 200
    assert {m["name"] for m in man.end_to_end(CELL)} == {"serve_tokens_per_s", "setup_s"}
    assert {m["name"] for m in man.per_layer(CELL)} == NEW_METRICS
    e2e = {m["name"]: m for m in man.raw["end_to_end"]}
    assert CELL not in e2e["tpot_p95_ms"]["workloads"]  # a window ends some 10 requests
    assert not stats.supported(12, 95.0)
    by_name = {p["name"]: p for p in man.raw["per_layer"]}
    assert len(by_name) == len(man.raw["per_layer"])
    for name in NEW_METRICS:
        p = by_name[name]
        assert p["workloads"] == [CELL] and p["moves"] == "serve_tokens_per_s"
        with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
            assert {k: v for k, v in json.load(f).items() if k in p} == {
                k: v for k, v in p.items() if k != "workloads"}
        assert set(p) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert by_name["block_attn_roofline.sala"]["layer"] == by_name["full_attn_roofline.laguna"]["layer"]
    assert by_name["prefill_ms.sala"]["layer"] == by_name["prefill_ms.laguna"]["layer"]
    for name, p in by_name.items():  # the cell stays off every other metric
        if name not in NEW_METRICS:
            assert CELL not in p.get("workloads", []), name
    assert sum(w["chips"] == 4 for w in man.raw["workloads"]) == 1
    assert len(json.dumps(man.raw)) < 64 * 1024
    for name in ("train-360m-h16", "serve-360m-batch", "train-1.7b-fsdp4-h8", "serve-olmoe-fewshot",
                 "serve-granite-h-docqa", "serve-glm-flash-agent", "serve-zaya1-reason",
                 "serve-evabyte-complete", "serve-keye-videoqa", "serve-dots3-notes",
                 "serve-laguna-repoedit"):
        assert any(w["name"] == name for w in man.raw["workloads"]), name


def test_configuration_file_holds_the_published_numbers(man):
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
        raw = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") if os.path.exists(
            "/opt/skills/guides/model-configs/architectures.jsonl") else open(os.devnull) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    for row in rows:
        if row.get("name") == "MiniCPM-SALA":  # the catalog itself, where this machine has it
            assert {k: v for k, v in row["config"].items() if k != "mixer_types"} == PUBLISHED
            assert row["config"]["mixer_types"] == raw["mixer_types"]
    for key, value in PUBLISHED.items():
        if key == "num_hidden_layers":
            assert raw[key] == 18 and raw["published"][key] == value
        else:
            assert raw[key] == value, key
    # the list whole, as published: the program runs its leading entries
    mixers = raw["mixer_types"]
    assert len(mixers) == 32 and mixers.count("minicpm4") == 8
    assert [i for i, m in enumerate(mixers[:18]) if m == "minicpm4"] == [0, 9, 16, 17]
    assert raw["reduced"] == ["num_hidden_layers"] and len(raw["assumed"]) >= 9
    assert "stage 0" in raw["stands_for"] and "q_chunk_size" not in raw
    assert raw["sparse_config"] == {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
                                    "topk": 64, "init_blocks": 1, "window_size": 2048, "dense_len": 8192}
    assert raw["parameters"]["as_run"] == costs_sala.param_count(raw) == 5_609_898_496
    assert raw["parameters"]["published"] == costs_sala.published_param_count(raw) == 9_477_206_016


def test_costs_by_hand(man):
    cfg = man.cell(CELL).config
    assert costs_sala.counts(cfg) == (14, 4) and costs_sala.counts(cfg, 32) == (24, 8)
    assert [costs_sala.layer_param_count(cfg, k) for k in ("sparse", "lightning")] == [
        253_763_840, 285_225_216]
    assert costs_sala.slot_bytes(cfg, 34_816) == {
        "kv": 142_606_336, "pooled": 4_456_448, "state": 29_360_128, "all": 176_422_912}
    peak = peaks.peak("TPU v5 lite")
    # a decode step of 12 slots: the state there and back, 4 H D D operations a token and layer
    flops, nbytes = costs_sala.lightning_cost(cfg, 14 * 12, 0)
    assert flops == 4 * 32 * 128 * 128 * 14 * 12 and nbytes == 2 * 4 * 32 * 128 * 128 * 14 * 12
    assert costs.roofline_seconds(flops, nbytes, peak)[1] == "memory"
    # a chunk of 2,048 tokens: the pairs under the triangle, q S and k^T v, the rows and the state once
    flops, nbytes = costs_sala.lightning_cost(cfg, 0, 14 * 2_048)
    assert flops == 14 * (4 * 32 * 128 * 128 * 2_048 + 4 * 32 * 128 * 2_048 * 2_049 / 2)
    assert nbytes == 14 * (2_048 * 4 * 4_096 * 2 + 2 * 4 * 32 * 128 * 128)
    assert costs.roofline_seconds(flops, nbytes, peak)[1] == "compute"
    pairs, keys = 4 * 2 * 12 * 1_900, 4 * 2 * 12 * 1_900
    assert costs_sala.block_select_cost(cfg, pairs, keys) == (2.0 * 16 * 128 * pairs, keys * 256.0)
    rows = 4 * 2 * 12 * 4_096
    assert costs_sala.block_attn_cost(cfg, rows, rows) == (4.0 * 16 * 128 * rows, rows * 512.0)
    assert costs.roofline_seconds(*costs_sala.block_attn_cost(cfg, rows, rows), peak)[1] == "memory"


@pytest.mark.parametrize("metric, cost, scope, columns, seconds", [
    ("lightning_roofline.sala", "lightning_cost", "odtp_lightning", [0, 1], 4e-3),
    ("block_select_roofline.sala", "block_select_cost", "odtp_block_select", [2, 3], 3e-3),
    ("block_attn_roofline.sala", "block_attn_cost", "odtp_block_attn", [4, 5], 8e-3),
])
def test_the_roofline_reader_serves_the_three_shares(man, metric, cost, scope, columns, seconds):
    read, params = man.reader(metric)
    assert params == {"scope": scope, "costs": "costs_sala", "cost": cost, "columns": columns}
    step = [14 * 12, 0, 8 * 12 * 1_900, 8 * 12 * 1_900, 8 * 12 * 4_096, 8 * 12 * 4_096, 1, 1_100, 5_800]
    chunk = [0, 14 * 2_048, 8 * 2_048 * 1_000, 8 * 1_100, 8 * 2_048 * 4_000, 8 * 18_000, 0, 140, 140]
    calls = [step, chunk]
    obs, lines = _obs(man, {"traced_kind_calls": calls, "dsa_ops": NAMED}, OPS)
    a, b = columns
    least = sum(
        costs.roofline_seconds(*getattr(costs_sala, cost)(obs["cell"].config, c[a], c[b]), obs["peak"])[0]
        for c in calls)
    want = 100.0 * least / seconds
    assert read(obs, params) == pytest.approx(want) and 0 < want < 100
    (what, line), = lines
    assert what == "kind_roofline" and line["scope"] == scope and line["calls"] == 2
    # nothing to read (the parent's program, no named instruction, no trace, no peak)
    assert read(_obs(man, {}, OPS)[0], params) is None
    assert read(_obs(man, {"traced_kind_calls": calls, "dsa_ops": {}}, OPS)[0], params) is None
    assert read(_obs(man, {"traced_kind_calls": calls, "dsa_ops": NAMED})[0], params) is None
    assert read(_obs(man, {"traced_kind_calls": calls, "dsa_ops": NAMED}, OPS, peak=None)[0], params) is None


def test_the_readers_that_were_there(man):
    read, params = man.reader("prefill_chunk_device_ms.sala")
    assert params == {"scope": "odtp_serve_prefill"}
    step, chunk = [1, 1, 1, 1], [1, 1, 1, 0]
    obs, _ = _obs(man, {"traced_dsa_calls": [step, chunk, chunk], "dsa_ops": NAMED}, OPS)
    assert read(obs, params) == pytest.approx(9.0 / 2)
    assert read(_obs(man, {}, OPS)[0], params) is None
    read, params = man.reader("prefill_ms.sala")
    assert read(_obs(man, {"prefill_s": 2.8, "admissions": 2})[0], params) == pytest.approx(1400.0)


def test_the_spans_become_calls(man):
    """``layer_calls``: what a traced decode step and a traced chunk asked of
    the three layers, from the spans' attributes as the engine counted them."""
    driver = man.driver("closed_loop_sala")
    attrs = dict(pooled_keys_scored=10, pooled_keys_read=7, block_rows_read=900,
                 block_rows_distinct=600, block_tiles_read=4, block_tiles_live=9)
    spans = {
        "serve_decode": [{"lightning_tokens": 14 * 11, **attrs}],
        "serve_prefill": [{"lightning_tokens": 14 * 2_048, **attrs, "tokens": 2_048}, {"tokens": 3}],
    }
    real = driver.program_obs.span_args
    driver.program_obs.span_args = lambda capture, name, t0, t1: spans[name]
    try:
        calls = driver.layer_calls(None, 0.0, 1.0)
    finally:
        driver.program_obs.span_args = real
    assert calls == [[0, 14 * 2_048, 10, 7, 900, 600, 0, 4, 9], [14 * 11, 0, 10, 7, 900, 600, 1, 4, 9]]


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
            before=snap, after={**snap, "decode_steps": 4, "prefill_chunks": 3, "block_tiles_read": 2,
                                "block_tiles_live": 8},
            check_ok=check_ok, batcher=types.SimpleNamespace(loop_error=loop_error))
    finally:
        driver.serve_cell.finish = real
    assert out["correct"] is want
    assert seen["extra_counters"]["prefill_chunks"] == 3
    (what, counted), = lines
    assert what == "window_counters" and counted["tiles_read_share"] == 0.25


def test_the_verdict_holds_every_limit(man):
    driver = man.driver("closed_loop_sala")
    sound = {"median_exchange_distance": 0.02, "median_exchange_distance_by_prompt": [0.01, 0.03],
             "median_exchange_distance_last_tokens": 0.0, "median_exchange_distance_decode_steps": 0.02,
             "blocks_differing_share_by_layer": [0.0, 0.02, 0.02, 0.02]}
    ok, limits, not_met = driver.verdict(driver.LOGITS_REL_L2 * 0.9, sound)
    assert ok and not not_met and set(limits) == {
        "logits_rel_l2", "median_exchange_distance", "median_exchange_distance_of_a_prompt",
        "median_exchange_distance_last_tokens", "median_exchange_distance_decode_steps",
        "blocks_differing_share_of_a_layer"}
    assert driver.verdict(driver.LOGITS_REL_L2 * 1.1, sound)[2] == ["logits_rel_l2"]
    assert not driver.verdict(float("nan"), sound)[0]
    for key, bad, name in (
        ("median_exchange_distance", 1.0, "median_exchange_distance"),
        ("median_exchange_distance_by_prompt", [0.0, 2.0], "median_exchange_distance_of_a_prompt"),
        ("median_exchange_distance_last_tokens", 2.0, "median_exchange_distance_last_tokens"),
        ("blocks_differing_share_by_layer", [0.0, 0.0, 0.5, 0.0], "blocks_differing_share_of_a_layer"),
    ):
        assert driver.verdict(1e-2, {**sound, key: bad})[2] == [name]
    # the readings' arrays as the check reduces them: [R, Ls, Kh] a prompt
    differing = [np.zeros((9, 4, 2), int), np.full((9, 4, 2), 2)]
    distance = [np.zeros((9, 4, 2)), np.full((9, 4, 2), 0.5)]
    chose = driver.exchanged(man.cell(CELL), differing, distance)
    assert chose["median_exchange_distance_by_prompt"] == [0.0, 0.5] and chose["sets_compared"] == 144
    assert chose["sets_differing"] == 72 and chose["blocks_differing_share_by_layer"] == [
        round(1 / 128, 5)] * 4


def test_driver_replaces_five_functions_and_refuses_a_program_without_the_layers(man):
    from odbench import traffic

    cell = man.cell(CELL)
    driver, loop, _ = _driver_with_fake_loop(man)
    for name in ("start", "warm_up", "snapshot", "traced_stretch"):
        assert getattr(loop.serve_cell, name) is getattr(driver, name), name
    assert loop.POOL == 256
    # the traffic: ISSUE 61's, and every request inside its slot's ring of whole chunks
    engine = cell.options["engine"]
    assert engine == {"num_slots": 12, "max_context": 34_816, "prefill_buckets": [], "prefill_chunk": 2_048}
    assert engine["max_context"] % engine["prefill_chunk"] == 0
    assert cell.traffic["prompt_tokens"] == {"dist": "uniform", "min": 28_673, "max": 32_768}
    reqs = traffic.requests(cell.traffic, 64, cell.config["vocab_size"], 2461000007)
    lens = np.array([len(a.prompt) for a in reqs])
    assert lens.min() >= 28_673 and lens.max() <= 32_768 and {a.max_new_tokens for a in reqs} == {512}
    assert lens.min() >= cell.config["sparse_config"]["dense_len"]  # every query runs the selection
    assert lens.max() + 512 <= engine["max_context"]  # no block wraps
    assert set(-(-lens // 2_048)) == {15, 16}  # fifteen or sixteen chunks
    assert max(max(a.prompt) for a in reqs[:2]) < cell.config["vocab_size"] == 73_448
    check = cell.options["check"]
    assert [n % 2_048 == 0 for n in check["prompt_tokens"]] == [True, False]  # one ends inside a chunk
    assert check["prompt_tokens"][1] % 16 not in (0, 15)  # and inside a pooling window
    assert all(28_673 <= n <= 32_768 for n in check["prompt_tokens"])
    assert max(check["prompt_tokens"]) + check["decode_steps"] <= check["pad_to"] <= engine["max_context"]
    # a program that knows no such layers (the parent reads the keys it knows as a
    # plain stack of 18 attention layers): refused before anything is built
    import opendiloco_tpu.models.llama as llama

    class Old:
        @staticmethod
        def from_dict(raw):
            return types.SimpleNamespace()

    class Older:
        @staticmethod
        def from_dict(raw):
            raise ValueError("unknown model_type")

    report = types.SimpleNamespace(line=lambda what, **kw: None)
    real = llama.LlamaConfig
    try:
        for old in (Old, Older):
            llama.LlamaConfig = old
            with pytest.raises(RuntimeError, match=f"cannot run {CONFIG}.*no lightning"):
                driver.start(cell, None, 0, 0, report, 0.0)
    finally:
        llama.LlamaConfig = real


def test_reference_sees_the_faults_on_the_tiny_preset(man):
    import jax

    from odbench import reference_sala
    from opendiloco_tpu.models.llama import LlamaConfig, init_params

    cfg = man.cell(CELL, rehearse=True).config
    params = init_params(jax.random.key(1), LlamaConfig.from_dict(cfg))
    ids = np.asarray(jax.random.randint(jax.random.key(2), (1, 64), 3, cfg["vocab_size"]))
    sound = np.asarray(reference_sala.forward(params, ids, cfg))
    for fault in ("no_out_gate", "depth_cut", "no_head_scale", "no_lightning_rope", "topk_beside_forced"):
        broken = np.asarray(reference_sala.forward(params, ids, cfg, faults=(fault,)))
        assert np.linalg.norm(broken - sound) > 1e-4 * np.linalg.norm(sound), fault
    rows, own, scores = reference_sala.forward(params, ids, cfg, rows=(50, 7), with_choices=True)
    np.testing.assert_allclose(np.asarray(rows), sound[:, 50:57], rtol=1e-5, atol=1e-6)
    assert own.shape == scores.shape == (7, 1, 2, 8) and (np.asarray(own).sum(-1) == 4).all()


def test_rehearsal_of_the_cell(man):
    env = {k: v for k, v in os.environ.items() if k not in ("ODTP_OBS", "ODTP_REQTRACE_CAP")}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2461000007",
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
    assert check["ok"] and check["reference"] == "reference_sala" and check["rows_compared"] == 14
    assert check["prompts"] == [48, 37] and check["prefill_chunks"] == 3 + 3
    assert check["sets_compared"] == 14 * 1 * 2 and check["dense_len_calls"] == 0
    forms = by_what["sala"]["block_forms"]
    assert forms["decode"] == "block-gather-xla" and forms["chunk"] == "tiled-xla"
    assert by_what["sala"]["layers"] == {"lightning": 4, "sparse": 1} and by_what["sala"]["chunk"] == 16
    counted = by_what["window_counters"]
    assert counted["prefill_chunks"] > 0 and counted["lightning_tokens"] > 0
    assert 0 < counted["block_tiles_read"] <= counted["block_tiles_live"]
    assert by_what["traced_sala"]["chunks"] > 0 and by_what["traced_sala"]["block_pairs"] > 0
    named = by_what["traced_sala"]["instructions_named"]
    assert all(named[scope] > 0 for scope in (
        "odtp_lightning", "odtp_block_select", "odtp_block_attn", "odtp_attn_gate"))
    assert set(out["metrics"]) >= {"serve_tokens_per_s", "setup_s", "prefill_ms.sala"}
