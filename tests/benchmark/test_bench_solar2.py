"""The Solar-Open2 cell's benchmark files (ISSUE 64): the manifest's soundness
with the cell in it, the configuration file against the catalog row, the cost
functions against hand counts, the roofline reader (``laguna_roofline``, which
serves the new mixer's share from ``costs_solar2``) and the other readers on a
synthetic trace, the one reader this PR adds (the decode kernel's share over
the one layer ``gqa_layers`` names among those run), the driver's own
functions, its refusal of a program without kda layers, its limit and its
``correct`` (the cell reports tokens per second and no tail), the reference's
faults on the tiny preset, and the cell's rehearsal. CPU only."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from odbench import costs, costs_routed, costs_solar2, manifest, peaks, stats

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
CELL = "serve-solar2-reason"
CONFIG = "solar-open2-250b"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW_METRICS = {
    "kda_roofline.solar2", "moe_ffn_roofline.solar2", "moe_max_over_mean_pairs.solar2",
    "paged_attn_roofline.solar2", "prefill_chunk_device_ms.solar2", "prefill_ms.solar2",
}
OPS = [
    ["%fusion.11 fusion", 0.0, 4e6, "f32[3,128,64,128,128]{4,3,2,1,0} fusion("],
    ["%while.3 while", 4e6, 6e6, "(f32[1,64,128,128], s32[]) while("],
    ["%odtp_paged_decode_attn.2 custom-call:tpu_custom_call", 10e6, 2e6, "(bf16[128,64,128]"],
    ["%ragged-dot-none.4 custom-call:tpu_custom_call", 12e6, 5e6, "bf16[1024,1280]"],
    ["%fusion.77 fusion", 19e6, 9e6, "bf16[2048,4096]{1,0} fusion("],
]
NAMED = {
    "odtp_kda": [["%fusion.11", "f32[3,128,64,128,128]"], ["%while.3", "f32[1,64,128,128]"]],
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
    driver = man.driver("closed_loop_solar2")
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
    assert entry["chips"] == 1 and entry["traffic"] == "reason-solar2"
    assert conf["name"] == CONFIG
    assert conf["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert conf["source"] == "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/config.json"
    assert len(entry["why"]) <= 200 and len(conf["why"]) <= 200
    assert {m["name"] for m in man.end_to_end(CELL)} == {"serve_tokens_per_s", "setup_s"}
    assert {m["name"] for m in man.per_layer(CELL)} == NEW_METRICS
    e2e = {m["name"]: m for m in man.raw["end_to_end"]}
    assert CELL not in e2e["tpot_p95_ms"]["workloads"]  # a window ends some 135 requests
    assert not stats.supported(135, 95.0)
    by_name = {p["name"]: p for p in man.raw["per_layer"]}
    assert len(by_name) == len(man.raw["per_layer"])
    for name in NEW_METRICS:
        p = by_name[name]
        assert p["workloads"] == [CELL] and p["moves"] == "serve_tokens_per_s"
        with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
            assert {k: v for k, v in json.load(f).items() if k in p} == {
                k: v for k, v in p.items() if k != "workloads"}
        assert set(p) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert by_name["kda_roofline.solar2"]["layer"] == by_name["lightning_roofline.sala"]["layer"]
    assert by_name["prefill_ms.solar2"]["layer"] == by_name["prefill_ms.sala"]["layer"]
    assert by_name["moe_max_over_mean_pairs.solar2"]["layer"] == by_name["moe_max_over_mean_pairs.laguna"]["layer"]
    for name, p in by_name.items():  # the cell stays off every other metric
        if name not in NEW_METRICS:
            assert CELL not in p.get("workloads", []), name
    assert sum(w["chips"] == 4 for w in man.raw["workloads"]) == 1
    assert len(json.dumps(man.raw)) < 64 * 1024
    for name in ("train-360m-h16", "serve-360m-batch", "train-1.7b-fsdp4-h8", "serve-olmoe-fewshot",
                 "serve-granite-h-docqa", "serve-glm-flash-agent", "serve-zaya1-reason",
                 "serve-evabyte-complete", "serve-keye-videoqa", "serve-dots3-notes",
                 "serve-laguna-repoedit", "serve-sala-longdoc"):
        assert any(w["name"] == name for w in man.raw["workloads"]), name


def test_configuration_file_holds_the_published_numbers(man):
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
        raw = json.load(f)
    cut = {"num_hidden_layers": (4, 48), "n_routed_experts": (40, 320), "vocab_size": (24_576, 196_608)}
    if os.path.exists(CATALOG):  # the catalog itself, where this machine has it
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r.get("name") == "Solar-Open2-250B")
        assert raw["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in cut:
                assert (raw[key], raw["published"][key]) == cut[key] and value == cut[key][1]
            else:
                assert raw[key] == value, key  # ``linear_attn_config`` whole
    # floors: a whole period and four layers, 8 experts or more, an eighth of the vocabulary
    assert raw["gqa_layers"][:2] == [0, 4] and len(raw["gqa_layers"]) == 12
    assert raw["n_routed_experts"] >= 8 and raw["vocab_size"] * 8 == raw["published"]["vocab_size"]
    assert raw["reduced"] == list(cut) and len(raw["assumed"]) >= 10
    assert (raw["num_experts"], raw["first_local_expert"]) == (320, 0)
    for said in ("eight chips share each layer", "twelve pipeline stages", "layers 0-3",
                 "40 of the 320", "24,576 of the 196,608"):
        assert said in raw["stands_for"], said
    assert any("intermediate_size 10240 sizes no layer" in a for a in raw["assumed"])
    assert raw["parameters"]["as_run"] == costs_solar2.param_count(raw) == 3_308_377_920
    assert raw["parameters"]["published"] == costs_solar2.published_param_count(raw) == 250_288_105_216
    assert "q_chunk_size" not in raw


def test_costs_by_hand(man):
    cfg = man.cell(CELL).config
    assert costs_solar2.layer_kinds(cfg) == ["gqa", "kda", "kda", "kda"]
    assert [costs_solar2.mixer_param_count(cfg, k) for k in ("kda", "gqa")] == [137_740_480, 109_051_904]
    assert [costs_solar2.layer_param_count(cfg, k, 40) for k in ("kda", "gqa")] == [783_933_952, 755_245_376]
    assert [costs_solar2.layer_param_count(cfg, k, 0) for k in ("kda", "gqa")] == [
        154_788_352, 126_099_776]  # outside its routed experts
    assert costs_solar2.slot_bytes(cfg, 5_120) == {
        "kv": 20_971_520, "state": 12_582_912, "tail": 442_368, "all": 33_996_800}
    peak = peaks.peak("TPU v5 lite")
    state = 64 * 128 * 128
    # a decode step of 128 slots: the state there and back, 8 H D D operations a token and layer
    flops, nbytes = costs_solar2.kda_cost(cfg, 3 * 128, 0)
    assert flops == 8 * state * 3 * 128 and nbytes == 2 * 4 * state * 3 * 128 == 3_221_225_472
    assert costs.roofline_seconds(flops, nbytes, peak)[1] == "memory"
    # a chunk of 2,048 tokens: 32 blocks' triangles four times, three products with
    # the state a token, the rows once and the state there and back
    flops, nbytes = costs_solar2.kda_cost(cfg, 0, 3 * 2_048)
    assert flops == 3 * (8 * 64 * 128 * 32 * 64 * 65 / 2 + 6 * state * 2_048)
    assert nbytes == 3 * (2_048 * 8_192 * (4 * 2 + 4) + 2 * 4 * state)
    # (0.26 ms of operations under 0.77 ms of rows: the equations' chunk is the memory's too)
    assert costs.roofline_seconds(flops, nbytes, peak)[1] == "memory"
    # a last chunk of 939 tokens: 14 whole blocks and 43 tokens of a fifteenth
    flops, _ = costs_solar2.kda_cost(cfg, 0, 3 * 939)
    assert flops == 3 * (8 * 64 * 128 * (14 * 64 * 65 / 2 + 43 * 44 / 2) + 6 * state * 939)
    assert costs_solar2.BLOCK == 64  # the family's, whatever the program's is


def test_the_roofline_reader_serves_the_mixers_share(man):
    read, params = man.reader("kda_roofline.solar2")
    assert params == {"scope": "odtp_kda", "costs": "costs_solar2", "cost": "kda_cost", "columns": [0, 1]}
    calls = [[3 * 127, 0, 0, 1], [0, 3 * 2_048, 3 * 64 * 32, 0]]
    obs, lines = _obs(man, {"traced_kind_calls": calls, "dsa_ops": NAMED}, OPS)
    least = sum(
        costs.roofline_seconds(*costs_solar2.kda_cost(obs["cell"].config, c[0], c[1]), obs["peak"])[0]
        for c in calls)
    want = 100.0 * least / 10e-3
    assert read(obs, params) == pytest.approx(want) and 0 < want < 100
    (what, line), = lines
    assert what == "kind_roofline" and line["scope"] == "odtp_kda" and line["calls"] == 2
    # nothing to read (the parent's program, no named instruction, no trace, no peak)
    assert read(_obs(man, {}, OPS)[0], params) is None
    assert read(_obs(man, {"traced_kind_calls": calls, "dsa_ops": {}}, OPS)[0], params) is None
    assert read(_obs(man, {"traced_kind_calls": calls, "dsa_ops": NAMED})[0], params) is None
    assert read(_obs(man, {"traced_kind_calls": calls, "dsa_ops": NAMED}, OPS, peak=None)[0], params) is None


def test_the_readers_that_were_there(man):
    read, params = man.reader("prefill_chunk_device_ms.solar2")
    assert params == {"scope": "odtp_serve_prefill"}
    step, chunk = [1, 1, 1, 1], [1, 1, 1, 0]
    obs, _ = _obs(man, {"traced_dsa_calls": [step, chunk, chunk], "dsa_ops": NAMED}, OPS)
    assert read(obs, params) == pytest.approx(9.0 / 2)
    assert read(_obs(man, {}, OPS)[0], params) is None
    read, params = man.reader("prefill_ms.solar2")
    assert read(_obs(man, {"prefill_s": 2.8, "admissions": 2})[0], params) == pytest.approx(1400.0)
    read, params = man.reader("moe_max_over_mean_pairs.solar2")
    assert params == {"held_key": "n_routed_experts"}
    assert read(_obs(man, {"moe_pairs": 4_000, "moe_max_pairs": 300})[0], params) == pytest.approx(3.0)
    assert read(_obs(man, {})[0], params) is None
    read, params = man.reader("moe_ffn_roofline.solar2")
    assert params == {"needles": ["%ragged-dot"], "width_key": "moe_intermediate_size"}
    obs, _ = _obs(man, {"traced_moe_calls": [[4 * 128 * 8 // 8, 150]]}, OPS)
    least = costs.roofline_seconds(
        *costs_routed.routed_ffn_cost(obs["cell"].config, 512, 150, "moe_intermediate_size"),
        obs["peak"])[0]
    assert read(obs, params) == pytest.approx(100.0 * least / 5e-3)
    assert read(_obs(man, {}, OPS)[0], params) is None


def test_the_decode_kernels_share_counts_the_one_ring(man):
    """The reader this PR adds: ``paged_attn_roofline`` told that of the four
    layers run one keeps a ring (``gqa_layers``' entries under the depth) and
    that a head is ``head_dim`` wide."""
    read, params = man.reader("paged_attn_roofline.solar2")
    assert params == {"needles": ["odtp_paged_decode_attn"]}
    counters = {"traced_decode_steps": 2, "traced_live_rows": 2 * 128 * 1_000, "traced_live_slots": 256}
    obs, lines = _obs(man, counters, OPS)
    one_layer = {**obs["cell"].config, "num_hidden_layers": 1, "hidden_size": 64 * 128}
    flops, nbytes = costs.paged_decode_cost(one_layer, counters["traced_live_rows"], 256)
    assert nbytes == 2 * 256_000 * 8 * 128 * 2 + 2 * 256 * 64 * 128 * 2
    want = 100.0 * costs.roofline_seconds(flops, nbytes, obs["peak"])[0] / 2e-3
    assert read(obs, params) == pytest.approx(want) and 0 < want < 100
    assert lines[0][0] == "paged_attn_roofline"
    # a cell without the key is another reader's; no trace, no steps: nothing
    other = _obs(man, counters, OPS)[0]
    other["cell"] = man.cell("serve-360m-batch")
    assert read(other, params) is None
    assert read(_obs(man, counters)[0], params) is None and read(_obs(man, {}, OPS)[0], params) is None


def test_the_spans_become_calls(man):
    """``layer_calls``: what a traced decode step and a traced chunk asked of
    the mixer and of the held experts, from the spans' attributes as the
    engine counted them."""
    driver = man.driver("closed_loop_solar2")
    routed = dict(moe_pairs=400, moe_experts_hit=150)
    spans = {
        "serve_decode": [{"kda_step_tokens": 3 * 127, "kda_chunk_tokens": 0, "kda_blocks_solved": 0, **routed}],
        "serve_prefill": [{"kda_step_tokens": 0, "kda_chunk_tokens": 3 * 2_048,
                           "kda_blocks_solved": 3 * 64 * 32, "tokens": 2_048, **routed}, {"tokens": 3}],
    }
    real = driver.program_obs.span_args
    driver.program_obs.span_args = lambda capture, name, t0, t1: spans[name]
    try:
        calls, moe = driver.layer_calls(None, 0.0, 1.0)
    finally:
        driver.program_obs.span_args = real
    assert calls == [[0, 3 * 2_048, 3 * 64 * 32, 0], [3 * 127, 0, 0, 1]]
    assert moe == [[400, 150], [400, 150]]


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
            before=snap, after={**snap, "decode_steps": 4, "prefill_chunks": 1, "moe_pairs": 1_600},
            check_ok=check_ok, batcher=types.SimpleNamespace(loop_error=loop_error))
    finally:
        driver.serve_cell.finish = real
    assert out["correct"] is want
    assert seen["extra_counters"]["prefill_chunks"] == 1
    (what, counted), = lines
    assert what == "window_counters" and counted["chunks_per_step"] == 0.25
    assert counted["held_pairs_a_step_and_expert"] == 1_600 / (5 * 40 * 4)


def test_the_verdict_holds_the_limit(man):
    driver = man.driver("closed_loop_solar2")
    ok, limits, not_met = driver.verdict(driver.LOGITS_REL_L2 * 0.9)
    assert ok and not not_met and limits == {"logits_rel_l2": 1.5e-1}
    assert driver.verdict(driver.LOGITS_REL_L2 * 1.1)[2] == ["logits_rel_l2"]
    assert not driver.verdict(float("nan"))[0]


def test_driver_replaces_five_functions_and_refuses_a_program_without_the_layers(man):
    from odbench import traffic

    cell = man.cell(CELL)
    driver, loop, _ = _driver_with_fake_loop(man)
    for name in ("start", "warm_up", "snapshot", "traced_stretch"):
        assert getattr(loop.serve_cell, name) is getattr(driver, name), name
    assert loop.POOL == 4_000_000 // 4_096  # under 1,000 pooled requests
    # the traffic: ISSUE 64's, and every request inside its slot's ring
    engine = cell.options["engine"]
    assert engine == {"num_slots": 128, "max_context": 5_120, "prefill_buckets": [], "prefill_chunk": 2_048}
    assert cell.traffic["prompt_tokens"] == {"dist": "uniform", "min": 2_049, "max": 4_096}
    reqs = traffic.requests(cell.traffic, 256, cell.config["vocab_size"], 2964000017)
    lens = np.array([len(a.prompt) for a in reqs])
    assert lens.min() >= 2_049 and lens.max() <= 4_096 and {a.max_new_tokens for a in reqs} == {1_024}
    assert lens.max() + 1_024 <= engine["max_context"]
    assert set(-(-lens // 2_048)) == {2}  # two chunks each, whatever the draw
    assert max(max(a.prompt) for a in reqs[:4]) < cell.config["vocab_size"] == 24_576  # ids from the slice
    check = cell.options["check"]
    assert [n % 2_048 == 0 for n in check["prompt_tokens"]] == [True, False]  # one ends inside a chunk
    assert (check["prompt_tokens"][1] - 2_048) % 64 not in (0, 63)  # and inside a block of 64
    assert all(2_049 <= n <= 4_096 for n in check["prompt_tokens"])
    assert max(check["prompt_tokens"]) + check["decode_steps"] <= check["pad_to"] <= engine["max_context"]
    # a program that knows no such layers (the parent refuses the keys it knows:
    # a file's ``num_local_experts`` beside no ``num_experts`` of its own reading):
    # refused before anything is built
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
            with pytest.raises(RuntimeError, match=f"cannot run {CONFIG}.*no kda"):
                driver.start(cell, None, 0, 0, report, 0.0)
    finally:
        llama.LlamaConfig = real


def test_reference_sees_the_faults_on_the_tiny_preset(man):
    import jax

    from odbench import reference_solar2
    from opendiloco_tpu.models.llama import LlamaConfig, init_params

    cfg = man.cell(CELL, rehearse=True).config
    params = init_params(jax.random.key(1), LlamaConfig.from_dict(cfg))
    ids = np.asarray(jax.random.randint(jax.random.key(2), (1, 48), 3, cfg["vocab_size"]))
    sound = np.asarray(reference_solar2.forward(params, ids, cfg))
    for fault in ("no_delta", "beta_one", "scalar_decay", "no_kda_gate", "no_gqa_gate", "no_shared"):
        broken = np.asarray(reference_solar2.forward(params, ids, cfg, faults=(fault,)))
        assert np.linalg.norm(broken - sound) > 1e-3 * np.linalg.norm(sound), fault
    rows = reference_solar2.forward(params, ids, cfg, rows=(40, 7))
    np.testing.assert_allclose(np.asarray(rows), sound[:, 40:47], rtol=1e-5, atol=1e-6)


def test_rehearsal_of_the_cell(man):
    env = {k: v for k, v in os.environ.items() if k not in ("ODTP_OBS", "ODTP_REQTRACE_CAP")}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "2964000017",
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
    assert check["ok"] and check["reference"] == "reference_solar2" and check["rows_compared"] == 14
    assert check["prompts"] == [32, 23] and check["prefill_chunks"] == 2 + 2
    assert check["kda_chunk_tokens"] == 3 * 55 and check["kda_step_tokens"] == 3 * 2 * 6
    forms = by_what["solar2"]["kda_forms"]
    assert forms["step"] == "xla" and forms["chunk"] == "chunked-xla" and forms["block"] == 64
    assert by_what["solar2"]["layers"] == {"kda": 3, "gqa": 1} and by_what["solar2"]["chunk"] == 16
    assert (by_what["solar2"]["experts_held"], by_what["solar2"]["experts"]) == (4, 8)
    counted = by_what["window_counters"]
    assert counted["prefill_chunks"] > 0 and counted["kda_step_tokens"] > 0 < counted["moe_pairs"]
    assert counted["moe_pairs"] < counted["moe_pairs_all"]  # a share's pairs
    assert by_what["traced_solar2"]["chunks"] > 0 and by_what["traced_solar2"]["kda_chunk_tokens"] > 0
    named = by_what["traced_solar2"]["instructions_named"]
    assert all(named[scope] > 0 for scope in ("odtp_kda", "odtp_kda_conv", "odtp_attn_gate"))
    assert set(out["metrics"]) >= {"serve_tokens_per_s", "setup_s", "prefill_ms.solar2",
                                   "moe_max_over_mean_pairs.solar2"}
