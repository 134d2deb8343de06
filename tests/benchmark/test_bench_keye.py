"""The Keye-VL-2.0 cell's benchmark files (ISSUE 49): the manifest's soundness
with the cell in it, the configuration file against the catalog row, the cost
functions against hand counts, the roofline reader on a synthetic trace, the
driver's five functions, its refusal of a program without learned sparse
attention and its ``correct`` (the cell reports tokens per second and no tail),
the reference on the tiny preset, and the cell's rehearsal. CPU only."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from odbench import costs, costs_keye, manifest, peaks, stats

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
CELL = "serve-keye-videoqa"
CONFIG = "keye-vl-2.0-30b-a3b"
# the catalog row's config (model-configs guide, Keye-VL-2.0-30B-A3B), key for key
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 262144,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                  "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
REDUCED = {"num_hidden_layers": 16, "num_local_experts": 16, "vocab_size": 18992}
NEW_METRICS = {"dsa_index_roofline.serve", "dsa_attn_roofline.serve", "prefill_ms.videoqa",
               "moe_ffn_roofline.keye", "moe_max_over_mean_pairs.keye",
               "prefill_chunk_device_ms.videoqa"}


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(REPO, BENCH)


def test_the_cell_reports_no_tail_and_why(man):
    """A p95 is reported with ten samples beyond it: 200 requests. A request
    here is 24-32 chunks and 256 steps, and a 45 s window ends a few dozen."""
    assert not stats.supported(60, 95.0) and stats.supported(200, 95.0)
    e2e = {m["name"]: m for m in man.raw["end_to_end"]}
    assert CELL in e2e["serve_tokens_per_s"]["workloads"]
    assert CELL not in e2e["tpot_p95_ms"]["workloads"]
    for p in man.raw["per_layer"]:  # a metric that moves the tail is not the cell's
        if CELL in p.get("workloads", []):
            assert p["moves"] == "serve_tokens_per_s", p["name"]


def _driver_with_fake_loop(man):
    driver = man.driver("closed_loop_keye")
    lines, seen = [], {}
    loop = types.SimpleNamespace(run=lambda **kw: seen.update(kw) or "ran", POOL=8192)
    load, manifest.load_module = manifest.load_module, lambda path: loop
    try:
        report = types.SimpleNamespace(line=lambda what, **kw: lines.append((what, kw)))
        assert driver.run(report=report, cell=man.cell(CELL)) == "ran"
    finally:
        manifest.load_module = load
    return driver, loop, lines


@pytest.mark.parametrize("check_ok, failed, loop_error, want", [
    (True, 0, None, True), (False, 0, None, False), (True, 1, None, False),
    (True, 0, RuntimeError("loop"), False),
])
def test_correct_is_the_harness_rule_without_the_tail_sample(man, check_ok, failed, loop_error, want):
    driver, loop, lines = _driver_with_fake_loop(man)
    snap = {"decode_s": 0.0, "prefill_s": 0.0, "decode_steps": 0, "new_tokens": 0,
            **{name: 0 for name in (*driver.COUNTERS, driver.RESIDENT)}}
    after = {**snap, "decode_s": 20.0, "decode_steps": 500, "prefill_s": 3.0,
             "prefill_chunks": 480, "dsa_rows_scored": 7000, "moe_pairs": 90}
    req = types.SimpleNamespace(error=None, t_done=1.0, t_first=0.5)
    bad = types.SimpleNamespace(error="boom", t_done=None, t_first=None)
    reqs = [(0.0, req)] * (30 - failed) + [(0.0, bad)] * failed
    out = loop.serve_cell.finish(
        cell=man.cell(CELL), peak=None, engine=types.SimpleNamespace(num_slots=12),
        batcher=types.SimpleNamespace(loop_error=loop_error), before=snap, after=after,
        window_s=45.0, reqs_due=reqs, in_window=0, check_ok=check_ok, e2e={},
        tail_facts={"p95_supported": False}, trace=0, tracer=None, instrument=None,
    )
    assert out["correct"] is want and out["failed"] == failed and out["attempted"] == 30
    counters = out["observations"]["counters"]
    assert counters["dsa_rows_scored"] == 7000 and counters["prefill_chunks"] == 480
    (what, line), = lines
    assert what == "window_counters" and line["decode_step_ms"] == pytest.approx(40.0)
    assert line["prefill_ms_per_chunk"] == pytest.approx(6.25)
    assert line["chunks_per_step"] == pytest.approx(0.96)


def test_manifest_is_sound_with_the_cell(man):
    """Properties, none of a moment (where the cell stands in a list is none):
    a later cell or metric joins without touching any of this."""
    problems = manifest.problems(man)
    assert problems == [], problems
    entry = next(w for w in man.raw["workloads"] if w["name"] == CELL)
    conf = next(c for c in man.raw["configs"] if c["name"] == entry["config"])
    assert entry["chips"] == 1 and entry["traffic"] == "videoqa-keye"
    assert conf["name"] == CONFIG and conf["reduced"] == list(REDUCED)
    assert conf["source"] == "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json"
    assert len(entry["why"]) <= 200 and len(conf["why"]) <= 200
    assert {m["name"] for m in man.end_to_end(CELL)} == {"serve_tokens_per_s", "setup_s"}
    assert {m["name"] for m in man.per_layer(CELL)} == NEW_METRICS
    by_name = {p["name"]: p for p in man.raw["per_layer"]}
    assert len(by_name) == len(man.raw["per_layer"])
    for name in NEW_METRICS:
        p = by_name[name]
        assert p["workloads"] == [CELL] and p["moves"] == "serve_tokens_per_s"
        with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
            assert {k: v for k, v in json.load(f).items() if k in p} == {
                k: v for k, v in p.items() if k != "workloads"}
        assert set(p) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert by_name["dsa_attn_roofline.serve"]["layer"] == by_name["paged_attn_roofline.serve"]["layer"]
    assert by_name["prefill_ms.videoqa"]["layer"] == by_name["prefill_ms.batch"]["layer"]
    assert by_name["moe_max_over_mean_pairs.keye"]["layer"] == by_name["moe_max_over_mean_pairs.glm"]["layer"]
    # metrics that read what this configuration has not, or that move a tail: the cell stays off them
    for name, p in by_name.items():
        if name not in NEW_METRICS:
            assert CELL not in p.get("workloads", []), name
    for m in [*man.raw["end_to_end"], *man.raw["per_layer"]]:
        names = m.get("workloads", [])
        assert len(set(names)) == len(names)
    assert sum(w["chips"] == 4 for w in man.raw["workloads"]) <= max(1, len(man.raw["workloads"]) // 4)
    assert len(json.dumps(man.raw)) < 64 * 1024
    # every cell and configuration the benchmark held is still there
    for name in ("train-360m-h16", "serve-360m-batch", "train-1.7b-fsdp4-h8", "serve-olmoe-fewshot",
                 "serve-granite-h-docqa", "serve-glm-flash-agent", "serve-zaya1-reason",
                 "serve-evabyte-complete"):
        assert any(w["name"] == name for w in man.raw["workloads"]), name


def test_configuration_file_holds_the_published_numbers(man):
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
        raw = json.load(f)
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert raw[key] == REDUCED[key] and raw["published"][key] == value, key
        else:
            assert raw[key] == value, key
    assert raw["reduced"] == list(REDUCED) == list(raw["published"])
    assert raw["norm_init_std"] == 0.02 and "norm_init_std" in " ".join(raw["assumed"])  # the draw the issue states
    assert raw["source"].endswith("Keye-VL-2.0-30B-A3B/blob/main/config.json")
    assert "three pipeline stages" in raw["stands_for"] and len(raw["assumed"]) >= 12
    joined = " ".join(raw["assumed"])
    for what in ("QK-norm per head", "q_lora_rank", "LayerNorm", "q_chunk_size", "by token",
                 "Hadamard", "ties", "initializer_range", "alignment loss", "vision tower",
                 "no locality", "bfloat16"):
        assert what in joined, what
    p = raw["parameters"]
    assert p["attention"] == 18_874_368 + 256 and p["indexer"] == 2_261_120
    assert p["router"] == 262_144 and p["two_norms"] == 4_096
    assert p["layer_outside_its_experts"] == 21_401_984 and p["one_expert"] == 4_718_592
    assert p["layer_whole"] == 625_381_760 and p["embedding_and_head_whole"] == 622_329_856
    assert p["published"] == 48 * 625_381_760 + 622_329_856 + 2048 == 30_640_656_384
    assert p["layer_as_held"] == 21_401_984 + 16 * 4_718_592 == 96_899_456
    assert p["as_run"] == 16 * 96_899_456 + 2 * 18_992 * 2048 + 2048 == 1_628_184_576
    assert p["as_run_bytes_bf16"] == 3_256_369_152
    assert p["ring_bytes_per_token_and_layer"] == 2_176
    assert p["ring_bytes_12_slots_of_16896_rows"] == 2_176 * 16 * 16_896 * 12 == 7_059_013_632
    cell = man.cell(CELL)
    assert cell.config["num_hidden_layers"] == 16 and cell.config["sa_config"]["topk"] == 2048
    # inside the guide's floors: a third of the layers, an eighth of the experts and of the vocabulary
    assert cell.config["num_local_experts"] * 8 == cell.config["num_experts"]
    assert cell.config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # and the program reads the file as the catalog's keys say
    from opendiloco_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.from_dict(cell.config)
    assert cfg.num_params() == p["as_run"] and cfg.sparse and cfg.held_experts == 16


def test_costs_by_hand(man):
    cfg = man.cell(CELL).config
    assert costs_keye.param_count(cfg) == 1_628_184_576
    assert costs_keye.published_param_count(cfg) == 30_640_656_384
    assert costs_keye.kv_row_bytes(cfg) == 2 * 4 * 128 * 2 == 2_048
    assert costs_keye.index_row_bytes(cfg) == 128
    assert costs_keye.ring_bytes(cfg, 12, 16_896) == {
        "kv": 6_643_777_536, "index": 415_236_096, "all": 7_059_013_632}
    # a decode step of 12 slots at 14,500 live rows in 16 layers
    scored = 16 * 12 * 14_500
    flops, nbytes = costs_keye.index_cost(cfg, scored, scored)
    assert flops == (2 * 16 * 64 + 2 * 16) * scored and nbytes == scored * 128
    peak = peaks.peak("TPU v5 lite")
    assert costs.roofline_seconds(flops, nbytes, peak)[1] == "memory"
    chosen = 16 * 12 * 2_048
    flops, nbytes = costs_keye.sparse_attn_cost(cfg, chosen, scored)
    assert flops == 4 * 128 * 32 * chosen and nbytes == chosen * 2_048  # 50 MB a layer
    assert nbytes // 16 == 50_331_648
    assert costs.roofline_seconds(flops, nbytes, peak)[1] == "memory"
    # a chunk of 512 queries behind 14,336 rows: its queries share the rows they score and choose from
    rows = 16 * (14_336 + 512)
    scored = 16 * sum(14_336 + i + 1 for i in range(512))
    flops, nbytes = costs_keye.index_cost(cfg, scored, rows)
    assert nbytes == rows * 128 and costs.roofline_seconds(flops, nbytes, peak)[1] == "compute"
    flops, nbytes = costs_keye.sparse_attn_cost(cfg, 16 * 512 * 2_048, rows)
    assert nbytes == rows * 2_048 and flops == 4 * 128 * 32 * 16 * 512 * 2_048
    assert costs.roofline_seconds(flops, nbytes, peak)[1] == "compute"


OPS = [
    ["%fusion.12 fusion", 0.0, 4e6, "f32[12,16,16896]{2,1,0} fusion("],
    ["%while.3 while", 5e6, 3e6, "(u32[12], s32[]) while("],
    ["%reduce_sum.7 fusion", 5.5e6, 2e6, "s32[12]{0} fusion("],
    ["%odtp_paged_decode_attn.5 custom-call:tpu_custom_call", 9e6, 6e6, "(bf16[12,1,32,128]"],
    ["%fusion.77 fusion", 16e6, 9e6, "bf16[512,2048]{1,0} fusion("],
]
NAMED = {
    "odtp_dsa_index": [["%fusion.12", "f32[12,16,16896]"], ["%while.3", "u32[12]"],
                       ["%reduce_sum.7", "s32[12]"]],
    "odtp_dsa_attn": [["%odtp_paged_decode_attn.5", "bf16[12,1,32,128]"]],
    "odtp_serve_prefill": [["%fusion.77", "bf16[512,2048]"], ["%fusion.12", "f32[512,16,16896]"]],
}


def _obs(man, counters, ops=None, peak="TPU v5 lite"):
    lines = []
    report = types.SimpleNamespace(line=lambda what, **kw: lines.append((what, kw)))
    obs = {"counters": counters, "cell": man.cell(CELL),
           "peak": peaks.peak(peak) if peak else None, "report": report}
    if ops is not None:
        obs["trace"] = {"ops": {"/device:TPU:0": ops}, "busy_s": 1.0, "window_s": 2.0}
    return obs, lines


@pytest.mark.parametrize("metric, cost, column, seconds", [
    ("dsa_index_roofline.serve", "index_cost", 0, 7e-3),  # 4 ms, the while's own 1 ms, 2 ms inside it
    ("dsa_attn_roofline.serve", "sparse_attn_cost", 1, 6e-3),
])
def test_dsa_roofline_reader(man, metric, cost, column, seconds):
    read, params = man.reader(metric)
    assert params["cost"] == cost and params["scope"] in NAMED
    step = [16 * 12 * 14_000, 16 * 12 * 2_048, 16 * 12 * 14_000, 1]
    chunk = [16 * sum(8_192 + i + 1 for i in range(512)), 16 * 512 * 2_048, 16 * 8_704, 0]
    calls = [step, chunk]
    obs, lines = _obs(man, {"traced_dsa_calls": calls, "dsa_ops": NAMED}, OPS)
    least = sum(
        costs.roofline_seconds(*getattr(costs_keye, cost)(obs["cell"].config, c[column], c[2]), obs["peak"])[0]
        for c in calls
    )
    want = 100.0 * least / seconds
    assert read(obs, params) == pytest.approx(want) and 0 < want < 100
    (what, line), = lines
    assert what == "dsa_roofline" and line["scope"] == params["scope"]
    assert line["decode_steps"] == 1 and line["calls"] == 2
    assert line["calls_by_bound"] == {"compute": 1, "memory": 1}
    # nothing to read: a program whose spans carry no such rows (the parent), no named
    # instruction, no such event, no trace, no peak: nothing, and nothing raised
    assert read(_obs(man, {"traced_dsa_calls": [], "dsa_ops": NAMED}, OPS)[0], params) is None
    assert read(_obs(man, {}, OPS)[0], params) is None
    assert read(_obs(man, {"traced_dsa_calls": calls, "dsa_ops": {}}, OPS)[0], params) is None
    assert read(_obs(man, {"traced_dsa_calls": calls, "dsa_ops": NAMED}, OPS[4:])[0], params) is None
    assert read(_obs(man, {"traced_dsa_calls": calls, "dsa_ops": NAMED})[0], params) is None
    assert read(_obs(man, {"traced_dsa_calls": calls, "dsa_ops": NAMED}, OPS, peak=None)[0], params) is None


def test_prefill_chunk_device_ms_reader(man):
    """The chunk program's operations' self time over the traced chunks: an
    event of the same name under another shape (the decode step's) is not
    among them; nothing without chunks, names, events or a trace."""
    read, params = man.reader("prefill_chunk_device_ms.videoqa")
    assert params == {"scope": "odtp_serve_prefill"}
    step, chunk = [1, 1, 1, 1], [1, 1, 1, 0]
    obs, lines = _obs(man, {"traced_dsa_calls": [step, chunk, chunk], "dsa_ops": NAMED}, OPS)
    assert read(obs, params) == pytest.approx(9.0 / 2)  # %fusion.77's 9 ms over two chunks
    (what, line), = lines
    assert what == "prefill_chunk_device" and line["chunks"] == 2 and line["events"] == 1
    assert read(_obs(man, {"traced_dsa_calls": [step], "dsa_ops": NAMED}, OPS)[0], params) is None
    assert read(_obs(man, {}, OPS)[0], params) is None
    assert read(_obs(man, {"traced_dsa_calls": [chunk], "dsa_ops": {}}, OPS)[0], params) is None
    assert read(_obs(man, {"traced_dsa_calls": [chunk], "dsa_ops": NAMED}, OPS[:4])[0], params) is None
    assert read(_obs(man, {"traced_dsa_calls": [chunk], "dsa_ops": NAMED})[0], params) is None


def test_the_data_only_metrics(man):
    read, params = man.reader("prefill_ms.videoqa")
    assert params == {"seconds": "prefill_s", "count": "admissions"}
    obs, _ = _obs(man, {"prefill_s": 2.8, "admissions": 2})
    assert read(obs, params) == pytest.approx(1400.0)
    assert read(_obs(man, {"prefill_s": 0.0, "admissions": 0})[0], params) is None
    read, params = man.reader("moe_max_over_mean_pairs.keye")
    assert params == {"held_key": "num_local_experts"}
    obs, _ = _obs(man, {"moe_pairs": 1600, "moe_max_pairs": 150})
    assert read(obs, params) == pytest.approx(16 * 150 / 1600)
    assert read(_obs(man, {})[0], params) is None
    read, params = man.reader("moe_ffn_roofline.keye")
    assert params == {"needles": ["%ragged-dot"], "width_key": "moe_intermediate_size"}
    assert read(_obs(man, {"traced_moe_calls": []}, OPS)[0], params) is None


def test_driver_replaces_five_functions_and_refuses_a_program_without_the_indexer(man):
    from odbench import traffic

    cell = man.cell(CELL)
    assert set(cell.traffic) == {"kind", "prompt_tokens", "output_tokens"}
    driver, loop, _ = _driver_with_fake_loop(man)
    for name in ("start", "warm_up", "snapshot", "traced_stretch"):
        assert getattr(loop.serve_cell, name) is getattr(driver, name), name
    assert loop.serve_cell.tails is driver.serve_cell.tails  # the rest is shared
    assert loop.POOL == 256 == max(256, driver.POOL_TOKENS // 16_384)
    # the traffic: ISSUE 49's, and every request inside its slot's ring of whole chunks
    engine = cell.options["engine"]
    assert engine == {"num_slots": 12, "max_context": 16_896, "prefill_buckets": []}
    assert engine["max_context"] % cell.config["sa_config"]["q_chunk_size"] == 0
    reqs = traffic.requests(cell.traffic, 256, cell.config["vocab_size"], 2147483659)
    lens = np.array([len(a.prompt) for a in reqs])
    # ISSUE 49's traffic as named (its third fallback, 14,336-16,384, was tried and not taken)
    assert cell.traffic["prompt_tokens"] == {"dist": "uniform", "min": 12_288, "max": 16_384}
    assert lens.min() >= 12_288 and lens.max() <= 16_384 and {a.max_new_tokens for a in reqs} == {256}
    assert lens.min() < 12_340 and lens.max() > 16_340
    assert lens.max() + 256 <= engine["max_context"]  # nothing wraps
    assert lens.min() > cell.config["sa_config"]["topk"]  # every step selects
    assert max(max(a.prompt) for a in reqs[:8]) < cell.config["vocab_size"]
    check = cell.options["check"]
    assert check["prompt_tokens"][0] % 512 and check["prompt_tokens"][1] % 512
    assert max(check["prompt_tokens"]) + check["decode_steps"] <= check["pad_to"] <= engine["max_context"]
    # a program that reads no sa_config (the parent): refused before anything is built
    import opendiloco_tpu.models.llama as llama

    class Old:
        @staticmethod
        def from_dict(raw):
            return types.SimpleNamespace()

    class Older:
        @staticmethod
        def from_dict(raw):
            raise TypeError("unexpected keyword 'sa_config'")

    report = types.SimpleNamespace(line=lambda what, **kw: None)
    real = llama.LlamaConfig
    try:
        for old in (Old, Older):
            llama.LlamaConfig = old
            with pytest.raises(RuntimeError, match=f"cannot run {CONFIG}.*no learned sparse attention"):
                driver.start(cell, None, 0, 0, report, 0.0)
    finally:
        llama.LlamaConfig = real
    assert 0 < driver.LOGITS_REL_L2 <= 6e-2 and 0 < driver.CHOICE_MARGIN < 1


def test_sets_as_rows_and_the_exchange(man):
    driver = man.driver("closed_loop_keye")
    chosen = np.zeros((2, 3, 10), bool)
    chosen[0, 0, [1, 4, 7]] = True
    chosen[1, 2, [0, 9]] = True
    rows = driver.sets_as_rows(chosen, 3)
    assert rows.shape == (2, 3, 3) and rows[0, 0].tolist() == [1, 4, 7]
    assert rows[1, 2].tolist() == [0, 9, -1] and rows[0, 1].tolist() == [-1, -1, -1]
    cell = man.cell(CELL)
    out = driver.exchanged(
        cell, [np.array([[0, 2], [1, 0]]), np.array([[0, 0], [0, 0]])],
        [np.array([[0.0, 0.02], [0.3, 0.0]]), np.zeros((2, 2))],
    )
    assert out["sets_compared"] == 8 and out["sets_differing"] == 2 and out["rows_differing"] == 3
    assert out["largest_exchange_distance"] == pytest.approx(0.3)
    assert out["median_exchange_distance"] == pytest.approx(0.0)
    assert out["median_exchange_distance_by_layer"] == [0.0, 0.0]
    assert out["rows_differing_share"] == pytest.approx(3 / (8 * 2048))
    assert out["median_exchange_distance_by_prompt"] == [0.01, 0.0]
    assert out["median_exchange_distance_last_tokens"] == pytest.approx(0.0)  # rows 0: [0, .02], [0, 0]
    assert out["median_exchange_distance_decode_steps"] == pytest.approx(0.0)
    assert out["rows_differing_share_by_layer"] == [round(1 / 4 / 2048, 5), round(2 / 4 / 2048, 5)]


@pytest.mark.parametrize("where, not_met", [
    (None, []),
    ("logits", ["logits_rel_l2"]),
    ("every set", ["median_exchange_distance", "median_exchange_distance_of_a_prompt",
                   "median_exchange_distance_last_tokens", "median_exchange_distance_decode_steps",
                   "rows_differing_share_of_a_layer"]),
    # (and half of the two last tokens' sets, whose median then lies half way)
    ("one prompt", ["median_exchange_distance_of_a_prompt", "median_exchange_distance_last_tokens"]),
    ("last tokens", ["median_exchange_distance_last_tokens"]),
    ("one layer", ["rows_differing_share_of_a_layer"]),
])
def test_the_verdict_holds_each_part_of_the_sets_by_itself(man, where, not_met):
    """Two prompts of 9 rows compared over 16 layers, as the cell's check has
    them, with sets exchanged far apart in a part of them alone: the median
    over all the sets sees a fault in every set and none confined to the
    prompt with fewer sets, to the prompts' last tokens or to one layer; the
    limit that holds that part does."""
    driver, cell = man.driver("closed_loop_keye"), man.cell(CELL)
    rows = (9, 5)  # the second prompt's sets are fewer than half of all
    differing = [np.full((r, 16), 40) for r in rows]  # 2% of 2,048 rows: bfloat16's near-ties
    distance = [np.full((r, 16), 0.1) for r in rows]
    far, many = 10 * driver.CHOICE_MARGIN, int(2 * driver.LAYER_ROWS_DIFFERING * 2048)
    if where == "every set":
        distance, differing = [d + far for d in distance], [d * 0 + many for d in differing]
    elif where == "one prompt":
        distance[1] += far
    elif where == "last tokens":
        for d in distance:
            d[0] += far
    elif where == "one layer":
        for d in differing:
            d[:, 11] = many
    rel = 2 * driver.LOGITS_REL_L2 if where == "logits" else driver.LOGITS_REL_L2 / 3
    ok, tolerance, failed = driver.verdict(rel, driver.exchanged(cell, differing, distance))
    assert failed == not_met and ok == (not not_met)
    assert set(tolerance) >= set(failed) and len(tolerance) == 6
    assert driver.verdict(float("nan"), driver.exchanged(cell, differing, distance))[2][0] == "logits_rel_l2"


def test_reference_sees_the_faults_on_the_tiny_preset(man):
    """The rehearsal's configuration (a share of 4 of 8 experts) through the
    reference: each broken equation moves the logits by far more than the
    driver's limit; the rows wanted are the rows of the whole."""
    import jax

    from odbench import reference_keye
    from opendiloco_tpu.models.llama import LlamaConfig, init_params

    cfg = man.cell(CELL, rehearse=True).config
    params = init_params(jax.random.key(0), LlamaConfig.from_dict(cfg))
    assert params["layers"]["gate_proj"].shape[1] == 4 and cfg["num_experts"] == 8
    ids = np.random.default_rng(0).integers(3, cfg["vocab_size"], (1, 72))
    sound = np.asarray(reference_keye.forward(params, ids, cfg))
    rel = lambda a, b: float(np.linalg.norm(a - b) / np.linalg.norm(b))
    driver = man.driver("closed_loop_keye")
    for fault in ("no_relu", "first_rows", "drop_rows", "chunk_blind"):
        broken = np.asarray(reference_keye.forward(params, ids, cfg, faults=(fault,)))
        assert rel(broken[:, 20:], sound[:, 20:]) > driver.LOGITS_REL_L2, fault
        assert rel(broken[:, :6], sound[:, :6]) < 1e-6, fault  # six rows in the first chunk: nothing to break
    part = np.asarray(reference_keye.forward(params, ids, cfg, rows=(40, 7)))
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
    assert out["failed"] == 0 and out["correct"]
    check = by_what["check"]
    assert check["ok"] and check["reference"] == "reference_keye"
    assert check["rows_compared"] == 2 * 7 and check["sets_compared"] == 2 * 7 * 3
    assert check["prefill_chunks"] == 8 + 6 and check["prefill_chunk_tokens"] == 62 + 41
    assert check["median_exchange_distance"] <= check["tolerance"]["median_exchange_distance"]
    assert check["limits_not_met"] == [] and len(check["tolerance"]) == 6
    assert len(check["rows_differing_share_by_layer"]) == 3 and len(check["median_exchange_distance_by_prompt"]) == 2
    assert check["median_exchange_distance"] <= check["largest_exchange_distance"]
    keye = by_what["keye"]
    assert keye["experts_held"] == 4 and keye["experts"] == 8 and keye["chunk"] == 8 and keye["topk"] == 12
    assert keye["index_cache_resident_bytes"] == keye["ring_bytes_by_shapes"]["index"] == 3 * 4 * 128 * 8 * 2
    assert keye["kv_ring_bytes"] == keye["ring_bytes_by_shapes"]["kv"]
    assert by_what["built"]["prefill_buckets"] == []
    assert {m["name"] for m in man.end_to_end(CELL)} <= set(out["metrics"])
    assert "tpot_p95_ms" not in out["metrics"] and "tpot_p95_ms" in by_what["tails"]
    window = by_what["window_counters"]
    assert window["prefill_chunks"] > 0 and window["dsa_rows_scored"] > window["dsa_rows_selected"] > 0
    assert window["moe_pairs_all"] > window["moe_pairs"] > 0  # the other chips' experts' pairs add nothing here
    assert 0.2 < window["chunks_per_step"] < 1.2  # a chunk an iteration, between two steps
    assert by_what["window"]["compiles_in_window"] == 0
    if trace:
        # no peak on the CPU: the roofline shares are left out, the rest is there
        assert {"prefill_ms.videoqa", "moe_max_over_mean_pairs.keye"} <= set(out["metrics"])
        assert not {"dsa_index_roofline.serve", "dsa_attn_roofline.serve"} & set(out["metrics"])
        traced = by_what["traced_dsa"]
        assert traced["calls"] > traced["chunks"] > 0 and traced["rows_scored"] > traced["rows_selected"] > 0
        assert all(traced["instructions_named"][scope] > 0
                   for scope in ("odtp_dsa_index", "odtp_dsa_attn", "odtp_serve_prefill"))
        assert by_what["traced_routed"]["pairs"] > 0
        assert by_what["traced"]["compiles_in_trace"] == 0
