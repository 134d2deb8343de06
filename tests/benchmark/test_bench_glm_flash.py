"""The GLM-4.7-Flash configuration's part of the benchmark (PR 32): its file
against the catalog's numbers, its cost functions by hand, its readers on
synthetic observations, its reference against the program's forward on the
cell's tiny preset, and the cell's rehearsal end to end."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from odbench import costs, costs_glm_flash, manifest, peaks, reference_glm_flash

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
CELL = "serve-glm-flash-agent"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
# the catalog row's config (model-configs guide, GLM-4.7-Flash), its numbers
PUBLISHED = {
    "hidden_size": 2048, "intermediate_size": 10240, "max_position_embeddings": 202752,
    "moe_intermediate_size": 1536, "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1, "routed_scaling_factor": 1.8,
    "num_experts_per_tok": 4, "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rms_norm_eps": 1e-05, "rope_theta": 1000000, "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880,
}
OTHER = {"attention_bias": False, "hidden_act": "silu", "model_type": "glm4_moe_lite",
         "topk_method": "noaux_tc", "norm_topk_prob": True, "rope_scaling": None,
         "tie_word_embeddings": False}
NEW_METRICS = {"prefill_ms.agent", "mla_attn_roofline.serve", "moe_ffn_roofline.glm",
               "moe_max_over_mean_pairs.glm"}


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(REPO, BENCH)


@pytest.mark.parametrize("cell,per_layer", [
    (CELL, {"decode_step_ms", "device_idle_share.serve", *NEW_METRICS}),
    ("serve-granite-h-docqa", {
        "decode_step_ms", "device_idle_share.serve", "prefill_ms.docqa", "ssm_mixer_roofline.serve",
        "moe_ffn_roofline.serve", "paged_attn_roofline.granite", "moe_max_over_mean_pairs.held"}),
])
def test_manifest_is_sound_with_the_cells(man, cell, per_layer):
    """Properties, none of a moment: this cell's entries, and what
    ``test_bench_granite_hybrid`` holds of the granite cell's beside "it is
    the manifest's last entry" (true at PR 30 alone; that test fails from this
    cell on and is a ``benchmark`` PR's to mend, PERF.md section 7). A later
    cell or metric joins without touching any of this."""
    assert manifest.problems(man) == []
    entry = next(w for w in man.raw["workloads"] if w["name"] == cell)
    conf = next(c for c in man.raw["configs"] if c["name"] == entry["config"])
    assert entry["chips"] == 1 and len(entry["why"]) <= 200 and len(conf["why"]) <= 200
    assert {m["name"] for m in man.end_to_end(cell)} >= {"tpot_p95_ms", "serve_tokens_per_s", "setup_s"}
    assert {m["name"] for m in man.per_layer(cell)} >= per_layer
    for m in [*man.raw["end_to_end"], *man.raw["per_layer"]]:
        names = m.get("workloads", [])
        assert len(set(names)) == len(names)


# what the benchmark held when this cell joined it (PR 31's manifest): cell -> its metrics
HELD_BEFORE = {
    "train-360m-h16": {"train_tokens_per_s_per_chip", "boundary_ms", "inner_step_ms", "inner_mfu",
                       "flash_attn_roofline.train", "device_idle_share.train", "boundary_d2h_ms",
                       "boundary_allreduce_ms", "boundary_apply_ms"},
    "serve-360m-batch": {"tpot_p95_ms", "serve_tokens_per_s", "decode_step_ms",
                         "paged_attn_roofline.serve", "device_idle_share.serve", "prefill_ms.batch"},
    "train-1.7b-fsdp4-h8": {"train_tokens_per_s_per_chip", "boundary_ms", "inner_step_ms", "inner_mfu",
                            "device_idle_share.train", "boundary_d2h_ms", "boundary_allreduce_ms",
                            "boundary_apply_ms"},
    "serve-olmoe-fewshot": {"tpot_p95_ms", "serve_tokens_per_s", "decode_step_ms",
                            "device_idle_share.serve", "moe_ffn_roofline.serve", "moe_max_over_mean_pairs",
                            "prefill_ms.fewshot", "paged_attn_roofline.olmoe"},
}


def test_the_manifest_gained_entries_and_lost_none(man):
    raw = man.raw
    cells = [w["name"] for w in raw["workloads"]]
    assert CELL in cells and set(HELD_BEFORE) <= set(cells) and len(set(cells)) == len(cells)
    assert "glm-4.7-flash" in {c["name"] for c in raw["configs"]}
    # at most a quarter of the cells, and always one, may take four chips
    assert sum(w["chips"] == 4 for w in raw["workloads"]) <= max(1, len(cells) // 4)
    for cell, metrics in HELD_BEFORE.items():  # no list lost a name
        have = {m["name"] for m in [*man.end_to_end(cell), *man.per_layer(cell)]}
        assert metrics <= have, (cell, metrics - have)
    by_name = {p["name"]: p for p in raw["per_layer"]}
    assert len(by_name) == len(raw["per_layer"])
    for name in NEW_METRICS:
        p = by_name[name]
        assert CELL in p["workloads"] and p["moves"] == "tpot_p95_ms"
        assert set(p) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    # the existing routed-FFN metrics read a key this configuration's file has not
    # (``intermediate_size`` as the experts' width, ``num_local_experts``): the cell
    # has metrics of its own there and stays off theirs
    for name in ("moe_ffn_roofline.serve", "moe_max_over_mean_pairs", "moe_max_over_mean_pairs.held"):
        assert CELL not in by_name[name]["workloads"]
    assert by_name["mla_attn_roofline.serve"]["layer"] == by_name["paged_attn_roofline.serve"]["layer"]
    assert by_name["moe_max_over_mean_pairs.glm"]["layer"] == by_name["moe_max_over_mean_pairs.held"]["layer"]


def test_configuration_file_holds_the_published_numbers(man):
    cell = man.cell(CELL)
    cfg = cell.config
    entry = next(c for c in man.raw["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert entry["source"] == cfg["source"] == "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    for key, value in OTHER.items():
        assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"], cfg["vocab_size"]) == (24, 8, 19360)
    assert cfg["num_experts"] == 64 and cfg["first_local_expert"] == 0  # the router whole
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"] and cfg["n_routed_experts"] * 8 == 64
    # inside the guide's floors: four expert layers behind the dense one, 8 experts, an eighth
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert any("prediction module" in line for line in cfg["assumed"])
    assert cell.chips == 1 and cell.traffic["kind"] == "closed_loop_glm_flash"
    assert cell.traffic["prompt_tokens"] == {"dist": "uniform", "min": 512, "max": 1792}
    assert cell.traffic["output_tokens"] == {"dist": "const", "value": 256}
    engine = cell.options["engine"]
    assert engine["max_context"] == 2048 and engine["prefill_buckets"] == [768, 1280, 1792]
    assert engine["num_slots"] in (64, 48) and engine["max_context"] % 128 == 0
    assert 1792 + 256 <= engine["max_context"]
    check = cell.options["check"]
    assert check["prompt_tokens"] == [900, 1700] and check["decode_steps"] == 8
    assert max(check["prompt_tokens"]) + check["decode_steps"] + 1 <= check["pad_to"]


def test_parameter_counts_by_hand(man):
    cfg = man.cell(CELL).config
    counted = cfg["parameters"]
    attention = (2048 * 768 + 768 + 768 * 5120 + 2048 * 576 + 512 + 512 * 8960 + 5120 * 2048)
    assert costs_glm_flash.latent_attention_param_count(cfg) == attention == 21_759_232
    assert attention == counted["latent_attention"]
    expert = 3 * 2048 * 1536
    assert costs_glm_flash.expert_param_count(cfg) == expert == 9_437_184 == counted["one_expert_routed_or_shared"]
    rest = attention + 2048 * 64 + 64 + expert + 2 * 2048
    assert costs_glm_flash.expert_layer_rest_param_count(cfg) == rest == 31_331_648
    assert rest == counted["expert_layer_outside_its_routed_experts"]
    dense = attention + 3 * 2048 * 10240 + 2 * 2048
    assert costs_glm_flash.dense_layer_param_count(cfg) == dense == 84_677_888 == counted["dense_layer"]
    as_run = 2 * 19360 * 2048 + 2048 + dense + 23 * (rest + 8 * expert)
    assert costs_glm_flash.param_count(cfg) == as_run == 2_621_048_256 == counted["as_run"]
    assert counted["as_run_bytes_bf16"] == 2 * as_run == 5_242_096_512
    whole = {**cfg, **cfg["published"], "num_experts": 64}
    assert costs_glm_flash.param_count(whole) == 29_943_393_920
    assert counted["published_without_prediction_module"] == 29_943_393_920
    assert counted["embedding_and_head_whole"] == 2 * 154880 * 2048 == 634_388_480
    assert costs_glm_flash.latent_row_dim(cfg) == 576
    assert costs_glm_flash.latent_bytes_per_token(cfg) == 24 * 576 * 2 == counted["latent_ring_bytes_per_token"]
    # the program draws exactly these leaves
    from opendiloco_tpu.models.llama import LlamaConfig

    assert LlamaConfig.from_dict(cfg).num_params() == counted["as_run"]


def test_cost_functions_by_hand(man):
    cfg = man.cell(CELL).config
    # a step of 64 slots with 1,280 live rows each, 24 layers: the span's latent_rows
    layer_rows = 24 * 64 * 1280
    flops, nbytes = costs_glm_flash.mla_decode_cost(cfg, layer_rows, 64)
    assert flops == 2.0 * layer_rows * 20 * (576 + 512)
    rows_once = layer_rows * 576 * 2  # 1,152 B a row and layer, once
    assert nbytes == rows_once + 24 * 64 * (20 * 576 + 20 * 512 + 576) * 2
    assert 2.2e9 < rows_once < 2.4e9  # the issue's "2.3 GB of latent rows a step"
    assert 35 < flops / nbytes < 38  # FLOPs a byte: memory-bound on a v5e (ridge 240)
    # 256 pairs of a step in each of 23 expert layers, every held expert hit
    flops, nbytes = costs_glm_flash.routed_ffn_cost(cfg, 23 * 32, 23 * 8)
    assert flops == 2.0 * 3 * 23 * 32 * 2048 * 1536
    assert nbytes == 23 * 8 * 3 * 2048 * 1536 * 2 + 23 * 32 * 2 * 2048 * 2


def _obs(man, counters, ops=None):
    lines = []
    report = types.SimpleNamespace(line=lambda what, **kw: lines.append((what, kw)))
    obs = {"counters": counters, "cell": man.cell(CELL), "peak": peaks.peak("TPU v5 lite"),
           "report": report}
    if ops is not None:
        obs["trace"] = {"ops": {"/device:TPU:0": ops}, "busy_s": 1.0, "window_s": 2.0}
    return obs, lines


OPS = [
    ["%odtp_mla_decode_attn.12 custom-call:tpu_custom_call", 0.0, 7e6, "bf16[64,20,512]"],
    ["%odtp_mla_decode_attn.12 custom-call:tpu_custom_call", 10e6, 8e6, "bf16[64,20,512]"],
    ["%ragged-dot-none.1 custom-call:tpu_custom_call", 20e6, 3e6, "bf16[256,1536]"],
    ["%ragged-dot-none.2 custom-call:tpu_custom_call", 24e6, 3e6, "bf16[256,2048]"],
    ["%fusion.3 fusion", 28e6, 9e6, "bf16[64,2048]"],
]


def test_mla_attn_roofline_reader(man):
    read, params = man.reader("mla_attn_roofline.serve")
    calls = [[24 * 64 * 1280, 64], [24 * 60 * 1000, 60]]  # two traced steps
    obs, lines = _obs(man, {"traced_mla_calls": calls}, OPS)
    cfg, peak = obs["cell"].config, obs["peak"]
    least = sum(costs.roofline_seconds(*costs_glm_flash.mla_decode_cost(cfg, r, s), peak)[0]
                for r, s in calls)
    want = 100.0 * least / 15e-3  # the kernel's two events: 15 ms
    assert read(obs, params) == pytest.approx(want) and 0 < want < 100
    (what, line), = lines
    assert what == "mla_attn_roofline" and line["kernel_events"] == 2
    assert line["steps_by_bound"] == {"compute": 0, "memory": 2}
    # nothing to read: a program whose spans carry no latent_rows; no event of
    # the kernel's name; no trace; no peak
    assert read(_obs(man, {"traced_mla_calls": []}, OPS)[0], params) is None
    assert read(_obs(man, {}, OPS)[0], params) is None
    assert read(_obs(man, {"traced_mla_calls": calls}, OPS[2:])[0], params) is None
    assert read(_obs(man, {"traced_mla_calls": calls})[0], params) is None
    assert read({"counters": {"traced_mla_calls": calls}, "cell": obs["cell"], "peak": None,
                 "trace": obs["trace"]}, params) is None


def test_routed_reader_takes_the_experts_width_from_the_metric_file(man):
    from odbench import costs_olmoe, costs_routed

    read, params = man.reader("moe_ffn_roofline.glm")
    assert params == {"needles": ["%ragged-dot"], "width_key": "moe_intermediate_size"}
    calls = [[23 * 32, 23 * 8], [23 * 4600, 23 * 8]]  # a step, a prefill of 1,150 tokens
    obs, lines = _obs(man, {"traced_moe_calls": calls}, OPS)
    cfg, peak = obs["cell"].config, obs["peak"]
    least = sum(costs.roofline_seconds(*costs_glm_flash.routed_ffn_cost(cfg, p, e), peak)[0]
                for p, e in calls)
    assert read(obs, params) == pytest.approx(100.0 * least / 6e-3)
    what, line = lines[0]
    assert what == "routed_ffn_roofline" and line["kernel_events"] == 2 and line["width"] == 1536
    # one count under two keys: the OLMoE module's is this one at ``intermediate_size``,
    # which here is the dense layer's width, 6.7 times the work
    assert costs_olmoe.routed_ffn_cost(cfg, 100, 8) == costs_routed.routed_ffn_cost(
        cfg, 100, 8, "intermediate_size")
    assert costs_olmoe.routed_ffn_cost(cfg, 100, 8)[0] == pytest.approx(
        costs_glm_flash.routed_ffn_cost(cfg, 100, 8)[0] * 10240 / 1536)
    # with the other key the same reader reads the OLMoE cell as its own reader does
    other, theirs = man.cell("serve-olmoe-fewshot"), man.reader("moe_ffn_roofline.serve")[0]
    ours = read({**_obs(man, {"traced_moe_calls": calls}, OPS)[0], "cell": other},
                {**params, "width_key": "intermediate_size"})
    assert ours == pytest.approx(theirs({**_obs(man, {"traced_moe_calls": calls}, OPS)[0], "cell": other},
                                        {"needles": params["needles"]}))
    # a cell whose file has no such key, or a program without the counters: nothing, nothing raised
    assert read({**obs, "cell": other}, params) is None
    assert read(_obs(man, {}, OPS)[0], params) is None
    assert read(_obs(man, {"traced_moe_calls": calls})[0], params) is None


def test_routing_imbalance_reader_counts_the_held_experts(man):
    read, params = man.reader("moe_max_over_mean_pairs.glm")
    assert params == {"held_key": "n_routed_experts"}
    obs, _ = _obs(man, {"moe_pairs": 8000, "moe_max_pairs": 1500})
    assert read(obs, params) == pytest.approx(8 * 1500 / 8000)  # 8 held, not the router's 64
    # with granite's key it is granite's reader
    other, theirs = man.cell("serve-granite-h-docqa"), man.reader("moe_max_over_mean_pairs.held")[0]
    assert read({**obs, "cell": other}, {"held_key": "num_local_experts"}) == theirs({**obs, "cell": other}, {})
    # a program without the counters (the parent), or a cell without the key: nothing
    assert read(_obs(man, {})[0], params) is None
    assert read({**obs, "cell": other}, params) is None


def test_data_only_metric_and_the_joined_ones(man):
    read, params = man.reader("prefill_ms.agent")
    assert read({"counters": {"prefill_s": 3.3, "admissions": 60}}, params) == pytest.approx(55.0)
    assert read({"counters": {}}, params) is None
    for name in NEW_METRICS:
        spec = man.metric_file(name)
        assert spec["name"] == name and spec["moves"] == "tpot_p95_ms"
    assert man.metric_file("mla_attn_roofline.serve")["params"]["needles"] == ["odtp_mla_decode_attn"]


def test_driver_replaces_four_functions_and_refuses_a_program_without_latent_attention(man):
    from odbench import traffic

    cell = man.cell(CELL)
    assert set(cell.traffic) == {"kind", "prompt_tokens", "output_tokens"}
    driver = man.driver("closed_loop_glm_flash")
    assert not hasattr(driver, "requests") and not hasattr(driver, "traffic")
    seen = {}
    loop = types.SimpleNamespace(run=lambda **kw: seen.update(kw) or "ran")
    load, manifest.load_module = manifest.load_module, lambda path: loop
    try:
        assert driver.run(report=None) == "ran"
    finally:
        manifest.load_module = load
    assert loop.serve_cell.snapshot is driver.snapshot
    assert loop.serve_cell.traced_stretch is driver.traced_stretch
    assert loop.serve_cell.warm_up is driver.serve_cell.warm_up  # the rest is shared
    reqs = traffic.requests(cell.traffic, 8192, cell.config["vocab_size"], 2147483659)
    lens = np.array([len(a.prompt) for a in reqs])
    assert lens.min() == 512 and lens.max() == 1792 and {a.max_new_tokens for a in reqs} == {256}
    assert max(max(a.prompt) for a in reqs[:64]) < 19360  # ids from the slice
    # a program that reads no kv_lora_rank (the parent): refused before anything is built
    import opendiloco_tpu.models.llama as llama

    class Old:
        @staticmethod
        def from_dict(raw):
            return types.SimpleNamespace()

    real, llama.LlamaConfig = llama.LlamaConfig, Old
    try:
        with pytest.raises(RuntimeError, match="cannot run glm-4.7-flash.*no latent attention"):
            driver.start(cell, None, 0, 0, None, 0.0)
    finally:
        llama.LlamaConfig = real
    assert 0 < driver.LOGITS_REL_L2 < 0.5


def test_reference_agrees_with_the_programs_forward_on_the_tiny_preset(man):
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.models.llama import LlamaConfig, causal_lm_loss, forward, init_params

    raw = man.cell(CELL, rehearse=True).config
    cfg = LlamaConfig.from_dict(raw)
    assert cfg.latent and cfg.layer_kinds == ("dense", "attention", "attention", "attention")
    assert (cfg.num_experts, cfg.num_local_experts, cfg.first_local_expert) == (16, 8, 8)
    params = init_params(jax.random.key(3), cfg)
    params["layers"]["attention"]["router"] = params["layers"]["attention"]["router"] * 25.0
    ids = jax.random.randint(jax.random.key(4), (2, 24), 0, cfg.vocab_size)
    want = forward(params, ids, cfg, compute_dtype=jnp.float32, remat=False)
    got = jax.jit(lambda p, i: reference_glm_flash.forward(p, i, raw))(params, ids)
    # float32 both: only the order of accumulation differs
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-6)
    loss = jax.jit(lambda p, i: reference_glm_flash.loss(p, i, i, raw))(params, ids)
    np.testing.assert_allclose(float(loss), float(causal_lm_loss(want, ids)), rtol=1e-5)
    # causal in every layer: a later token changes no earlier logit
    other = np.asarray(ids).copy()
    other[:, 16:] = 7
    again = jax.jit(lambda p, i: reference_glm_flash.forward(p, i, raw))(params, other)
    np.testing.assert_array_equal(np.asarray(again)[:, :16], np.asarray(got)[:, :16])
    # below the stated precision the reference moves by orders of magnitude more
    low = jax.jit(lambda p, i: reference_glm_flash.forward(p, i, raw, jnp.float8_e4m3fn))(params, ids)
    assert float(jnp.linalg.norm(low - got) / jnp.linalg.norm(got)) > 0.05


@pytest.mark.parametrize("trace", [0, 2])
def test_rehearsal_of_the_cell(man, trace):
    env = {k: v for k, v in os.environ.items() if k not in ("ODTP_OBS", "ODTP_REQTRACE_CAP")}
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed",
         "2147483659", "--seconds", "3", "--rehearse", "--trace", str(trace)],
        capture_output=True, text=True, timeout=600, env={**env, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    by_what = {x.get("what", "result"): x for x in
               (json.loads(line) for line in proc.stdout.splitlines() if line.startswith("{"))}
    out = by_what["rehearsal"]
    assert "result" not in by_what and "fault" not in by_what and out["correct"] is True
    assert by_what["check"]["reference"] == "reference_glm_flash" and by_what["check"]["ok"]
    latent = by_what["latent"]
    assert latent["experts_held"] == 8 and latent["experts"] == 16 and latent["row_values"] == 24
    assert latent["latent_cache_resident_bytes"] == 4 * 4 * 64 * 24 * 2  # layers, slots, rows, a row
    assert {m["name"] for m in man.end_to_end(CELL)} <= set(out["metrics"])
    window = by_what["window_counters"]
    assert window["latent_rows_read"] > 0 and window["latent_bytes_moved"] > 0
    assert window["latent_cache_resident_bytes"] == latent["latent_cache_resident_bytes"]
    assert 0 < window["moe_pairs"] < window["moe_pairs_all"]
    assert by_what["window"]["compiles_in_window"] == 0
    if trace:
        # no peak on the CPU: the roofline shares are left out, the rest is there
        assert {"decode_step_ms", "device_idle_share.serve", "prefill_ms.agent",
                "moe_max_over_mean_pairs.glm"} <= set(out["metrics"])
        assert by_what["traced_latent"]["decode_steps"] > 0 and by_what["traced_latent"]["latent_rows"] > 0
        assert by_what["traced_routed"]["calls"] > by_what["traced_latent"]["decode_steps"]
        assert by_what["traced"]["compiles_in_trace"] == 0
