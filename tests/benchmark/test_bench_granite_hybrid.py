"""The granite-4.0-h configuration's part of the benchmark (PR 30): its file
against the catalog's numbers, its cost functions by hand, its reader on
synthetic observations, the driver's reading of a compiled program's text, its
reference against the program's forward on the cell's tiny preset, and the
cell's rehearsal end to end."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from odbench import costs_granite_h, manifest, peaks, reference_granite_h

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
CELL = "serve-granite-h-docqa"
REDUCED = ["num_hidden_layers", "num_local_experts", "vocab_size"]
# the catalog row's config (model-configs guide, granite-4.0-h-small), its numbers
PUBLISHED = {
    "attention_multiplier": 0.0078125, "embedding_multiplier": 12, "hidden_size": 4096,
    "intermediate_size": 768, "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 128, "max_position_embeddings": 131072, "num_attention_heads": 32,
    "num_experts_per_tok": 10, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "shared_intermediate_size": 1536, "vocab_size": 100352,
}


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(REPO, BENCH)


def test_manifest_is_sound_with_the_cell(man):
    assert manifest.problems(man) == []
    entry = next(w for w in man.raw["workloads"] if w["name"] == CELL)
    conf = next(c for c in man.raw["configs"] if c["name"] == entry["config"])
    assert entry["chips"] == 1 and len(entry["why"]) <= 200 and len(conf["why"]) <= 200
    assert man.raw["workloads"][-1] == entry and man.raw["configs"][-1] == conf  # appended
    assert {m["name"] for m in man.end_to_end(CELL)} == {"tpot_p95_ms", "serve_tokens_per_s", "setup_s"}
    assert {m["name"] for m in man.per_layer(CELL)} == {
        "decode_step_ms", "device_idle_share.serve", "prefill_ms.docqa", "ssm_mixer_roofline.serve",
        "moe_ffn_roofline.serve", "paged_attn_roofline.granite", "moe_max_over_mean_pairs.held"}
    # a list that gained the cell gained it at its end and kept the rest
    for m in [*man.raw["end_to_end"], *man.raw["per_layer"]]:
        if CELL in m.get("workloads", []):
            assert m["workloads"][-1] == CELL and len(set(m["workloads"])) == len(m["workloads"])


def test_configuration_file_holds_the_published_numbers(man):
    cell = man.cell(CELL)
    cfg = cell.config
    entry = next(c for c in man.raw["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_local_experts"], cfg["vocab_size"]) == (10, 9, 12544)
    assert cfg["num_experts"] == 72 and cfg["first_local_expert"] == 0  # the router whole
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"] and cfg["num_local_experts"] * 8 == 72
    # the pattern whole, as published: period 10, attention at 5, 15, 25, 35
    kinds = cfg["layer_types"]
    assert len(kinds) == 40 and [i for i, k in enumerate(kinds) if k == "attention"] == [5, 15, 25, 35]
    assert costs_granite_h.layer_kinds(cfg) == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert cfg["model_type"] == "granitemoehybrid" and cfg["position_embedding_type"] == "nope"
    assert cfg["tie_word_embeddings"] is True and cfg["mamba_conv_bias"] and not cfg["mamba_proj_bias"]
    assert cell.chips == 1 and cell.traffic["kind"] == "closed_loop_granite_h"
    assert cell.traffic["prompt_tokens"] == {"dist": "uniform", "min": 256, "max": 2048}
    assert cell.traffic["output_tokens"] == {"dist": "const", "value": 64}
    rows = cell.options["engine"]["max_context"]
    assert rows % 128 == 0 and rows - 128 < 2048 + 64 <= rows
    assert cell.options["engine"]["prefill_buckets"] == [512, 1024, 1536, 2048]
    assert cell.options["engine"]["num_slots"] in (32, 24, 16)
    assert cell.options["check"]["prompt_tokens"] == [700, 1800]


def test_parameter_counts_by_hand(man):
    cfg = man.cell(CELL).config
    counted = cfg["parameters"]
    mixer = (4096 * (8192 + 8448 + 128) + 8448 * 4 + 8448 + 3 * 128 + 8192 + 8192 * 4096)
    assert costs_granite_h.mamba_mixer_param_count(cfg) == mixer == 102_286_976 == counted["mamba_mixer"]
    attention = 2 * 4096 * 4096 + 2 * 4096 * 1024
    assert costs_granite_h.attention_mixer_param_count(cfg) == attention == counted["attention_mixer"]
    rest = 3 * 4096 * 1536 + 4096 * 72 + 2 * 4096
    assert costs_granite_h.ffn_rest_param_count(cfg) == rest == counted["shared_mlp_router_norms"]
    assert costs_granite_h.expert_param_count(cfg) == 3 * 4096 * 768 == counted["one_expert"]
    as_run = 9 * (mixer + rest + 9 * 9_437_184) + (attention + rest + 9 * 9_437_184) + 12544 * 4096 + 4096
    assert costs_granite_h.param_count(cfg) == as_run == 2_055_031_424 == counted["as_run"]
    whole = {**cfg, **cfg["published"]}
    assert costs_granite_h.param_count(whole) == counted["published"] == 32_207_337_984
    # 128 heads x 64 x 128 float32 and three bf16 rows of 8,448 channels, nine layers
    assert costs_granite_h.ssm_state_bytes_per_slot(cfg) == 9 * (128 * 64 * 128 * 4 + 3 * 8448 * 2)
    # the program draws exactly these leaves
    from opendiloco_tpu.models.llama import LlamaConfig

    assert LlamaConfig.from_dict(cfg).num_params() == counted["as_run"]


def test_ssm_mixer_cost_by_hand(man):
    cfg = man.cell(CELL).config
    weights = (4096 * 16768 + 8192 * 4096 + 8448 * 5 + 3 * 128 + 8192) * 2
    per_token = 2 * (4096 * 16768 + 8192 * 4096) + 2 * 4 * 8448 + 2 * 3 * 8192 * 128
    state = 8192 * 128 * 4 + 3 * 8448 * 2
    flops, nbytes = costs_granite_h.ssm_mixer_cost(cfg, tokens=1000, sequences=1, decode=False)
    assert flops == 9 * 1000 * per_token
    assert nbytes == 9 * (weights + 1000 * 2 * 4096 * 2 + state)
    flops, nbytes = costs_granite_h.ssm_mixer_cost(cfg, tokens=32, sequences=32, decode=True)
    assert flops == 9 * 32 * per_token
    assert nbytes == 9 * (weights + 32 * 2 * 4096 * 2 + 32 * 2 * state)


def _obs(man, counters, ops=None):
    lines = []
    report = types.SimpleNamespace(line=lambda what, **kw: lines.append((what, kw)))
    obs = {"counters": counters, "cell": man.cell(CELL), "peak": peaks.peak("TPU v5 lite"),
           "report": report}
    if ops is not None:
        obs["trace"] = {"ops": {"/device:TPU:0": ops}, "busy_s": 1.0, "window_s": 2.0}
    return obs, lines


def test_ssm_mixer_roofline_reader(man):
    from odbench import costs

    read, params = man.reader("ssm_mixer_roofline.serve")
    ops = [
        # a while whose body holds two of the mixer's operations: self time only
        ["%while.3 while", 0.0, 60e6, "(s32[], bf16[32,1,4096]"],
        ["%fusion.20 fusion", 1e6, 20e6, "bf16[32,16768]{1,0:T(8,128)(2,1)} fusion(%p"],
        ["%fusion.21 fusion", 22e6, 15e6, "f32[9,32,128,64,128]{4,3,2,1,0} fusion(%p"],
        # the same name with another shape is another program's: not the mixer's
        ["%fusion.20 fusion", 38e6, 5e6, "bf16[32,768]{1,0} fusion(%q"],
        ["%ragged-dot-none.3 custom-call:tpu_custom_call", 44e6, 4e6, "bf16[320,768]"],
    ]
    named = [["%fusion.20", "bf16[32,16768]"], ["%fusion.21", "f32[9,32,128,64,128]"],
             ["%convolution.2", "bf16[1,2048,8448]"]]
    calls = [[1152, 1, 0], [32, 32, 1]]  # a prefill of 1,152 tokens, a step of 32 slots
    obs, lines = _obs(man, {"traced_ssm_calls": calls, "ssm_ops": named}, ops)
    cfg, peak = obs["cell"].config, obs["peak"]
    least = sum(costs.roofline_seconds(*costs_granite_h.ssm_mixer_cost(cfg, t, s, bool(d)), peak)[0]
                for t, s, d in calls)
    want = 100.0 * least / 0.035  # the two named events: 35 ms
    assert read(obs, params) == pytest.approx(want) and 0 < want < 100
    (what, line), = lines
    assert what == "ssm_mixer_roofline" and line["mixer_events"] == 2
    assert line["calls_by_bound"] == {"compute": 1, "memory": 1}
    # nothing to read: a parent's spans carry no ssm_tokens; no instruction named;
    # no event of those names; no trace; no peak
    assert read(_obs(man, {"traced_ssm_calls": [], "ssm_ops": named}, ops)[0], params) is None
    assert read(_obs(man, {"traced_ssm_calls": calls, "ssm_ops": []}, ops)[0], params) is None
    assert read(_obs(man, {"traced_ssm_calls": calls, "ssm_ops": named}, ops[3:])[0], params) is None
    assert read(_obs(man, {"traced_ssm_calls": calls, "ssm_ops": named})[0], params) is None
    assert read(_obs(man, {})[0], params) is None
    assert read({"counters": {}, "cell": obs["cell"], "peak": None}, params) is None


def test_driver_reads_the_mixers_instructions_from_a_programs_text(man):
    driver = man.driver("closed_loop_granite_h")
    text = """HloModule jit__decode

%fused_computation.5 (p: bf16[4,8]) -> bf16[4,8] {
  %mul.1 = bf16[4,8]{1,0} multiply(%p, %p), metadata={op_name="jit(_decode)/while/body/odtp_ssm/mul"}
}

%region_1.2 (arg: (s32[], bf16[4,8])) -> (s32[], bf16[4,8]) {
  %fusion.7 = bf16[4,8]{1,0:T(8,128)(2,1)} fusion(%x), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(_decode)/while/body/odtp_ssm/mul"}
  %fusion.8 = bf16[4,16]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.6, metadata={op_name="jit(_decode)/while/body/odtp_mlp/mul"}
  ROOT %tuple.3 = (s32[], bf16[4,8]{1,0}) tuple(%i, %fusion.7), metadata={op_name="jit(_decode)/while/body/odtp_ssm/add"}
}

ENTRY %main.9 (p0: bf16[4,8]) -> bf16[4,8] {
  %custom-call.2 = f32[2,4]{1,0} custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(_decode)/odtp_ssm/dot_general"}
}
"""
    inside, outside = driver.top_level_instructions(text)
    assert inside == {("%fusion.7", "bf16[4,8]"), ("%tuple.3", "s32[]"), ("%custom-call.2", "f32[2,4]")}
    assert outside == {("%fusion.8", "bf16[4,16]")}  # the fused computation's own line is neither
    assert driver.top_level_instructions(text, "odtp_mlp")[0] == outside


def test_requests_are_the_harness_generators(man):
    """The issue's mix is the generator's permutation, as in the other
    closed-loop cells: the mix holds the generator's keys alone and the
    driver replaces nothing of ``closed_loop.py`` but four ``serve_cell``
    functions."""
    from odbench import traffic

    cell = man.cell(CELL)
    assert set(cell.traffic) == {"kind", "prompt_tokens", "output_tokens"}
    assert cell.traffic["prompt_tokens"] == {"dist": "uniform", "min": 256, "max": 2048}
    assert cell.traffic["output_tokens"] == {"dist": "const", "value": 64}
    driver = man.driver("closed_loop_granite_h")
    assert not hasattr(driver, "requests") and not hasattr(driver, "traffic")
    seen = {}
    loop = types.SimpleNamespace(run=lambda **kw: seen.update(kw) or "ran")
    load, manifest.load_module = manifest.load_module, lambda path: loop
    try:
        assert driver.run(report=None) == "ran"
    finally:
        manifest.load_module = load
    assert not hasattr(loop, "traffic")  # closed_loop.py keeps its own ``traffic``
    assert {loop.serve_cell.start, loop.serve_cell.snapshot, loop.serve_cell.traced_stretch} == {
        driver.start, driver.snapshot, driver.traced_stretch}
    reqs = traffic.requests(cell.traffic, 8192, cell.config["vocab_size"], 2147483659)
    lens = np.array([len(a.prompt) for a in reqs])
    assert lens.min() == 256 and lens.max() == 2048 and {a.max_new_tokens for a in reqs} == {64}


def test_driver_tells_which_mixer_names_another_program_shares(man):
    driver = man.driver("closed_loop_granite_h")
    decode = """ENTRY %main.1 (p0: bf16[4,8]) -> bf16[4,8] {
  %fusion.7 = bf16[4,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(_decode)/odtp_ssm/mul"}
  %fusion.9 = bf16[4,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.6, metadata={op_name="jit(_decode)/odtp_ssm/add"}
}
"""
    prefill = """ENTRY %main.2 (p0: bf16[4,8]) -> bf16[4,8] {
  %fusion.7 = bf16[4,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(_prefill)/odtp_mlp/mul"}
  %fusion.9 = bf16[16,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.6, metadata={op_name="jit(_prefill)/odtp_mlp/add"}
}
"""
    inside, outside = driver.top_level_instructions(decode)
    assert inside == {("%fusion.7", "bf16[4,8]"), ("%fusion.9", "bf16[4,8]")} and not outside
    # the same name and shape outside the mixers in another program: told apart
    # by nothing a trace's event carries; another shape is another operation
    assert inside & driver.top_level_instructions(prefill)[1] == {("%fusion.7", "bf16[4,8]")}


def test_routed_and_attention_readers_on_the_cell(man):
    """The shared routed FFN and the decode kernel are read on this cell too:
    the grouped matmuls against the held experts' pairs, the kernel against
    the one layer that has a ring, the balance among the 9 held experts."""
    from odbench import costs, costs_olmoe

    cfg = man.cell(CELL).config
    # one attention layer of ten: QK^T and PV over 32 heads, K and V rows of 8 heads, q and o
    flops = 2 * 2.0 * 40000 * 32 * 128
    nbytes = 2 * 40000 * 8 * 128 * 2 + 2 * 32 * 32 * 128 * 2
    ops = [
        ["%odtp_paged_decode_attn.1 custom-call:tpu_custom_call", 0.0, 2e6, "bf16[32,32,128]"],
        ["%ragged-dot-none.3 custom-call:tpu_custom_call", 3e6, 4e6, "bf16[320,768]"],
        ["%ragged-dot-none.4 custom-call:tpu_custom_call", 8e6, 4e6, "bf16[320,4096]"],
    ]
    counters = {"traced_decode_steps": 1, "traced_live_rows": 40000, "traced_live_slots": 32,
                "traced_moe_calls": [[400, 90]], "moe_pairs": 4000, "moe_max_pairs": 800}
    obs, lines = _obs(man, counters, ops)
    peak = obs["peak"]
    read, params = man.reader("paged_attn_roofline.granite")
    want = 100.0 * costs.roofline_seconds(flops, nbytes, peak)[0] / 2e-3
    assert read(obs, params) == pytest.approx(want) and 0 < want < 100
    read, params = man.reader("moe_ffn_roofline.serve")
    least = costs.roofline_seconds(*costs_olmoe.routed_ffn_cost(cfg, 400, 90), peak)[0]
    assert read(obs, params) == pytest.approx(100.0 * least / 8e-3) and least < 8e-3
    read, params = man.reader("moe_max_over_mean_pairs.held")
    assert read(obs, params) == pytest.approx(9 * 800 / 4000)  # 9 held, not the router's 72
    assert [what for what, _ in lines] == ["paged_attn_roofline", "moe_ffn_roofline"]
    # a parent's program: no counters, no spans, nothing to read, nothing raised
    empty, _ = _obs(man, {}, ops)
    for name in ("paged_attn_roofline.granite", "moe_ffn_roofline.serve",
                 "moe_max_over_mean_pairs.held"):
        read, params = man.reader(name)
        assert read(empty, params) is None


def test_data_only_metric_of_the_cell(man):
    read, params = man.reader("prefill_ms.docqa")
    assert read({"counters": {"prefill_s": 3.5, "admissions": 50}}, params) == 70.0
    spec = man.metric_file("ssm_mixer_roofline.serve")
    assert spec["reader"] == "ssm_mixer_roofline" and spec["unit"] == "%"


def test_reference_agrees_with_the_programs_forward_on_the_tiny_preset(man):
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.models.llama import LlamaConfig, causal_lm_loss, forward, init_params

    raw = man.cell(CELL, rehearse=True).config
    cfg = LlamaConfig.from_dict(raw)
    assert cfg.layer_types == ("mamba", "mamba", "attention", "mamba")
    assert (cfg.num_experts, cfg.num_local_experts, cfg.first_local_expert) == (16, 8, 8)
    params = init_params(jax.random.key(3), cfg)
    for stack in params["layers"].values():
        stack["router"] = stack["router"] * 25.0  # spread logits
    ids = jax.random.randint(jax.random.key(4), (2, 24), 0, cfg.vocab_size)
    want = forward(params, ids, cfg, compute_dtype=jnp.float32, remat=False)
    got = jax.jit(lambda p, i: reference_granite_h.forward(p, i, raw))(params, ids)
    # float32 both: only the order of accumulation differs
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-6)
    loss = jax.jit(lambda p, i: reference_granite_h.loss(p, i, i, raw))(params, ids)
    np.testing.assert_allclose(float(loss), float(causal_lm_loss(want, ids)), rtol=1e-5)
    # causal in every layer: a later token changes no earlier logit
    other = np.asarray(ids).copy()
    other[:, 16:] = 7
    again = jax.jit(lambda p, i: reference_granite_h.forward(p, i, raw))(params, other)
    np.testing.assert_array_equal(np.asarray(again)[:, :16], np.asarray(got)[:, :16])


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
    assert by_what["check"]["reference"] == "reference_granite_h" and by_what["check"]["ok"]
    assert by_what["hybrid"]["layers"] == ["mamba", "mamba", "attention", "mamba"]
    assert by_what["hybrid"]["experts_held"] == 8 and by_what["hybrid"]["experts"] == 16
    assert by_what["hybrid"]["ssm_state_resident_bytes"] > 0
    assert {m["name"] for m in man.end_to_end(CELL)} <= set(out["metrics"])
    assert by_what["window"]["compiles_in_window"] == 0
    if trace:
        # no peak on the CPU: the roofline share is left out, the rest is there
        assert {"decode_step_ms", "device_idle_share.serve", "prefill_ms.docqa"} <= set(out["metrics"])
        mixers = by_what["traced_mixers"]
        assert mixers["calls"] > mixers["prefills"] > 0 and mixers["instructions_named"] > 0
        assert by_what["traced"]["compiles_in_trace"] == 0
