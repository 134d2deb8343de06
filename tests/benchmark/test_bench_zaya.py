"""The ZAYA1-8B configuration's part of the benchmark (PR 37): its file against
the catalog's numbers, its cost functions by hand, its readers on synthetic
observations, its reference against the program's forward on the cell's tiny
preset (and along another's choices), the check's two limits, and the cell's
rehearsal end to end."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from odbench import costs, costs_zaya, manifest, peaks, reference_zaya

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
CELL = "serve-zaya1-reason"
REDUCED = ["num_hidden_layers"]
# the catalog row's config (model-configs guide, ZAYA1-8B), its numbers
PUBLISHED = {
    "cca_time0": 2, "cca_time1": 2, "head_dim": 128, "hidden_size": 2048,
    "max_position_embeddings": 131072, "moe_intermediate_size": 2048, "num_attention_heads": 8,
    "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.5, "rms_norm_eps": 1e-05, "router_hidden_size": 256,
    "vocab_size": 262272,
}
OTHER = {"attention_bias": False, "hidden_act": "silu", "lm_head_bias": False, "model_type": "zaya",
         "sliding_window": None, "tie_word_embeddings": True}
NEW_METRICS = {"prefill_ms.reason", "paged_attn_roofline.zaya", "moe_ffn_roofline.zaya",
               "moe_max_over_mean_pairs.zaya", "cca_mix_roofline.serve"}
JOINED = {"decode_step_ms", "device_idle_share.serve"}
# ISSUE 37 also asks for the cell on the lists of PR 34's four span metrics; it stays off them:
# ``test_bench_span_ms.py`` holds those lists with ``==`` (the batch and the agent cell), and a
# file that was under ``tests/benchmark`` is not this PR's to edit (PERF.md section 7, C-b13)
SPAN_METRICS = {"decode_submit_ms", "prefill_submit_ms", "decode_fetch_ms", "loop_overhead_ms"}


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(REPO, BENCH)


def test_manifest_is_sound_with_the_cell(man):
    """Properties, none of a moment (where the cell stands in a list is none):
    a later cell or metric joins without touching any of this."""
    assert manifest.problems(man) == []
    entry = next(w for w in man.raw["workloads"] if w["name"] == CELL)
    conf = next(c for c in man.raw["configs"] if c["name"] == entry["config"])
    assert entry["chips"] == 1 and len(entry["why"]) <= 200 and len(conf["why"]) <= 200
    assert {m["name"] for m in man.end_to_end(CELL)} >= {"tpot_p95_ms", "serve_tokens_per_s", "setup_s"}
    assert {m["name"] for m in man.per_layer(CELL)} >= NEW_METRICS | JOINED
    assert not {m["name"] for m in man.per_layer(CELL)} & SPAN_METRICS
    for m in [*man.raw["end_to_end"], *man.raw["per_layer"]]:
        names = m.get("workloads", [])
        assert len(set(names)) == len(names)
    assert len(json.dumps(man.raw)) < 64 * 1024


# what the benchmark held when this cell joined it (PR 35's manifest): cell -> its metrics
HELD_BEFORE = {
    "train-360m-h16": {"train_tokens_per_s_per_chip", "boundary_ms", "inner_step_ms", "inner_mfu",
                       "flash_attn_roofline.train", "device_idle_share.train", "boundary_d2h_ms",
                       "boundary_allreduce_ms", "boundary_apply_ms"},
    "serve-360m-batch": {"tpot_p95_ms", "serve_tokens_per_s", "decode_step_ms", "paged_attn_roofline.serve",
                         "device_idle_share.serve", "prefill_ms.batch", "decode_submit_ms",
                         "prefill_submit_ms", "decode_fetch_ms", "loop_overhead_ms"},
    "train-1.7b-fsdp4-h8": {"train_tokens_per_s_per_chip", "boundary_ms", "inner_step_ms", "inner_mfu",
                            "device_idle_share.train", "boundary_d2h_ms", "boundary_allreduce_ms",
                            "boundary_apply_ms"},
    "serve-olmoe-fewshot": {"tpot_p95_ms", "serve_tokens_per_s", "decode_step_ms", "device_idle_share.serve",
                            "moe_ffn_roofline.serve", "moe_max_over_mean_pairs", "prefill_ms.fewshot",
                            "paged_attn_roofline.olmoe"},
    "serve-granite-h-docqa": {"tpot_p95_ms", "serve_tokens_per_s", "decode_step_ms", "device_idle_share.serve",
                              "prefill_ms.docqa", "ssm_mixer_roofline.serve", "moe_ffn_roofline.serve",
                              "paged_attn_roofline.granite", "moe_max_over_mean_pairs.held"},
    "serve-glm-flash-agent": {"tpot_p95_ms", "serve_tokens_per_s", "decode_step_ms", "device_idle_share.serve",
                              "prefill_ms.agent", "mla_attn_roofline.serve", "moe_ffn_roofline.glm",
                              "moe_max_over_mean_pairs.glm", "decode_submit_ms", "prefill_submit_ms",
                              "decode_fetch_ms", "loop_overhead_ms"},
}


def test_the_manifest_gained_entries_and_lost_none(man):
    raw = man.raw
    cells = [w["name"] for w in raw["workloads"]]
    assert CELL in cells and set(HELD_BEFORE) <= set(cells) and len(set(cells)) == len(cells)
    assert "zaya1-8b" in {c["name"] for c in raw["configs"]}
    assert sum(w["chips"] == 4 for w in raw["workloads"]) <= max(1, len(cells) // 4)
    for cell, metrics in HELD_BEFORE.items():  # no list lost a name
        have = {m["name"] for m in [*man.end_to_end(cell), *man.per_layer(cell)]}
        assert metrics <= have, (cell, metrics - have)
    by_name = {p["name"]: p for p in raw["per_layer"]}
    assert len(by_name) == len(raw["per_layer"])
    for name in NEW_METRICS:
        p = by_name[name]
        assert p["workloads"] == [CELL] and p["moves"] == "tpot_p95_ms"
        assert set(p) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    # metrics that read a key or a span this configuration has not: the cell stays off them
    for name in ("moe_ffn_roofline.serve", "moe_max_over_mean_pairs", "moe_max_over_mean_pairs.held",
                 "paged_attn_roofline.serve", "ssm_mixer_roofline.serve", "mla_attn_roofline.serve"):
        assert CELL not in by_name[name]["workloads"]
    assert by_name["cca_mix_roofline.serve"]["layer"] == by_name["ssm_mixer_roofline.serve"]["layer"]
    assert by_name["moe_max_over_mean_pairs.zaya"]["layer"] == by_name["moe_max_over_mean_pairs.glm"]["layer"]
    assert by_name["prefill_ms.reason"]["layer"] == by_name["prefill_ms.agent"]["layer"]


def test_configuration_file_holds_the_published_numbers(man):
    cell = man.cell(CELL)
    cfg = cell.config
    entry = next(c for c in man.raw["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == cfg["reduced"] == REDUCED
    assert entry["source"] == cfg["source"] == "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"
    for key, value in PUBLISHED.items():
        if key in REDUCED:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    for key, value in OTHER.items():
        assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == 10 and cfg["layer_types"] == ["hybrid"] * 40  # the pattern whole
    assert cfg["rope_parameters"]["hybrid"] == {
        "partial_rotary_factor": 0.5, "rope_theta": 5000000, "rope_type": "default"}
    # inside the guide's floors: a period is one layer, ten follow; every expert; the whole vocabulary
    assert cfg["num_hidden_layers"] >= 4 and cfg["num_experts"] == 16 and cfg["vocab_size"] == 262272
    assert any("MoD" in line for line in cfg["assumed"])
    assert cell.chips == 1 and cell.traffic["kind"] == "closed_loop_zaya"
    assert cell.traffic["prompt_tokens"] == {"dist": "uniform", "min": 256, "max": 1024}
    assert cell.traffic["output_tokens"] == {"dist": "const", "value": 512}
    engine = cell.options["engine"]
    assert engine["prefill_buckets"] == [512, 1024] and engine["max_context"] % 128 == 0
    # the issue's first choice, or one of its named fallbacks
    assert (engine["num_slots"], engine["max_context"]) in ((128, 1536), (96, 1536))
    assert 1024 + 512 <= engine["max_context"]  # no request wraps its ring
    check = cell.options["check"]
    assert check["decode_steps"] == 8 and len(check["prompt_tokens"]) == 2
    assert all(n not in engine["prefill_buckets"] for n in check["prompt_tokens"])  # padded to a bucket
    assert max(check["prompt_tokens"]) + check["decode_steps"] + 1 <= check["pad_to"]


def test_parameter_counts_by_hand(man):
    cfg = man.cell(CELL).config
    counted = cfg["parameters"]
    attention = 2048 * 1024 + 2048 * 256 + 2 * 2048 * 128 + 1024 * 2048
    assert costs_zaya.attention_param_count(cfg) == attention == 5_242_880 == counted["attention"]
    convs = 2 * 1280 + 1280 + 10 * 2 * 128 * 128 + 1280
    assert costs_zaya.convolution_param_count(cfg) == convs == 332_800 == counted["convolutions"]
    router = 2048 * 256 + 256 + 256 + 256 + 2 * (256 * 256 + 256) + 256 * 16 + 16
    assert costs_zaya.router_param_count(cfg) == router == 660_752 == counted["router"]
    experts = 16 * 3 * 2048 * 2048
    assert 16 * costs_zaya.expert_param_count(cfg) == experts == 201_326_592 == counted["experts"]
    layer = attention + convs + router + experts + 2 * 2048 + 8 * 2048 + 2
    assert costs_zaya.layer_param_count(cfg) == layer == 207_583_506 == counted["a_layer"]
    as_run = 10 * layer + 262272 * 2048 + 2048
    assert costs_zaya.param_count(cfg) == as_run == 2_612_970_164 == counted["as_run"]
    assert counted["as_run_bytes_bf16"] == 2 * as_run == 5_225_940_328
    whole = {**cfg, **cfg["published"]}
    assert costs_zaya.param_count(whole) == 40 * layer + 537_133_056 + 2048 == counted["published"]
    assert 8.29e9 < 40 * layer < 8.31e9  # the publisher's 8.3 B is the layers without the embedding
    assert costs_zaya.kv_bytes_per_token(cfg) == 10 * 2 * 2 * 128 * 2 == counted["ring_bytes_per_token"]
    assert costs_zaya.state_values(cfg) == 2688 == counted["state_values_per_slot_and_layer"]
    assert costs_zaya.state_bytes_per_slot(cfg) == 10 * 2688 * 2
    # the harness's dense formula reads heads of 256 and rows twice as wide
    assert costs.kv_bytes_per_token(cfg) == 2 * costs_zaya.kv_bytes_per_token(cfg)
    # the program draws exactly these leaves
    from opendiloco_tpu.models.llama import LlamaConfig

    assert LlamaConfig.from_dict(cfg).num_params() == counted["as_run"]


def test_cost_functions_by_hand(man):
    cfg = man.cell(CELL).config
    # a decode step of 128 live slots
    flops, nbytes = costs_zaya.cca_mix_cost(cfg, 128, 128, True)
    per_token = 2.0 * (2048 * 1536 + 2 * 1280 + 2 * 128 * 1280)
    assert flops == 10 * 128 * per_token
    weights = (2048 * 1536 + 332_800) * 2
    assert nbytes == 10 * (weights + 128 * (2048 + 1536) * 2 + 128 * 2688 * 2 * 2)
    assert flops / nbytes < 240  # under the v5e's ridge: a step's projections are memory-bound
    # a prefill of 700 tokens writes one slot's state and reads none
    flops, nbytes = costs_zaya.cca_mix_cost(cfg, 700, 1, False)
    assert flops == 10 * 700 * per_token
    assert nbytes == 10 * (weights + 700 * (2048 + 1536) * 2 + 2688 * 2)
    assert flops / nbytes > 240  # compute-bound
    # 128 pairs of a step in each of 10 layers, every expert hit: the issue's 4.03 GB a step
    flops, nbytes = costs_zaya.routed_ffn_cost(cfg, 10 * 128, 10 * 16)
    assert flops == 2.0 * 3 * 10 * 128 * 2048 * 2048
    assert nbytes == 10 * 16 * 3 * 2048 * 2048 * 2 + 10 * 128 * 2 * 2048 * 2
    assert 4.02e9 < 10 * 16 * 3 * 2048 * 2048 * 2 < 4.04e9


def _obs(man, counters, ops=None):
    lines = []
    report = types.SimpleNamespace(line=lambda what, **kw: lines.append((what, kw)))
    obs = {"counters": counters, "cell": man.cell(CELL), "peak": peaks.peak("TPU v5 lite"),
           "report": report}
    if ops is not None:
        obs["trace"] = {"ops": {"/device:TPU:0": ops}, "busy_s": 1.0, "window_s": 2.0}
    return obs, lines


OPS = [
    ["%odtp_paged_decode_attn.3 custom-call:tpu_custom_call", 0.0, 2e6, "(bf16[128,8,128]"],
    ["%odtp_paged_decode_attn.3 custom-call:tpu_custom_call", 3e6, 2e6, "(bf16[128,8,128]"],
    ["%ragged-dot-none.1 custom-call:tpu_custom_call", 6e6, 3e6, "bf16[128,2048]"],
    ["%fusion.7 fusion", 10e6, 1e6, "bf16[128,1,1280]{2,1,0} fusion("],
    ["%fusion.9 fusion", 12e6, 2e6, "bf16[1,1024,1280]{2,1,0} fusion("],
    ["%fusion.7 fusion", 15e6, 4e6, "bf16[128,2048]{1,0} fusion("],  # the name, another shape
]


def test_cca_mix_roofline_reader(man):
    read, params = man.reader("cca_mix_roofline.serve")
    calls = [[128, 128, 1], [700, 1, 0]]  # a traced step, a traced prefill
    named = [["%fusion.7", "bf16[128,1,1280]"], ["%fusion.9", "bf16[1,1024,1280]"]]
    obs, lines = _obs(man, {"traced_cca_calls": calls, "cca_ops": named}, OPS)
    cfg, peak = obs["cell"].config, obs["peak"]
    least = sum(costs.roofline_seconds(*costs_zaya.cca_mix_cost(cfg, t, s, bool(d)), peak)[0]
                for t, s, d in calls)
    want = 100.0 * least / 3e-3  # the two named operations: 1 ms + 2 ms
    assert read(obs, params) == pytest.approx(want) and 0 < want < 100
    (what, line), = lines
    assert what == "cca_mix_roofline" and line["cca_events"] == 2
    assert line["calls_by_bound"] == {"compute": 1, "memory": 1}
    # nothing to read: a program whose spans carry no cca_tokens (the parent), no
    # instruction named, no such event, no trace, no peak: nothing, nothing raised
    assert read(_obs(man, {"traced_cca_calls": [], "cca_ops": named}, OPS)[0], params) is None
    assert read(_obs(man, {}, OPS)[0], params) is None
    assert read(_obs(man, {"traced_cca_calls": calls, "cca_ops": []}, OPS)[0], params) is None
    assert read(_obs(man, {"traced_cca_calls": calls, "cca_ops": named}, OPS[:3])[0], params) is None
    assert read(_obs(man, {"traced_cca_calls": calls, "cca_ops": named})[0], params) is None
    assert read({"counters": {"traced_cca_calls": calls, "cca_ops": named}, "cell": obs["cell"],
                 "peak": None, "trace": obs["trace"]}, params) is None


def test_paged_attn_reader_counts_heads_of_the_stated_size(man):
    read, params = man.reader("paged_attn_roofline.zaya")
    assert params == {"needles": ["odtp_paged_decode_attn"]}
    counters = {"traced_decode_steps": 2, "traced_live_rows": 2 * 128 * 900, "traced_live_slots": 256}
    obs, lines = _obs(man, counters, OPS)
    cfg = obs["cell"].config
    # rows of 2 KV heads x 128 values, keys and values, ten layers; 8 query heads of 128
    kv_bytes = 10 * 2 * counters["traced_live_rows"] * 2 * 128 * 2
    qo_bytes = 10 * 2 * 256 * 8 * 128 * 2
    least = (kv_bytes + qo_bytes) / obs["peak"].hbm_bytes_per_s
    assert read(obs, params) == pytest.approx(100.0 * least / 4e-3)
    assert lines[0][1]["kernel_events"] == 2 and lines[0][1]["bound"] == "memory"
    # the harness's reader derives heads of 256 from hidden_size and credits twice the bytes
    plain = manifest.load_module(os.path.join(BENCH, "readers", "paged_attn_roofline.py")).read
    assert plain(_obs(man, counters, OPS)[0], params) == pytest.approx(
        2 * read(_obs(man, counters, OPS)[0], params))
    # a configuration without the key is the other reader's; the parent's spans give no rows
    other = man.cell("serve-360m-batch")
    assert read({**obs, "cell": other}, params) is None
    assert read(_obs(man, {}, OPS)[0], params) is None
    assert cfg["hidden_size"] // cfg["num_attention_heads"] == 2 * cfg["head_dim"]


def test_the_data_only_metrics(man):
    read, params = man.reader("moe_ffn_roofline.zaya")
    assert params == {"needles": ["%ragged-dot"], "width_key": "moe_intermediate_size"}
    calls = [[10 * 128, 10 * 15], [10 * 700, 10 * 16]]
    obs, lines = _obs(man, {"traced_moe_calls": calls}, OPS)
    least = sum(costs.roofline_seconds(*costs_zaya.routed_ffn_cost(obs["cell"].config, p, e), obs["peak"])[0]
                for p, e in calls)
    assert read(obs, params) == pytest.approx(100.0 * least / 3e-3)
    assert lines[0][1]["width"] == 2048
    read, params = man.reader("moe_max_over_mean_pairs.zaya")
    assert params == {"held_key": "num_experts"}
    assert read(_obs(man, {"moe_pairs": 8000, "moe_max_pairs": 1500})[0], params) == pytest.approx(16 * 1500 / 8000)
    assert read(_obs(man, {})[0], params) is None
    read, params = man.reader("prefill_ms.reason")
    assert read({"counters": {"prefill_s": 3.3, "admissions": 60}}, params) == pytest.approx(55.0)
    assert read({"counters": {}}, params) is None
    for name in NEW_METRICS:
        spec = man.metric_file(name)
        assert spec["name"] == name and spec["moves"] == "tpot_p95_ms"


def test_driver_replaces_four_functions_and_refuses_a_program_without_cca(man):
    from odbench import traffic

    cell = man.cell(CELL)
    assert set(cell.traffic) == {"kind", "prompt_tokens", "output_tokens"}
    driver = man.driver("closed_loop_zaya")
    assert not hasattr(driver, "requests") and not hasattr(driver, "traffic")
    seen = {}
    loop = types.SimpleNamespace(run=lambda **kw: seen.update(kw) or "ran")
    load, manifest.load_module = manifest.load_module, lambda path: loop
    try:
        assert driver.run(report=None, cell=cell) == "ran"
    finally:
        manifest.load_module = load
    assert loop.serve_cell.snapshot is driver.snapshot
    assert loop.serve_cell.traced_stretch is driver.traced_stretch
    assert loop.serve_cell.warm_up is driver.serve_cell.warm_up  # the rest is shared
    reqs = traffic.requests(cell.traffic, 8192, cell.config["vocab_size"], 2147483659)
    lens = np.array([len(a.prompt) for a in reqs])
    assert lens.min() == 256 and lens.max() == 1024 and {a.max_new_tokens for a in reqs} == {512}
    # a program that reads no cca_time0 (the parent), or refuses the file's layer_types
    # as the parent does: refused before anything is built
    import opendiloco_tpu.models.llama as llama

    class Old:
        @staticmethod
        def from_dict(raw):
            return types.SimpleNamespace()

    class Older:
        @staticmethod
        def from_dict(raw):
            raise ValueError("layer_types must name 10 layers, each 'attention' or 'mamba'")

    report = types.SimpleNamespace(line=lambda what, **kw: None)
    real = llama.LlamaConfig
    try:
        for old in (Old, Older):
            llama.LlamaConfig = old
            with pytest.raises(RuntimeError, match="cannot run zaya1-8b.*no CCA"):
                driver.start(cell, None, 0, 0, report, 0.0)
    finally:
        llama.LlamaConfig = real
    assert 0 < driver.LOGITS_REL_L2 < 0.5 and 0 < driver.CHOICE_MARGIN < 0.5


def test_where_choices_differ_the_margin_is_what_is_held(man):
    driver = man.driver("closed_loop_zaya")
    engine = np.array([[0, 1], [2, 3], [4, 5]])
    own = np.array([[0, 1], [2, 7], [6, 5]])
    margins = np.array([[0.5, 0.4], [0.3, 0.002], [0.01, 0.2]])
    out = driver.differing([engine], [own], [margins])
    assert out == {"choices_compared": 6, "choices_differing": 2,
                   "choices_differing_share": 2 / 6, "largest_differing_margin": 0.01}
    assert driver.differing([engine], [engine], [margins])["largest_differing_margin"] == 0.0


def test_reference_agrees_with_the_programs_forward_on_the_tiny_preset(man):
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.models.llama import LlamaConfig, causal_lm_loss, forward, init_params

    raw = man.cell(CELL, rehearse=True).config
    cfg = LlamaConfig.from_dict(raw)
    assert cfg.cca and cfg.num_hidden_layers == 3 and (cfg.head_dim, cfg.rotary_dim) == (16, 8)
    params = init_params(jax.random.key(3), cfg)
    ids = jax.random.randint(jax.random.key(4), (2, 24), 0, cfg.vocab_size)
    want = forward(params, ids, cfg, compute_dtype=jnp.float32, remat=False)
    ref = jax.jit(lambda p, i: reference_zaya.forward(p, i, raw, with_choices=True))
    got, own, margin = ref(params, ids)
    # float32 both: only the order of accumulation differs
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-6)
    loss = jax.jit(lambda p, i: reference_zaya.loss(p, i, i, raw))(params, ids)
    np.testing.assert_allclose(float(loss), float(causal_lm_loss(want, ids)), rtol=1e-5)
    # causal in every layer: a later token changes no earlier logit
    other = np.asarray(ids).copy()
    other[:, 16:] = 7
    again = ref(params, other)[0]
    np.testing.assert_array_equal(np.asarray(again)[:, :16], np.asarray(got)[:, :16])
    # below the stated precision the reference moves by orders of magnitude more,
    # along its own choices and, the check's way, along the low precision's
    low, low_own, _ = jax.jit(lambda p, i: reference_zaya.forward(
        p, i, raw, jnp.float8_e4m3fn, with_choices=True))(params, ids)
    along = jax.jit(lambda p, i, f: reference_zaya.forward(p, i, raw, follow=f))(params, ids, low_own)
    rel = lambda a, b: float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
    assert rel(low, got) > 0.02 and rel(low, along) > 0.02
    assert float(jnp.min(margin)) >= 0 and own.shape == (2, 24, 3)


@pytest.mark.parametrize("trace", [2])  # a ``--trace 2`` run is a ``--trace 0`` run until its window closes
def test_rehearsal_of_the_cell(man, trace):
    env = {k: v for k, v in os.environ.items() if k not in ("ODTP_OBS", "ODTP_REQTRACE_CAP")}
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed",
         "2147483659", "--seconds", "3", "--rehearse", "--trace", str(trace)],
        capture_output=True, text=True, timeout=900, env={**env, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    by_what = {x.get("what", "result"): x for x in
               (json.loads(line) for line in proc.stdout.splitlines() if line.startswith("{"))}
    out = by_what["rehearsal"]
    assert "result" not in by_what and "fault" not in by_what
    assert out["failed"] == 0 and by_what["check"]["ok"]
    check = by_what["check"]
    assert check["reference"] == "reference_zaya" and check["choices_compared"] == (12 + 4 + 5 + 4) * 3
    assert check["largest_differing_margin"] <= check["tolerance"]["largest_differing_margin"]
    zaya = by_what["zaya"]
    assert zaya["cca_state_resident_bytes"] == 3 * 8 * (2 * 96 + 16) * 2  # layers, slots, a row, bf16
    assert zaya["kv_cache_bytes"] == 2 * 3 * 8 * 64 * 2 * 16 * 2
    assert {m["name"] for m in man.end_to_end(CELL)} <= set(out["metrics"])
    window = by_what["window_counters"]
    assert window["cca_tokens"] > 0 and window["cca_state_bytes_moved"] > 0
    assert window["cca_state_resident_bytes"] == zaya["cca_state_resident_bytes"]
    assert window["moe_pairs"] == window["moe_pairs_all"] == 3 * window["cca_tokens"]  # a pair is a token
    assert by_what["window"]["compiles_in_window"] == 0
    if trace:
        # no peak on the CPU: the roofline shares are left out, the rest is there
        assert {"decode_step_ms", "device_idle_share.serve", "prefill_ms.reason",
                "moe_max_over_mean_pairs.zaya"} <= set(out["metrics"])
        assert by_what["traced_cca"]["calls"] > 0 and by_what["traced_cca"]["instructions_named"] > 0
        assert by_what["traced_routed"]["calls"] == by_what["traced_cca"]["calls"]
        assert by_what["traced"]["compiles_in_trace"] == 0
