"""The OLMoE configuration's part of the benchmark (PR 26): its file against
the catalog's numbers, its cost functions by hand, its two readers on small
fixtures, its reference against the program's forward on the cell's tiny
preset, and the cell's rehearsal end to end."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from odbench import costs_olmoe, manifest, peaks, reference_olmoe

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
CELL = "serve-olmoe-fewshot"


@pytest.fixture(scope="module")
def man():
    return manifest.Manifest(REPO, BENCH)


def test_configuration_file_holds_the_published_numbers(man):
    cell = man.cell(CELL)
    published = {  # the catalog row's config (model-configs guide), its numbers
        "hidden_size": 2048, "intermediate_size": 1024, "max_position_embeddings": 4096,
        "num_attention_heads": 16, "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 16, "num_key_value_heads": 16, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "vocab_size": 50304,
    }
    entry = next(c for c in man.raw["configs"] if c["name"] == cell.config_name)
    assert entry["reduced"] == cell.config["reduced"] == ["num_hidden_layers"]
    for key, value in published.items():
        if key not in entry["reduced"]:
            assert cell.config[key] == value, key
    assert cell.config["model_type"] == "olmoe" and cell.config["norm_topk_prob"] is False
    assert cell.chips == 1 and cell.traffic["kind"] == "closed_loop_olmoe"
    rows = cell.options["engine"]["max_context"]
    longest = cell.traffic["prompt_tokens"]["max"] + cell.traffic["output_tokens"]["value"]
    assert rows % 128 == 0 and rows - 128 < longest <= rows
    buckets = cell.options["engine"]["prefill_buckets"]
    assert len(buckets) <= 4 and max(buckets) == cell.traffic["prompt_tokens"]["max"]
    assert cell.options["engine"]["num_slots"] == 16


def test_parameter_counts(man):
    cfg = man.cell(CELL).config
    whole = {**cfg, "num_hidden_layers": 16}
    assert costs_olmoe.layer_param_count(cfg) == 419_569_664 == cfg["parameters"]["per_layer"]
    assert costs_olmoe.param_count(whole) == 6_919_161_856 == cfg["parameters"]["published"]
    assert costs_olmoe.param_count(cfg) == 1_884_325_888 == cfg["parameters"]["as_run"]
    # attention 4 x 2048^2, router 2048 x 64, 8 experts x 3 x 2048 x 1024, per layer; the head
    per_layer = 4 * 2048 * 2048 + 2048 * 64 + 8 * 3 * 2048 * 1024
    assert costs_olmoe.active_matmul_param_count(whole) == 16 * per_layer + 2048 * 50304
    # the program draws exactly these leaves
    from opendiloco_tpu.models.llama import LlamaConfig

    assert LlamaConfig.from_dict(cfg).num_params() == cfg["parameters"]["as_run"]


def test_routed_ffn_cost_by_hand(man):
    cfg = man.cell(CELL).config
    flops, nbytes = costs_olmoe.routed_ffn_cost(cfg, pairs=1000, experts_hit=10)
    assert flops == 2 * 3 * 1000 * 2048 * 1024
    assert nbytes == 10 * 3 * 2048 * 1024 * 2 + 1000 * 2 * 2048 * 2


def _obs(man, counters, ops=None):
    lines = []
    report = types.SimpleNamespace(line=lambda what, **kw: lines.append((what, kw)))
    obs = {"counters": counters, "cell": man.cell(CELL), "peak": peaks.peak("TPU v5 lite"),
           "report": report}
    if ops is not None:
        obs["trace"] = {"ops": {"/device:TPU:0": ops}, "busy_s": 1.0, "window_s": 2.0}
    return obs, lines


def test_moe_ffn_roofline_reader(man):
    read, params = man.reader("moe_ffn_roofline.serve")
    ops = [
        ["%ragged-dot-none.3 custom-call:tpu_custom_call", 0.0, 4e6, "bf16[16384,1024]"],
        ["%ragged-dot-metadata custom-call:tpu_custom_call", 5e6, 1e5, "(s32[65]"],
        ["%ragged-dot-none.4 custom-call:tpu_custom_call", 6e6, 5.9e6, "bf16[128,1024]"],
        ["%odtp_paged_decode_attn.2 custom-call:tpu_custom_call", 1e7, 9e6, "bf16[16"],
    ]
    # a prefill of 3,072 live tokens in 4 layers (6.3 ms of matmul against 4.7 ms
    # of weights and rows), and a decode step of 16 slots (the experts' weights)
    calls = [[3072 * 8 * 4, 64 * 4], [16 * 8 * 4, 55 * 4]]
    obs, lines = _obs(man, {"traced_moe_calls": calls}, ops)
    peak = obs["peak"]
    prefill = 2 * 3 * calls[0][0] * 2048 * 1024 / peak.bf16_flops  # compute bound
    decode = (calls[1][1] * 3 * 2048 * 1024 * 2 + calls[1][0] * 2 * 2048 * 2) / peak.hbm_bytes_per_s
    want = 100.0 * (prefill + decode) / 0.01  # the three grouped-matmul events: 10 ms
    assert read(obs, params) == pytest.approx(want) and 0 < want < 100
    (what, line), = lines
    assert what == "moe_ffn_roofline" and line["kernel_events"] == 3
    assert line["calls_by_bound"] == {"compute": 1, "memory": 1}
    # nothing to read: a parent's spans carry no counts; no grouped-matmul event; no trace
    assert read(_obs(man, {"traced_moe_calls": []}, ops)[0], params) is None
    assert read(_obs(man, {"traced_moe_calls": calls}, ops[3:])[0], params) is None
    assert read(_obs(man, {"traced_moe_calls": calls})[0], params) is None
    assert read({"counters": {}, "cell": obs["cell"], "peak": None}, params) is None


def test_moe_max_over_mean_pairs_reader(man):
    read, params = man.reader("moe_max_over_mean_pairs")
    obs, _ = _obs(man, {"moe_pairs": 64_000, "moe_max_pairs": 1_500})
    assert read(obs, params) == 1.5  # the mean expert had 1,000
    assert read(_obs(man, {"moe_pairs": 0, "moe_max_pairs": 0})[0], params) is None
    assert read(_obs(man, {})[0], params) is None


def test_data_only_metrics_of_the_cell(man):
    read, params = man.reader("prefill_ms.fewshot")
    assert read({"counters": {"prefill_s": 3.0, "admissions": 60}}, params) == 50.0
    spec = man.metric_file("paged_attn_roofline.olmoe")
    assert spec["reader"] == "paged_attn_roofline"
    assert spec["params"]["needles"] == ["odtp_paged_decode_attn"]
    listed = {m["name"] for m in man.per_layer(CELL)}
    assert listed == {"decode_step_ms", "device_idle_share.serve", "moe_ffn_roofline.serve",
                      "moe_max_over_mean_pairs", "prefill_ms.fewshot", "paged_attn_roofline.olmoe"}
    # the batch cell's kernel metric sums every Pallas call: this cell is not under it
    assert CELL not in next(m for m in man.raw["per_layer"]
                            if m["name"] == "paged_attn_roofline.serve")["workloads"]


def test_reference_agrees_with_the_programs_forward_on_the_tiny_preset(man):
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.models.llama import LlamaConfig, causal_lm_loss, forward, init_params

    raw = man.cell(CELL, rehearse=True).config
    cfg = LlamaConfig.from_dict(raw)
    assert cfg.qk_norm and cfg.num_experts == 8 and cfg.num_experts_per_tok == 2
    params = init_params(jax.random.key(3), cfg)
    params["layers"]["router"] = params["layers"]["router"] * 25.0  # spread probabilities
    ids = jax.random.randint(jax.random.key(4), (2, 24), 0, cfg.vocab_size)
    want, aux = forward(params, ids, cfg, compute_dtype=jnp.float32, remat=False,
                        return_moe_aux=True)
    got = jax.jit(lambda p, i: reference_olmoe.forward(p, i, raw))(params, ids)
    # float32 both: only the order of accumulation differs
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-5)
    loss = jax.jit(lambda p, i: reference_olmoe.loss(p, i, i, raw))(params, ids)
    np.testing.assert_allclose(float(loss), float(causal_lm_loss(want, ids)) + float(aux), rtol=1e-5)
    # causal: a later token changes no earlier logit
    other = np.asarray(ids).copy()
    other[:, 16:] = 7
    again = jax.jit(lambda p, i: reference_olmoe.forward(p, i, raw))(params, other)
    np.testing.assert_array_equal(np.asarray(again)[:, :16], np.asarray(got)[:, :16])


def test_rehearsal_of_the_cell_with_trace_2(man):
    env = {k: v for k, v in os.environ.items() if k not in ("ODTP_OBS", "ODTP_REQTRACE_CAP")}
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed",
         "2147483659", "--seconds", "3", "--rehearse", "--trace", "2"],
        capture_output=True, text=True, timeout=600, env={**env, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    by_what = {x.get("what", "result"): x for x in
               (json.loads(line) for line in proc.stdout.splitlines() if line.startswith("{"))}
    out = by_what["rehearsal"]
    assert "result" not in by_what and "fault" not in by_what and out["correct"] is True
    assert by_what["check"]["reference"] == "reference_olmoe" and by_what["check"]["ok"]
    assert {m["name"] for m in man.end_to_end(CELL)} <= set(out["metrics"])
    # no peak on the CPU: the two roofline shares are left out, the rest is there
    assert {"decode_step_ms", "device_idle_share.serve", "prefill_ms.fewshot",
            "moe_max_over_mean_pairs"} <= set(out["metrics"])
    routed = by_what["traced_routed"]
    assert routed["calls"] > 0 and routed["pairs"] > 0 and routed["experts_hit"] > 0
    assert by_what["window"]["compiles_in_window"] == 0
    assert by_what["traced"]["compiles_in_trace"] == 0
    assert by_what["routed"]["experts"] == 8 and by_what["routed"]["per_token"] == 2
