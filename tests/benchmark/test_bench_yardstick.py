"""The yardstick's arithmetic: traffic, percentiles, costs, trace reduction."""

import json
import math
import os

import numpy as np
import pytest

from odbench import costs, peaks, stats, traffic, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmark")


def _config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _mix(name):
    return traffic.load(os.path.join(BENCH, "traffic", name + ".json"))


# -- traffic ------------------------------------------------------------------


def _flat(arrivals):
    return [(a.due_s, tuple(a.prompt), a.max_new_tokens) for a in arrivals]


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345])
def test_open_loop_same_seed_same_traffic(seed):
    mix = _mix("chat-1.7b")
    a = traffic.open_loop(mix, 30.0, 49152, seed)
    b = traffic.open_loop(mix, 30.0, 49152, seed)
    assert _flat(a) == _flat(b)


def test_open_loop_seeds_differ_in_order_not_in_work():
    mix = _mix("chat-1.7b")
    a = traffic.open_loop(mix, 30.0, 49152, 1)
    b = traffic.open_loop(mix, 30.0, 49152, 2)
    assert _flat(a) != _flat(b)
    # the same set of lengths and the same set of gaps, in another order
    assert sorted(len(x.prompt) for x in a) == sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new_tokens for x in a) == sorted(x.max_new_tokens for x in b)
    assert len(a) == len(b) == round(mix["rate_per_s"] * 30.0)
    whole = set(np.round(traffic.gap_set(mix, len(a)), 9))
    for xs in (a, b):  # every gap between arrivals is one of the fixed set
        assert set(np.round(np.diff([x.due_s for x in xs]), 9)) <= whole
    assert a[0].due_s == 0.0 and a[-1].due_s < 30.0


def test_lengths_respect_the_mix():
    mix = _mix("chat-1.7b")
    reqs = traffic.requests(mix, 400, 49152, 5)
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    assert all(p["min"] <= len(r.prompt) <= p["max"] for r in reqs)
    assert all(o["min"] <= r.max_new_tokens <= o["max"] for r in reqs)
    assert all(traffic.FIRST_TOKEN <= t < 49152 for r in reqs for t in r.prompt)
    median = sorted(len(r.prompt) for r in reqs)[200]
    assert abs(median - p["median"]) <= 0.05 * p["median"]


def test_closed_loop_mix():
    mix = _mix("batch-360m")
    reqs = traffic.requests(mix, 64, 49152, 3)
    p = mix["prompt_tokens"]
    assert {r.max_new_tokens for r in reqs} == {mix["output_tokens"]["value"]}
    assert min(len(r.prompt) for r in reqs) >= p["min"]
    assert max(len(r.prompt) for r in reqs) <= p["max"]
    # two requests of one length still carry tokens of their own
    same = [r.prompt for r in traffic.requests(
        {"prompt_tokens": {"dist": "const", "value": 40},
         "output_tokens": {"dist": "const", "value": 4}}, 2, 1000, 1)]
    assert len(same[0]) == len(same[1]) == 40 and same[0] != same[1]


def test_gaps_are_the_exponential_s_quantiles_with_the_exact_mean():
    gaps = traffic.gap_set({"rate_per_s": 4.0}, 500)
    assert math.isclose(gaps.mean(), 0.25, rel_tol=1e-9)
    assert np.all(np.diff(gaps) > 0)  # ascending quantiles, none repeated
    assert 0.9 < gaps.std() / gaps.mean() < 1.1  # an exponential's is 1


def test_ramp_batch_is_the_learnable_stream():
    ids, labels = traffic.ramp_batch(traffic.rng_for(9, 4), 100, 3, 8)
    assert ids.shape == (3, 8) and ids.dtype == np.int32
    assert np.array_equal((ids[:, 1:] - ids[:, :-1]) % 100, np.ones((3, 7)))
    assert np.array_equal(ids, labels)
    assert traffic.jax_seed(2**31 + 5) < 2**31 - 1


# -- percentiles ---------------------------------------------------------------


def test_percentile_rule():
    xs = list(range(1, 201))  # 200 samples: p95 is the 190th, ten beyond
    assert stats.percentile(xs, 95.0) == 190
    assert stats.samples_beyond(200, 95.0) == 10
    assert stats.supported(200, 95.0) and not stats.supported(199, 95.0)
    assert not stats.supported(50, 95.0)  # 2 beyond: no p95 from 50 requests
    assert stats.highest_supported(199) == 90.0
    assert stats.highest_supported(1000) == 99.0
    assert stats.highest_supported(15) is None


def test_failures_enter_as_infinity():
    xs = [1.0] * 189 + [math.inf] * 11  # 5.5% failed: the p95 misses
    assert stats.percentile(xs, 95.0) == math.inf
    xs = [1.0] * 191 + [math.inf] * 9
    assert stats.percentile(xs, 95.0) == 1.0


def test_spread_is_the_contract_s():
    vals = [100, 101, 102, 103, 104, 105]
    q1, _, q3 = __import__("statistics").quantiles(vals, n=4)
    assert stats.spread(vals) == (q3 - q1) / 102.5


# -- costs and peaks -------------------------------------------------------------


@pytest.mark.parametrize(
    "name, params, flops_per_token, kv_bytes",
    [
        # 6 * (N - norms) + 6 * L * d * seq, worked by hand in PERF.md
        ("smollm2-360m", 361_821_120, 6 * 361_758_720 + 6 * 32 * 960 * 2048, 40_960),
        ("smollm2-1.7b", 1_711_376_384, 6 * 1_711_276_032 + 6 * 24 * 2048 * 2048, 196_608),
    ],
)
def test_model_costs(name, params, flops_per_token, kv_bytes):
    cfg = _config(name)
    assert costs.param_count(cfg) == params == cfg["parameters"]
    assert costs.train_flops_per_token(cfg, 2048) == flops_per_token
    assert costs.kv_bytes_per_token(cfg) == kv_bytes


def test_kernel_costs_by_hand():
    flops, nbytes = costs.flash_train_cost(_config("smollm2-360m"), 8, 2048)
    # 7 matmuls x 2 x (8 x 15 x 2048^2 x 64 / 2) MACs x 32 layers
    assert flops == 32 * 7 * 2 * (8 * 15 * 2048 * 2048 * 64 // 2) == 7_215_545_057_280
    # (6 q-sized + 6 kv-sized tensors) x 2 bytes x 32 layers
    assert nbytes == 32 * 2 * (6 * 8 * 2048 * 15 * 64 + 6 * 8 * 2048 * 5 * 64) == 8_053_063_680
    flops, nbytes = costs.paged_decode_cost(_config("smollm2-1.7b"), 1000, 8)
    assert flops == 24 * 2 * 2 * 1000 * 32 * 64
    assert nbytes == 24 * 2 * 1000 * 32 * 64 * 2 + 24 * 2 * 8 * 32 * 64 * 2
    peak = peaks.peak("TPU v5 lite")
    assert (peak.bf16_flops, peak.hbm_bytes_per_s, peak.hbm_bytes) == (197e12, 819e9, 16e9)
    least, bound = costs.roofline_seconds(197e12, 819e9 * 2, peak)
    assert (least, bound) == (2.0, "memory")
    with pytest.raises(ValueError):
        peaks.peak("TPU v99")


# -- trace reduction -------------------------------------------------------------

# a hand-made trace: a while spanning two fusions and a kernel, a gap, a copy
SYNTHETIC = {
    "devices": {
        "/device:TPU:0": [
            ["while.1", 1000.0, 600.0, ""],
            ["fusion.1", 1000.0, 200.0, ""],
            ["custom-call.7", 1250.0, 100.0, "flash"],
            ["fusion.1", 1400.0, 200.0, ""],
            ["copy.2", 2000.0, 100.0, ""],
        ]
    },
    "host": [
        ["main", "bench/window", 900.0, 1300.0, {}],
        ["main", "bench/inner_step", 950.0, 500.0, {}],
        ["main", "bench/flush", 1500.0, 600.0, {}],
    ],
}


def test_reduction_on_a_synthetic_trace():
    ops = SYNTHETIC["devices"]["/device:TPU:0"]
    assert xplane.busy_intervals(ops) == [[1000.0, 1600.0], [2000.0, 2100.0]]
    assert xplane.busy_seconds(ops) == 700e-9
    assert xplane.idle_gaps(ops, 900.0, 2200.0) == [
        [900.0, 1000.0], [1600.0, 2000.0], [2100.0, 2200.0]]
    assert dict(xplane.top_ops(ops)) == {
        "fusion.1": 400e-9, "while.1": 100e-9, "custom-call.7": 100e-9, "copy.2": 100e-9}
    assert xplane.matching_seconds(ops, ["flash"]) == (100e-9, 1)
    s = xplane.summarize(SYNTHETIC)
    assert s["window_s"] == 1300e-9 and s["busy_s"] == 700e-9
    assert dict(s["idle_gaps"]) == {
        "bench/inner_step": 100e-9, "bench/flush": 400e-9, "bench/window": 100e-9}


def test_program_spans_land_on_the_trace_clock():
    trace = {"devices": SYNTHETIC["devices"], "host": [
        ["main", "bench/window", 900.0, 1300.0, {"pc": "100.0"}]]}
    spans = [["outer/d2h", 100.0 + 700e-9, 100.0 + 1000e-9],  # 1600..1900 ns
             ["outer/apply", 99.0, 99.5]]  # before the traced window
    assert xplane.place_program_spans(trace, spans) == 1
    assert trace["host"][-1][1] == "program/outer/d2h"
    assert abs(trace["host"][-1][2] - 1600.0) < 1e-3 and abs(trace["host"][-1][3] - 300.0) < 1e-3
    gaps = dict(xplane.summarize(trace)["idle_gaps"])
    assert abs(gaps["program/outer/d2h"] - 400e-9) < 1e-12  # the 1600..2000 gap
    assert xplane.short_name(
        '%k.1 = bf16[8]{0} custom-call(bf16[8]{0} %x), custom_call_target="tpu_custom_call"'
    ) == ("%k.1 custom-call:tpu_custom_call", "bf16[8]{0} custom-call(bf16[8]{0} %x), custom_call_target=\"tpu_custom_call\"")
    assert xplane.short_name("ThunkExecutor::Execute") == ("ThunkExecutor::Execute", "")


@pytest.mark.parametrize("name", ["trace_train.json", "trace_serve.json"])
def test_reduction_on_a_recorded_trace(name):
    path = os.path.join(HERE, "fixtures", name)
    with open(path) as f:
        trace = json.load(f)
    with open(path.replace(".json", ".expect.json")) as f:
        expect = json.load(f)
    s = xplane.summarize(trace)
    assert 0 < s["busy_s"] <= s["window_s"]
    assert math.isclose(s["busy_s"], expect["busy_s"], rel_tol=1e-9)
    assert math.isclose(s["window_s"], expect["window_s"], rel_tol=1e-9)
    assert [n for n, _ in s["device_ops"]] == expect["top_op_names"]
    assert sum(sec for _, sec in s["idle_gaps"]) <= s["window_s"] - s["busy_s"] + 1e-9
    ops = s["ops"][sorted(s["ops"])[0]]
    seconds, count = xplane.matching_seconds(ops, expect["needles"])
    assert count == expect["kernel_events"]
    assert math.isclose(seconds, expect["kernel_seconds"], rel_tol=1e-9)
