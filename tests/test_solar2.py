"""Solar-Open2's block (PR 64): Kimi-delta linear-attention layers (a gated
delta rule under a decay for every key channel; a float32 state and a
convolution's tail a layer and slot, carried from chunk to chunk of a prompt
and on to the decode step) beside gated NoPE grouped-query layers, every layer
over routed experts of which a chip holds a share, beside a shared one. At a
small size, in float32, against ``benchmark/odbench/reference_solar2.py``
(written from the equations, nothing of the program's in it; its kda layers run
the recurrence token by token): the five forwards (training, whole-prompt
prefill, a prompt in chunks that ends inside a chunk and inside a block of the
chunked form, the decode step in XLA and under the interpreted kernel); the
chunked form against the recurrence at decays under which ``1 / Gamma`` over a
block overflows; each assumed equation against the reference with that equation
broken; the eight shares against the uncut layer; the configuration's file;
the engine's chunks, its counters and what it refuses."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from odbench import costs_solar2, reference_solar2  # noqa: E402

from opendiloco_tpu.models import kda, llama, ring_cache  # noqa: E402
from opendiloco_tpu.models.llama import LlamaConfig  # noqa: E402
from opendiloco_tpu.serve import ContinuousBatcher, ServeEngine  # noqa: E402

F32 = dict(compute_dtype=jnp.float32)
TINY = dict(
    model_type="solar_open2", vocab_size=128, hidden_size=64, intermediate_size=96,
    moe_intermediate_size=32, num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, rms_norm_eps=1e-5, rope_theta=10000, partial_rotary_factor=1,
    # twelve published layers of which the cut runs five: G K K K G
    gqa_interval=3, gqa_layers=[0, 4, 8], use_rope=False, use_gqa_gate=True,
    kda_use_full_proj=False, kda_allow_neg_eigval=True, first_k_dense_replace=0,
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16, num_heads=4, num_kv_heads=None),
    # a share: 4 of 16 experts from expert 8 on, the router over all 16
    n_routed_experts=4, num_experts=16, first_local_expert=8, n_shared_experts=1,
    num_experts_per_tok=3, norm_topk_prob=True, routed_scaling_factor=1,
    max_position_embeddings=256, norm_init_std=0.1,
)
CHUNK, RING, SLOTS = 16, 96, 3
REL = 5e-6  # float32 against float32: three orders under the least a fault moves
FAULTS = (
    "bf16_state", "beta_one", "scalar_decay", "decay_after", "no_delta", "zero_state_chunks",
    "zero_tail_chunks", "no_l2", "no_q_scale", "no_silu", "norm_all", "no_kda_gate",
    "no_gqa_gate", "headwise_gate", "gqa_rope", "softmax_router", "topk_among_held",
    "bias_weighed", "no_shared",
)


def rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a) - jnp.asarray(b)) / jnp.linalg.norm(jnp.asarray(b)))


def spread(cfg, params):
    """Weights under which no top-k choice lies near a tie, the attention's
    scores are not all alike (a rotation's fault shows) and the gates' low-rank
    pairs reach their sigmoids."""
    for stack in params["layers"].values():
        stack["router"] = stack["router"] * 25.0
        for name in ("q_proj", "k_proj", "g_b_proj", "f_b_proj"):
            if name in stack:
                stack[name] = stack[name] * 6.0
    return params


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.from_dict(TINY)
    return cfg, spread(cfg, llama.init_params(jax.random.key(0), cfg))


def tokens(seed, n):
    return np.random.default_rng(seed).integers(3, 128, n)


def test_the_configuration_reads_the_published_keys(model):
    cfg, params = model
    assert cfg.layer_kinds == ("attention", "kda", "kda", "kda", "attention")
    assert cfg.traits == ("kda",) and cfg.position_embedding_type == "nope"
    assert cfg.attention_gate_type == "elementwise" and cfg.topk_method == "noaux_tc"
    assert (cfg.num_experts, cfg.num_local_experts, cfg.first_local_expert) == (16, 4, 8)
    assert cfg.kda_short_conv == 4 and cfg.kda_allow_neg_eigval and cfg.shared_width == 32
    assert set(params["layers"]) == {"attention", "kda"}
    stack = params["layers"]["kda"]
    assert stack["conv_weight"].shape == (3, 4, 192) and stack["A_log"].shape == (3, 4)
    assert stack["f_a_proj"].shape == (3, 64, 16) and stack["g_b_proj"].shape == (3, 16, 64)
    assert stack["out_norm"].shape == (3, 16) and stack["router"].shape == (3, 64, 16)
    assert params["layers"]["attention"]["attn_gate"].shape == (2, 64, 64)
    # drawn as the family initialises them: decays of 0.2 to 0.999 a token
    a, dt = np.exp(stack["A_log"]), np.log1p(np.exp(stack["dt_bias"]))
    assert 1 <= a.min() and a.max() <= 16 and 1e-3 * 0.999 <= dt.min() and dt.max() <= 1e-1 * 1.001
    assert cfg.num_params() == costs_solar2.param_count(TINY)
    assert LlamaConfig.from_dict(cfg.to_dict()) == cfg
    for key, bad in (("kda_use_full_proj", True), ("use_rope", True), ("first_k_dense_replace", 1)):
        with pytest.raises(ValueError, match=f"written for {key}"):
            LlamaConfig.from_dict({**TINY, key: bad})
    with pytest.raises(ValueError, match="linear_attn_config.num_kv_heads"):
        LlamaConfig.from_dict({**TINY, "linear_attn_config": {**TINY["linear_attn_config"], "num_kv_heads": 2}})


def test_the_benchmarks_configuration_is_the_catalog_rows():
    with open(os.path.join(ROOT, "benchmark", "configs", "solar-open2-250b.json")) as f:
        raw = json.load(f)
    cfg = LlamaConfig.from_dict(raw)
    assert cfg.layer_kinds == ("attention", "kda", "kda", "kda")
    assert cfg.num_params() == 3_308_377_920 == raw["parameters"]["as_run"]
    assert costs_solar2.param_count(raw) == 3_308_377_920
    assert costs_solar2.published_param_count(raw) == 250_288_105_216 == raw["parameters"]["published"]
    assert (cfg.num_experts, cfg.held_experts, cfg.num_experts_per_tok) == (320, 40, 8)
    assert costs_solar2.slot_bytes(raw, 5120)["all"] == 33_996_800
    assert kda.state_shapes(cfg, 128) == ((3, 128, 64, 128, 128), (3, 3, 128, 24576))
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Solar-Open2-250B")
    assert raw["source"] == row["source_url"]
    for key, value in row["config"].items():  # every number under its key, but the three cut
        if key in raw["reduced"]:
            assert raw["published"][key] == value and raw[key] < value
        else:
            assert raw[key] == value, key


@pytest.mark.parametrize("n", [20, 70])
def test_the_training_forward_is_the_references(model, n):
    cfg, params = model
    seq = tokens(n, n)
    got = llama.forward(params, jnp.asarray(seq[None]), cfg, remat=False, **F32)[0]
    assert rel(got, reference_solar2.forward(params, jnp.asarray(seq[None]), TINY)[0]) < REL


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("n", [21, 53])
def test_the_five_forwards_agree_on_this_block(model, n, kernel, monkeypatch):
    """Training forward, whole-prompt prefill, the prompt in chunks (21 and 53
    end inside a chunk of 16 and inside a block of the chunked form), the
    decode steps through the ring, the states and the tails: one block, the
    reference's logits for the same tokens. A slot that holds no sequence keeps
    the state and the tail a former tenant left, and a prompt's first chunk
    starts from zeros whatever its slot held."""
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "16")
    cfg, params = model
    seq = tokens(n, n + 10)
    want = reference_solar2.forward(params, jnp.asarray(seq[None]), TINY)[0]
    ids = np.zeros((1, 64), np.int32)
    ids[0, :n] = seq[:n]
    whole, ks, vs, state, tail = llama.prefill_forward(params, jnp.asarray(ids), jnp.int32(n), cfg, **F32)
    assert rel(whole[0], want[n - 1]) < REL and ks.shape == (2, 64, 2, 16)
    assert state.shape == (3, 4, 16, 16) and tail.shape == (3, 3, 192)
    cache = ring_cache.init_kv_cache(cfg, SLOTS, RING, jnp.float32)
    held = ring_cache.init_kda_state(cfg, SLOTS, jnp.float32)
    # what a former tenant left, everywhere: a first chunk must not read it
    rings = [cache["k"], cache["v"], held["state"] + 3.0, held["tail"] + 3.0]
    view = llama.dataclasses.replace(cfg, q_chunk_size=CHUNK)
    # one program a forward (called piece by piece each scan would be a program of its
    # own, the same under both kernels: two workers then race for one cache entry)
    chunk = jax.jit(lambda p, part, plen, count, ck, cv, ks, kt: llama.chunk_prefill_forward(
        p, part, plen, count, 1, ck, cv, None, view, kda_state=ks, kda_tail=kt,
        decode_kernel=kernel, **F32))
    decode = jax.jit(lambda p, toks, lens, ck, cv, ks, kt: llama.decode_forward(
        p, toks, lens, ck, cv, view, kda_state=ks, kda_tail=kt, decode_kernel=kernel, **F32))
    for plen in range(0, n, CHUNK):
        count = min(CHUNK, n - plen)
        part = np.zeros((1, CHUNK), np.int32)
        part[0, :count] = seq[plen : plen + count]
        logits, ck, cv, _, ks_, kt_ = chunk(params, jnp.asarray(part), plen, count, *rings)
        rings = [ck, cv, ks_, kt_]
    assert rel(logits[0], want[n - 1]) < REL
    assert rel(rings[2][:, 1], state) < REL and rel(rings[3][:, :, 1], tail) < REL
    np.testing.assert_array_equal(rings[2][:, 0], 3.0)  # the other slots' as they were
    np.testing.assert_array_equal(rings[3][:, :, 2], 3.0)
    steps = []
    for i in range(10):
        toks = jnp.asarray([0, seq[n + i], 0], jnp.int32)
        lens = jnp.asarray([0, n + i, 0], jnp.int32)
        step, *rings = decode(params, toks, lens, *rings)
        steps.append(step[1])
    assert rel(jnp.stack(steps), want[n : n + 10]) < REL
    np.testing.assert_array_equal(rings[2][:, 0], 3.0)  # a slot at ``lens`` 0 keeps both
    np.testing.assert_array_equal(rings[3][:, :, 2], 3.0)
    np.testing.assert_array_equal(rings[0][:, 0], 0.0)  # and its ring is written nothing


@pytest.mark.parametrize("t,block,sub", [(150, 64, 16), (37, 64, 16), (100, 32, 8), (64, 16, 16)])
def test_the_chunked_form_is_the_recurrence_at_strong_decays(t, block, sub):
    """With an entering state, over a run that is not a multiple of the block,
    at decays of up to 3 a token and channel: over a block of 64 the split
    ``1 / Gamma`` would reach exp(192) and overflow float32 (exp(88)); the
    differences never do. And beyond ``length`` a token changes nothing."""
    rng = np.random.default_rng(t)
    b, h, d = 2, 3, 16
    q, k = (rng.standard_normal((b, t, h, d)).astype(np.float32) for _ in range(2))
    q, k = q / np.linalg.norm(q, axis=-1, keepdims=True), k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((b, t, h, d)).astype(np.float32)
    g = -rng.uniform(1e-3, 3.0, (b, t, h, d)).astype(np.float32)
    beta = rng.uniform(0, 2, (b, t, h)).astype(np.float32)
    s0 = rng.standard_normal((b, h, d, d)).astype(np.float32)
    if t >= block == 64:
        with np.errstate(over="ignore"):
            assert np.isinf(np.exp(-np.cumsum(g[:, :block], axis=1), dtype=np.float32)).any()
    o, s = kda.recurrence(q, k, v, g, beta, s0)
    o2, s2 = jax.jit(lambda *a: kda.chunked(*a, block=block, sub=sub))(q, k, v, g, beta, s0)
    assert rel(o2, o) < REL and rel(s2, s) < REL
    cut = t - 11
    o3, s3 = kda.chunked(q, k, v, g, beta, s0, length=jnp.int32(cut), block=block, sub=sub)
    o4, s4 = kda.recurrence(q[:, :cut], k[:, :cut], v[:, :cut], g[:, :cut], beta[:, :cut], s0)
    assert rel(o3[:, :cut], o4) < REL and rel(s3, s4) < REL


@pytest.fixture(scope="module")
def faulted(model):
    """The program's logits over a prompt of three chunks and the sound
    reference's, with the faults' chunk at the test's."""
    cfg, params = model
    seq = tokens(3, 45)
    got = llama.forward(params, jnp.asarray(seq[None]), cfg, remat=False, **F32)[0]
    return seq, got


@pytest.mark.parametrize("fault", FAULTS)
def test_each_assumed_equation_is_held(model, faulted, fault, monkeypatch):
    """The reference with one equation broken lies far from the program (and
    from the sound reference): a test of the five forwards would fail were the
    equation left out of the program."""
    monkeypatch.setattr(reference_solar2, "CHUNK", CHUNK)
    cfg, params = model
    seq, got = faulted
    assert rel(got, reference_solar2.forward(params, jnp.asarray(seq[None]), TINY)[0]) < REL
    broken = reference_solar2.forward(params, jnp.asarray(seq[None]), TINY, faults=(fault,))[0]
    assert rel(got, broken) > 200 * REL, fault


def test_the_eight_shares_add_up_to_the_whole_layer(model):
    """The share tied to the model: at a small size the four shares' routed
    terms (4 of 16 experts each, the router over all 16) and the shared
    expert's, counted once, add up to the uncut reference's layer; and the
    program's share is its reference's."""
    cfg, params = model
    ops = reference_solar2._Ops()
    whole_cfg = {**TINY, "n_routed_experts": 16}
    whole = llama.init_params(jax.random.key(4), LlamaConfig.from_dict(whole_cfg))
    w = {name: x[1] for name, x in whole["layers"]["kda"].items()}
    w["router"] = w["router"] * 25.0
    m = jnp.asarray(np.random.default_rng(9).standard_normal((19, 64)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = reference_solar2.routed_ffn(m, w, whole_cfg, ops)
        parts = reference_solar2._swiglu(m, w, ops, "shared_")
        for first in range(0, 16, 4):
            share = {**w, **{name: w[name][first : first + 4] for name in reference_solar2.EXPERTS}}
            part = reference_solar2.routed_ffn(
                m, share, {**TINY, "first_local_expert": first}, ops, shared=False)
            parts = parts + part
            view = llama.dataclasses.replace(cfg, first_local_expert=first)
            own = llama._ffn(view, m[None], {k: v for k, v in share.items()})[0][0]
            assert rel(own - reference_solar2._swiglu(m, w, ops, "shared_"), part) < REL
    assert rel(parts, want) < REL


def engine_of(model, **kw):
    cfg, params = model
    return ServeEngine(
        cfg, params, num_slots=SLOTS, max_context=RING, prefill_buckets=(), prefill_chunk=CHUNK,
        compute_dtype=jnp.float32, **kw,
    )


def test_the_engine_serves_it_in_chunks_through_the_batcher(model):
    """Every prompt goes in chunks; tokens equal the training forward's greedy
    ones; the counters count what the equations move; ``GET /stats`` names the
    forms."""
    cfg, params = model
    engine = engine_of(model)
    assert engine.needs_chunks(1) and engine.cfg.q_chunk_size == CHUNK
    batcher = ContinuousBatcher(engine).start()
    prompts = [tokens(i, n).tolist() for i, n in enumerate((9, 37, 16, 33))]
    reqs = [batcher.submit(p, max_new_tokens=5) for p in prompts]
    for r, p in zip(reqs, prompts):
        assert r.wait(120) and r.error is None, r.error
        # greedy, token by token: each is the training forward's choice behind the ones before
        # it (one forward over the prompt and the stream, not one a token: a program a length)
        seq = list(p) + list(r.tokens)
        logits = llama.forward(params, jnp.asarray([seq[:-1]], jnp.int32), cfg, remat=False, **F32)[0]
        assert len(r.tokens) == 5 and list(r.tokens) == jnp.argmax(logits[len(p) - 1 :], axis=-1).tolist()
    stats = batcher.stats()["kda"]
    batcher.stop()
    chunks = sum(-(-len(p) // CHUNK) for p in prompts)
    assert engine.prefill_chunks == chunks and stats["chunk_tokens"] == 3 * sum(map(len, prompts))
    assert stats["blocks_solved"] == 3 * 4 * chunks  # a block of 64 holds a chunk of 16
    assert stats["step_tokens"] == engine.kda_step_tokens > 0 and stats["step_tokens"] % 3 == 0
    slot_state = 3 * 4 * 16 * 16 * 4
    assert stats["state_resident_bytes"] == SLOTS * slot_state
    assert stats["tail_resident_bytes"] == SLOTS * 3 * 3 * 192 * 4
    assert stats["state_bytes_moved"] == 2 * slot_state * (chunks + stats["step_tokens"] // 3)
    assert stats["forms"] == {
        "step": "xla", "chunk": "chunked-xla", "block": 64, "sub_block": 16,
        "attention_step": "xla", "attention_chunk": "tiled-xla",
    }


def test_what_is_refused_is_refused_by_name(model):
    cfg, params = model
    with pytest.raises(ValueError, match="give the engine a prefill_chunk"):
        ServeEngine(cfg, params, num_slots=2, max_context=32, prefill_buckets=(16,))
    engine = engine_of(model)
    with pytest.raises(ValueError, match="kda linear-attention layers"):
        engine.admit(0, tokens(1, 20).tolist(), prefix_src=1, prefix_len=8)
    with pytest.raises(ValueError, match="kda linear-attention layers"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg, attn_impl="pallas")
    from opendiloco_tpu.models import hf_io

    with pytest.raises(ValueError, match="kda linear-attention layers"):
        hf_io._reject_moe(cfg, "export")
    with pytest.raises(ValueError, match="written for a stack of 'kda'"):
        LlamaConfig.from_dict({**TINY, "qk_norm": True})


# --- the decode step's kernel (PR 65): a live slot's state visited once, where it lies ---

WIDE = {**TINY, "head_dim": 128, "linear_attn_config": {**TINY["linear_attn_config"], "head_dim": 128}}


@pytest.fixture(scope="module")
def wide():
    """The file's stack with heads of 128: a head's state is a whole tile, the
    shape at which the decode step takes the kernel."""
    cfg = LlamaConfig.from_dict(WIDE)
    return cfg, spread(cfg, llama.init_params(jax.random.key(1), cfg))


def step_rows(seed, slots, heads, d=128):
    """A decode step's rows as ``kda.step_inputs`` hands them, at the family's
    strongest: unit keys, queries by D^-1/2, decays of down to exp(-3) a token
    and channel, beta up to 2; and the states a long sequence leaves."""
    rng = np.random.default_rng(seed)
    q, k = (rng.standard_normal((slots, heads, d)).astype(np.float32) for _ in range(2))
    q, k = q / np.linalg.norm(q, axis=-1, keepdims=True) * d**-0.5, k / np.linalg.norm(k, axis=-1, keepdims=True)
    v = rng.standard_normal((slots, heads, d)).astype(np.float32)
    g = -rng.uniform(1e-3, 3.0, (slots, heads, d)).astype(np.float32)
    beta = rng.uniform(0, 2, (slots, heads)).astype(np.float32)
    states = rng.standard_normal((3, slots, heads, d, d)).astype(np.float32)
    return (q, k, v, g, beta), states


@pytest.mark.parametrize("heads_a_step", [4, 2])
@pytest.mark.parametrize("live", [(1, 1, 1, 1, 1), (1, 0, 1, 1, 0), (0, 0, 1, 0, 1), (0, 0, 0, 0, 0)])
def test_the_step_kernel_is_the_recurrences_token(live, heads_a_step, monkeypatch):
    """``decode_kernels.kda_step`` interpreted beside ``kda.recurrence``'s one
    token: ``o`` and the live slots' states to float32's rounding; a dead
    slot's state (before the first live one, between two, behind the last,
    and where none is live) and every other layer's states bit for bit what
    went in; one block of all four heads a grid step, and two of two."""
    from opendiloco_tpu.ops import decode_kernels

    monkeypatch.setattr(decode_kernels, "_KDA_STATE_BYTES", heads_a_step * 128 * 128 * 4)
    assert decode_kernels._kda_heads(4, 128) == heads_a_step
    rows, states = step_rows(sum(live), 5, 4)
    live = np.asarray(live, bool)
    o, new = jax.jit(lambda *a: decode_kernels.kda_step(*a))(*rows, states, 1, jnp.asarray(live))
    want_o, want = kda.recurrence(*(x[:, None] for x in rows), states[1])
    np.testing.assert_array_equal(new[0], states[0])
    np.testing.assert_array_equal(new[2], states[2])
    np.testing.assert_array_equal(new[1][~live], states[1][~live])
    np.testing.assert_array_equal(o[~live], 0.0)
    if live.any():
        assert rel(o[live], want_o[live, 0]) < 1e-5 and rel(new[1][live], want[live]) < 1e-5
        assert rel(new[1][live], states[1][live]) > 0.1  # and it is another state


def test_the_step_kernel_is_the_xla_step_under_a_scan_over_the_layers(wide):
    """Through the model's own rows (``kda.step_inputs`` of a layer's weights)
    the kernel is ``kda.step``, the XLA form and the tests' reference, with the
    layer's index traced: a ``lax.scan`` over two of the three layers updates
    those two layers' states in the stack, each to its own ``kda.step``, and
    leaves the third's and a dead slot's as they were."""
    from opendiloco_tpu.ops import decode_kernels

    cfg, params = wide
    stack = {name: x[:2] for name, x in params["layers"]["kda"].items()}
    _, states = step_rows(11, SLOTS, 4)
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.standard_normal((SLOTS, 64)), jnp.float32)
    tails = jnp.asarray(rng.standard_normal((2, 3, SLOTS, 3 * 4 * 128)), jnp.float32)
    live = jnp.asarray([True, False, True])

    def body(states, xs):
        layer, li, tail = xs
        *rows, tail = kda.step_inputs(cfg, x, layer, tail, live)
        o, states = decode_kernels.kda_step(*rows, states, li, live)
        return states, (o, tail)

    new, (o, new_tails) = jax.jit(lambda s: jax.lax.scan(body, s, (stack, jnp.arange(2), tails)))(states)
    for li in range(2):
        layer = {name: x[li] for name, x in stack.items()}
        want_o, want, want_tail = kda.step(cfg, x, layer, states[li], tails[li], live)
        assert rel(o[li][live], want_o[live]) < 1e-5 and rel(new[li][live], want[live]) < 1e-5
        np.testing.assert_array_equal(new[li][1], states[li][1])
        np.testing.assert_array_equal(new_tails[li], want_tail)
    np.testing.assert_array_equal(new[2], states[2])


def test_the_engine_takes_the_step_kernel_where_a_heads_state_is_whole_tiles(model, wide, monkeypatch):
    """Which form the step takes is read from what the engine sees: the kernel
    under ``decode_kernel="pallas"`` (here interpreted) at heads of 128, where
    the stream is the XLA engine's token for token; the XLA form at this
    file's heads of 16 whatever the decode kernel, and off the chip."""
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "32")
    assert engine_of(model, decode_kernel="pallas").kda_forms["step"] == "xla"
    prompts = [tokens(i, n).tolist() for i, n in enumerate((9, 21))]
    streams = {}
    for kernel in ("xla", "pallas"):
        engine = engine_of(wide, decode_kernel=kernel)
        assert engine.kda_forms["step"] == kernel
        batcher = ContinuousBatcher(engine).start()
        reqs = [batcher.submit(p, max_new_tokens=4) for p in prompts]
        assert all(r.wait(240) and r.error is None for r in reqs), [r.error for r in reqs]
        streams[kernel] = [list(r.tokens) for r in reqs]
        assert batcher.stats()["kda"]["forms"]["step"] == kernel
        batcher.stop()
        assert engine.kda_step_tokens == 3 * sum(len(s) - 1 for s in streams[kernel])
    assert streams["pallas"] == streams["xla"]
    assert ServeEngine(*wide, num_slots=2, max_context=RING, prefill_buckets=(), prefill_chunk=CHUNK,
                       compute_dtype=jnp.float32).kda_forms["step"] == "xla"
