"""Laguna-S-2.1's block (PR 56): full and sliding grouped-query layers in one
stack (two head counts over the same KV heads, two ``(k, v)`` rings of two
lifetimes a slot, the sliding one wrapping under a window, rope tables by kind:
half-rotated YaRN beside plain rope), a gate per head, softmax routing scaled
by 2.5 beside a shared expert, prompts admitted in chunks of the engine's own
size. At a small size, in float32, against
``benchmark/odbench/reference_laguna.py`` (written from the equations, nothing
of the program's in it): the five forwards (training, whole-prompt prefill, a
prompt in chunks that ends inside a chunk, the decode step in XLA and under the
interpreted kernel) over a context long enough that the sliding ring wraps
three times; each assumed equation against the reference with that equation
broken; the four shares of a layer's experts against the uncut layer; the
configuration's file; the engine's chunk, its one copy of the weights and its
counters."""

import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from odbench import costs_laguna, reference_laguna  # noqa: E402

from opendiloco_tpu.models import llama, ring_cache  # noqa: E402
from opendiloco_tpu.models.llama import LlamaConfig  # noqa: E402
from opendiloco_tpu.serve import ContinuousBatcher, ServeEngine  # noqa: E402

F32 = dict(compute_dtype=jnp.float32)
TINY = dict(
    model_type="laguna", vocab_size=128, hidden_size=64, intermediate_size=96, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16, max_position_embeddings=512,
    attention_bias=False, rms_norm_eps=1e-6, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=32, shared_expert_intermediate_size=32, norm_topk_prob=True,
    decoder_sparse_step=1, mlp_only_layers=[0], tie_word_embeddings=False, gating="per-head",
    sliding_window=5,
    rope_parameters={
        "full_attention": {"rope_theta": 500000, "rope_type": "yarn", "factor": 8,
                           "original_max_position_embeddings": 16, "beta_slow": 1, "beta_fast": 4,
                           "attention_factor": 1.2, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000, "partial_rotary_factor": 1},
    },
    layer_types=["full_attention", "sliding_attention", "sliding_attention", "sliding_attention",
                 "full_attention", "sliding_attention"],
    moe_apply_router_weight_on_input=False,
    mlp_layer_types=["dense", "sparse", "sparse", "sparse", "sparse", "sparse"],
    gating_types=["per_head"] * 6, moe_routed_scaling_factor=2.5,
    num_attention_heads_per_layer=[4, 6, 6, 6, 4, 6], moe_router_logit_softcapping=0,
    num_local_experts=4, first_local_expert=0, norm_init_std=0.1,
)
CHUNK = 8
P, STEPS = 43, 12  # five chunks and three rows; the sliding ring of 16 rows wraps three times


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.from_dict(TINY)
    params = llama.init_params(jax.random.key(0), cfg)
    for kind in ("attention", "sliding"):  # a router that spreads: no choice near a tie
        params["layers"][kind]["router"] = params["layers"][kind]["router"] * 25.0
    for stack in params["layers"].values():  # scores of order one: a rotation that matters
        stack["q_proj"], stack["k_proj"] = stack["q_proj"] * 6.0, stack["k_proj"] * 6.0
    ids = np.asarray(jax.random.randint(jax.random.key(1), (1, P + STEPS), 3, 128))
    want = np.asarray(reference_laguna.forward(params, ids, TINY))[0]
    return cfg, params, ids, want


def close(got, want, tol=3e-5):
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=tol)


def through_the_rings(cfg, params, ids, kernel):
    """The prompt in chunks into slot 1 of three, then ``STEPS`` decode steps
    -> (the last chunk's logits, each step's, the two pairs of rings)."""
    cfg = dataclasses.replace(cfg, q_chunk_size=CHUNK)  # as an engine lays its own chunk
    S, T, slot = 3, 64, 1
    cache = ring_cache.init_kv_cache(cfg, S, T, jnp.float32)
    ck, cv = cache["k"], cache["v"]
    chunk = jax.jit(lambda i, plen, count, ck, cv: llama.chunk_prefill_forward(
        params, i, plen, count, slot, ck, cv, None, cfg, **F32)[:3])
    for plen in range(0, P, CHUNK):
        count = min(CHUNK, P - plen)
        x = np.zeros((1, CHUNK), np.int32)
        x[0, :count] = ids[0, plen : plen + count]
        last, ck, cv = chunk(jnp.array(x), plen, count, ck, cv)
    step = jax.jit(lambda t, l, ck, cv: llama.decode_forward(
        params, t, l, ck, cv, cfg, decode_kernel=kernel, **F32))
    lens, rows = np.zeros(S, np.int32), []
    lens[slot] = P
    for i in range(STEPS):
        toks = np.zeros(S, np.int32)
        toks[slot] = ids[0, P + i]
        logits, ck, cv = step(jnp.array(toks), jnp.array(lens), ck, cv)
        jax.block_until_ready(logits)  # before ``lens`` changes: on the CPU its buffer may be the array's
        rows.append(logits[slot])
        lens[slot] += 1
    return last[0], rows, (ck, cv)


def test_training_forward_and_whole_prompt_prefill_are_the_references(model):
    cfg, params, ids, want = model
    assert cfg.layer_kinds == ("dense", "sliding", "sliding", "sliding", "attention")
    close(llama.forward(params, jnp.asarray(ids), cfg, remat=False, **F32)[0], want)
    padded = np.zeros((1, 48), np.int32)
    padded[0, :P] = ids[0, :P]
    logits, full, sliding = llama.prefill_forward(params, jnp.asarray(padded), jnp.int32(P), cfg, **F32)
    close(logits[0], want[P - 1])
    assert full.k.shape == full.v.shape == (2, 48, 2, 16) and sliding.k.shape == (3, 48, 2, 16)
    with pytest.raises(ValueError, match="refused for a stack with sliding layers"):
        llama.forward(params, jnp.asarray(ids), cfg, attn_impl="pallas", **F32)


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_chunks_then_decode_through_both_rings_are_the_references(model, kernel, monkeypatch):
    """A prompt in chunks (the last one three rows of eight) equals the
    whole-prompt prefill equals the reference's row; the decode steps, in XLA
    and under the interpreted kernel (the window over a ring that wraps, at 2
    and 3 query heads a KV head; slots at ``lens`` 0 written nothing), give the
    reference's next rows; no other slot's rings are touched."""
    cfg, params, ids, want = model
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "8")
    last, rows, (ck, cv) = through_the_rings(cfg, params, ids, kernel)
    close(last, want[P - 1])
    for i, row in enumerate(rows):
        close(row, want[P + i])
    assert ck.shape == (2, 3, 2, 16, 64) and cv.shape == (3, 3, 2, 16, 16)
    for ring in (*ck, *cv):
        assert np.any(np.asarray(ring[:, 1]))
        assert not np.any(np.asarray(ring[:, 0])) and not np.any(np.asarray(ring[:, 2]))


@pytest.mark.parametrize("fault", [
    "no_gate", "no_factor", "no_ramp", "rotate_whole", "swap_rope", "window_minus", "window_plus",
    "sigmoid_scores", "no_scaling",
])
def test_each_assumed_equation_is_in_the_program(model, fault):
    """The reference with one assumed equation broken (the gate dropped; cos
    and sin without YaRN's factor; the pairs at their plain frequencies; a full
    layer's whole head rotated; each kind under the other's tables; a window of
    4 or of 6 where 5 is stated; sigmoid scores where softmax; the routed
    weights without the 2.5) is far from the program, which is the sound
    reference's to 3e-5."""
    cfg, params, ids, want = model
    broken = np.asarray(reference_laguna.forward(params, ids, TINY, faults=(fault,)))[0]
    got = np.asarray(llama.forward(params, jnp.asarray(ids), cfg, remat=False, **F32)[0])
    assert np.linalg.norm(got - broken) > 1e-2 * np.linalg.norm(want)
    assert np.linalg.norm(got - want) < 1e-4 * np.linalg.norm(want)


def test_the_four_shares_add_up_to_the_uncut_layer(model):
    """The shares tie to the model: the routed parts that four chips compute,
    each from its own quarter of the experts, add up to the uncut layer's
    routed part, and with the shared expert counted once to the reference's
    uncut FFN."""
    cfg, params, _, _ = model
    kind_view = llama.kind_view(cfg, "sliding")
    whole = dataclasses.replace(kind_view, num_local_experts=None)
    rng = jax.random.key(3)
    layer = {
        name: jax.random.normal(jax.random.fold_in(rng, i), shape) * 0.3
        for i, (name, shape) in enumerate({
            "router": (64, 8), "gate_proj": (8, 64, 32), "up_proj": (8, 64, 32),
            "down_proj": (8, 32, 64), "shared_gate_proj": (64, 32), "shared_up_proj": (64, 32),
            "shared_down_proj": (32, 64)}.items())
    }
    x = jax.random.normal(jax.random.fold_in(rng, 99), (24, 64))
    uncut, _, counts = llama._ffn(whole, x, layer)
    assert int(counts[0]) == 24 * 2
    routed = lambda c, w: llama._routed_ffn(c, x, w, None)[0]
    shares = 0.0
    for first in range(0, 8, 2):
        held = dataclasses.replace(kind_view, num_local_experts=2, first_local_expert=first)
        cut = {k: (v[first : first + 2] if k in llama.EXPERT_LEAVES else v) for k, v in layer.items()}
        shares = shares + routed(held, cut)
    close(shares, routed(whole, layer), 2e-5)
    uncut_cfg = {k: v for k, v in TINY.items() if k not in ("num_local_experts", "first_local_expert")}
    with jax.default_matmul_precision("highest"):
        want = reference_laguna.routed_ffn(x, layer, uncut_cfg, reference_laguna._Ops())
    close(uncut, want, 2e-5)
    close(shares + llama._swiglu(x, layer, "shared_"), want, 2e-5)


def test_the_published_file_is_the_catalogs_and_counts_as_reckoned():
    with open(os.path.join(ROOT, "benchmark", "configs", "laguna-s-2.1.json")) as f:
        raw = json.load(f)
    cfg = LlamaConfig.from_dict(raw)
    assert cfg.layer_kinds == ("dense", "sliding", "sliding", "sliding", "attention", "sliding",
                               "sliding", "sliding")
    assert (cfg.num_attention_heads, cfg.swa_num_attention_heads, cfg.kv_heads, cfg.head_dim) == (
        48, 72, 8, 128)
    assert (cfg.sliding_window_size, cfg.rope_theta, cfg.swa_rope_theta) == (512, 500000.0, 10000.0)
    assert (cfg.partial_rotary_factor, cfg.swa_partial_rotary_factor) == (0.5, 1.0)
    assert dict(cfg.rope_yarn)["attention_factor"] == 1.4852030263919618
    assert (cfg.num_experts, cfg.held_experts, cfg.num_experts_per_tok, cfg.routed_scaling_factor,
            cfg.shared_width, cfg.expert_width) == (256, 64, 10, 2.5, 1024, 1024)
    assert cfg.q_chunk_size == 0  # no key of the file: the engine's
    assert cfg.num_params() == costs_laguna.param_count(raw) == raw["parameters"]["as_run"] == 5_034_052_608
    assert LlamaConfig.from_dict(cfg.to_dict()) == cfg
    low, high = 9, 18  # the ramp's ends, by the file's own arithmetic
    f, factor = llama._yarn_frequencies(64, 500000.0, cfg.rope_yarn)
    plain = 500000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(f[: low + 1], plain[: low + 1], rtol=1e-6)
    np.testing.assert_allclose(f[high:], plain[high:] / 128, rtol=1e-6)
    assert factor == 1.4852030263919618 and plain[12] / 128 < f[12] < plain[12]


@pytest.mark.parametrize("wrong, says", [
    ({"attention_bias": True}, "attention_bias False"),
    ({"moe_router_logit_softcapping": 30}, "moe_router_logit_softcapping 0"),
    ({"gating": "per-channel"}, "a gate per head"),
    ({"mlp_only_layers": []}, "disagree on the dense layers"),
    ({"num_attention_heads_per_layer": [6, 6, 6, 6, 4, 6]}, "full layer of 6 heads"),
    ({"num_attention_heads_per_layer": [4, 6, 8, 6, 4, 6]}, "one head count"),
    ({"layer_types": ["sliding_attention"] * 6}, "a dense FFN under a full one"),
    ({"rope_parameters": {"full_attention": {"rope_type": "llama3"}}}, "YaRN or plain rope"),
    ({"sliding_window": 0}, "sliding grouped-query layers need"),
])
def test_what_the_block_is_not_written_for_is_refused(wrong, says):
    with pytest.raises(ValueError, match=says):
        LlamaConfig.from_dict({**TINY, **wrong})


def test_the_lm_loss_trains_every_leaf_and_the_specs_cover_the_tree(model):
    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.parallel.sharding import param_specs

    cfg, params, ids, _ = model

    def loss(p):
        logits = llama.forward(p, jnp.asarray(ids), cfg, remat=True, **F32)
        return llama.causal_lm_loss(logits, jnp.asarray(ids))

    grads = jax.grad(loss)(params)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    dead = [jax.tree_util.keystr(path) for path, g in flat if not np.any(np.asarray(g))]
    assert not dead, dead
    specs = param_specs(cfg, build_mesh("NO_SHARD", devices=jax.devices()[:1]))
    assert jax.tree.structure(specs) == jax.tree.structure(llama.shapes(cfg))


def test_the_engine_takes_its_own_chunk_and_serves_through_the_batcher(model, monkeypatch):
    """``ServeEngine(prefill_chunk=)`` lays the chunk over a configuration that
    names none; every prompt goes in chunks between decode steps; the tokens
    are the forward's; the counters, the forms and the plans by kind are on
    ``stats()``; the caller's tree is adopted where it is in the compute dtype."""
    cfg, params, _, _ = model
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "8")
    with pytest.raises(ValueError, match="give the engine a prefill_chunk"):
        ServeEngine(cfg, params, num_slots=2, max_context=48, prefill_buckets=(), **F32)
    with pytest.raises(ValueError, match="not whole chunks"):
        ServeEngine(cfg, params, num_slots=2, max_context=50, prefill_buckets=(), prefill_chunk=8, **F32)
    dense = LlamaConfig(hidden_size=32, intermediate_size=64, num_hidden_layers=1, num_attention_heads=2,
                        vocab_size=64)
    with pytest.raises(ValueError, match="prefill_chunk 8 is the engine's to give only"):
        ServeEngine(dense, llama.init_params(jax.random.key(0), dense), prefill_chunk=8, **F32)
    engine = ServeEngine(
        cfg, params, num_slots=3, max_context=48, prefill_buckets=(), prefill_chunk=CHUNK,
        decode_kernel="pallas", adopt_params=True, **F32,
    )
    assert engine.cfg.q_chunk_size == CHUNK and engine.needs_chunks(3)
    assert engine.weights_adopted == len(jax.tree.leaves(params))
    assert engine.cache_k.shape == (2, 3, 2, 16, 48) and engine.cache_v.shape == (3, 3, 2, 16, 16)
    forms = engine.kind_forms
    assert forms["full"] == {"decode": "pallas", "chunk": "tiled-xla", "block_t": 8, "heads": 2}
    assert forms["sliding"]["chunk"] == "banded-xla" and forms["sliding"]["band_block"] == 8
    batcher = ContinuousBatcher(engine).start()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, 128, n).tolist() for n in (9, 20, 33, 17)]
    reqs = [batcher.submit(p, max_new_tokens=5) for p in prompts]
    for r in reqs:
        assert r.wait(300) and r.error is None, r.error
    stats = batcher.stats()
    batcher.stop()
    full = lambda seq: llama.forward(params, jnp.asarray([seq], jnp.int32), cfg, remat=False, **F32)[0]
    for prompt, req in zip(prompts, reqs):  # the forward over the whole answer gives each token
        rows = full(list(prompt) + list(req.tokens))[len(prompt) - 1 : -1]
        assert np.asarray(jnp.argmax(rows, axis=-1)).tolist() == list(req.tokens)
    kinds = stats["kinds"]
    assert kinds["forms"] == forms and kinds["swa_rows_read"] > 0 and kinds["full_rows_read"] > 0
    assert stats["dsa"]["prefill_chunks"] == sum(-(-len(p) // CHUNK) for p in prompts)
    plan = stats["decode_plan"]
    assert plan["serve_swa_ring_rows"] == 16 and plan["serve_full_ring_rows"] == 48
    # a sliding layer's grid is the tiles a window can cross (2 of 8 rows under a window of 5)
    assert plan["serve_decode_grid_steps"] == 2 * 3 * 6 + 3 * 3 * 2
    with pytest.raises(ValueError, match="sliding layers"):
        ContinuousBatcher(engine, prefix_cache=True)


def test_an_engine_asked_for_the_kernels_runs_its_full_layers_chunks_through_the_chunk_kernel(
    model, monkeypatch
):
    """``odtp_chunk_attn`` interpreted under the engine's full layers (a ring of
    three tiles of 16 rows, the causal mask alone): the XLA engine's greedy
    tokens, and ``kind_forms`` says which form ran; the sliding layers keep the
    band."""
    from opendiloco_tpu.ops import decode_kernels

    cfg, params, _, _ = model
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "8")
    monkeypatch.setattr(llama, "_SUFFIX_TILE", 16)
    monkeypatch.setattr(decode_kernels, "_PREFILL_SCORE_BYTES", 0)
    prompt = np.random.default_rng(3).integers(3, 128, 29).tolist()
    got = {}
    for kernel in ("xla", "pallas"):
        engine = ServeEngine(cfg, params, num_slots=3, max_context=48, prefill_buckets=(),
                             prefill_chunk=CHUNK, decode_kernel=kernel, **F32)
        form = "tiles-pallas" if kernel == "pallas" else "tiled-xla"
        assert engine.kind_forms["full"]["chunk"] == engine.chunk_form == form
        assert engine.kind_forms["sliding"]["chunk"] == "banded-xla"
        tok, logits = engine.admit(1, prompt)
        toks, lens, out = np.asarray([0, tok, 0]), np.asarray([0, 29, 0]), [tok]
        for _ in range(4):
            nxt, _ = engine.decode_step(toks, lens)
            toks, lens = np.asarray([0, nxt[1], 0]), lens + np.asarray([0, 1, 0])
            out.append(int(nxt[1]))
        got[kernel] = (out, np.asarray(logits))
    assert got["pallas"][0] == got["xla"][0]
    close(got["pallas"][1], got["xla"][1])


@pytest.mark.parametrize("feature", ["prefix reuse", "page-out", "page-in"])
def test_what_handles_one_ring_from_row_0_refuses_the_stack_by_name(model, feature):
    """The engine's three sites ask the table the scheduler asks, so each refuses
    by name before it reaches a ``take`` over a ``RingPair`` or a list of no buckets."""
    cfg, params, _, _ = model
    engine = ServeEngine(cfg, params, num_slots=2, max_context=48, prefill_buckets=(),
                         prefill_chunk=CHUNK, **F32)
    rows = np.zeros((5, 16, 2, 16), np.float32)
    call = {
        "prefix reuse": lambda: engine.admit(1, list(range(3, 19)), prefix_src=0, prefix_len=12),
        "page-out": lambda: engine.fetch_slot_pages(0, 16),
        "page-in": lambda: engine.install_slot_pages(0, rows, rows),
    }[feature]
    with pytest.raises(ValueError, match=f"{feature}.* is refused for a configuration with sliding layers"):
        call()


def test_a_ring_the_kernel_cannot_tile_is_refused_at_construction(model, monkeypatch):
    cfg, params, _, _ = model
    monkeypatch.delenv("ODTP_DECODE_BLOCK_T", raising=False)
    with pytest.raises(ValueError, match="no plan for this stack's rings"):
        ServeEngine(cfg, params, num_slots=2, max_context=48, prefill_buckets=(), prefill_chunk=8,
                    decode_kernel="pallas", **F32)
