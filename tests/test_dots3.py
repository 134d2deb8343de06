"""dots3-note-prev's block (PR 54): two kinds of latent attention in one stack
(full layers under a learned indexer over a latent ring as long as the
context, sliding layers under a window over a wider latent ring that wraps), a
gate per head, prompts admitted in chunks over all three rings. At a small
size, in float32, against ``benchmark/odbench/reference_dots3.py`` (written
from the equations, nothing of the program's in it): the five forwards
(training, whole-prompt prefill, a prompt in chunks, the decode step in XLA and
under the interpreted kernel) over a context long enough that the sliding ring
wraps twice and the indexer drops rows; each assumed equation against the
reference with that equation broken; the eight shares of a layer's experts
against the uncut layer; the configuration's file; the engine's one copy of
the weights and its counters."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from odbench import costs_dots3, reference_dots3  # noqa: E402

from opendiloco_tpu.models import llama, ring_cache  # noqa: E402
from opendiloco_tpu.models.llama import LlamaConfig  # noqa: E402
from opendiloco_tpu.serve import ContinuousBatcher, ServeEngine  # noqa: E402

F32 = dict(compute_dtype=jnp.float32)
TINY = dict(
    model_type="dots3_note", hidden_size=64, intermediate_size=96, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=4, vocab_size=128, max_position_embeddings=512,
    rms_norm_eps=1e-5, rope_theta=80000000, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=8, first_k_dense_replace=1, moe_intermediate_size=32,
    n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2, norm_topk_prob=True,
    topk_method="noaux_tc", routed_scaling_factor=1, scoring_func="sigmoid",
    layer_types=["full_attention", "full_attention", "sliding_attention", "sliding_attention",
                 "sliding_attention", "full_attention"],
    index_head_dim=16, index_n_heads=4, index_topk=12, sliding_window_size=5,
    swa_num_attention_heads=2, swa_num_key_value_heads=2, swa_q_lora_rank=32, swa_kv_lora_rank=24,
    swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=8, swa_v_head_dim=8, swa_rope_theta=50000,
    attention_gate_type="headwise", swa_attention_gate_type="headwise",
    apply_mla_qkv_lora_rescale=True, q_chunk_size=8, num_local_experts=4,
    tie_word_embeddings=False, norm_init_std=0.1,
)
P, STEPS = 43, 5  # the sliding ring of 16 rows wraps twice; 12 of 43 rows are chosen


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.from_dict(TINY)
    params = llama.init_params(jax.random.key(0), cfg)
    ids = np.asarray(jax.random.randint(jax.random.key(1), (1, P + STEPS), 3, 128))
    want = np.asarray(reference_dots3.forward(params, ids, TINY))[0]
    return cfg, params, ids, want


def close(got, want, tol=3e-5):
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=tol)


def through_the_rings(cfg, params, ids, kernel):
    """The prompt in chunks into slot 1 of three, then ``STEPS`` decode steps
    -> (the last chunk's logits, each step's, the three rings)."""
    S, T, slot, C = 3, 64, 1, cfg.q_chunk_size
    cache = ring_cache.init_kv_cache(cfg, S, T, jnp.float32)
    ck, cv = cache["k"], cache["v"]
    ci = ring_cache.init_index_cache(cfg, S, T, jnp.float32)
    chunk = jax.jit(lambda i, plen, count, ck, cv, ci: llama.chunk_prefill_forward(
        params, i, plen, count, slot, ck, cv, ci, cfg, **F32))
    for plen in range(0, P, C):
        count = min(C, P - plen)
        x = np.zeros((1, C), np.int32)
        x[0, :count] = ids[0, plen : plen + count]
        last, ck, cv, ci = chunk(jnp.array(x), plen, count, ck, cv, ci)
    step = jax.jit(lambda t, l, ck, cv, ci: llama.decode_forward(
        params, t, l, ck, cv, cfg, index_cache=ci, decode_kernel=kernel, **F32))
    lens, rows = np.zeros(S, np.int32), []
    lens[slot] = P
    for i in range(STEPS):
        toks = np.zeros(S, np.int32)
        toks[slot] = ids[0, P + i]
        logits, ck, cv, ci = step(jnp.array(toks), jnp.array(lens), ck, cv, ci)
        jax.block_until_ready(logits)  # before ``lens`` changes: on the CPU its buffer may be the array's
        rows.append(logits[slot])
        lens[slot] += 1
    return last[0], rows, (ck, cv, ci)


def test_training_forward_and_whole_prompt_prefill_are_the_references(model):
    cfg, params, ids, want = model
    close(llama.forward(params, jnp.asarray(ids), cfg, remat=False, **F32)[0], want)
    padded = np.zeros((1, 48), np.int32)
    padded[0, :P] = ids[0, :P]
    logits, full, sliding, keys = llama.prefill_forward(params, jnp.asarray(padded), jnp.int32(P), cfg, **F32)
    close(logits[0], want[P - 1])
    assert full.shape == (2, 48, 24) and sliding.shape == (3, 48, 32) and keys.shape == (2, 48, 16)


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_chunks_then_decode_through_the_three_rings_are_the_references(model, kernel, monkeypatch):
    """A prompt in chunks equals the whole-prompt prefill equals the
    reference's row; the decode steps, in XLA and under the interpreted kernel
    (selection operand, window over a ring that wraps, slots at ``lens`` 0
    written nothing), give the reference's next rows; no other slot's rings
    are touched."""
    cfg, params, ids, want = model
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "8")
    last, rows, (ck, cv, ci) = through_the_rings(cfg, params, ids, kernel)
    close(last, want[P - 1])
    for i, row in enumerate(rows):
        close(row, want[P + i])
    assert ck.shape == (2, 3, 1, 24, 64) and cv.shape == (3, 3, 1, 32, 16)
    for ring in (ck, cv, ci):
        assert not np.any(np.asarray(ring[:, 0])) and not np.any(np.asarray(ring[:, 2]))


@pytest.mark.parametrize(
    "fault", ["no_gate", "no_rescale", "window_minus", "window_plus", "index_rotate_whole", "no_relu"]
)
def test_each_assumed_equation_is_in_the_program(model, fault):
    """The reference with one assumed equation broken (the gate dropped, the
    latents' rescale dropped, a window of 4 or of 6 where 5 is stated, the
    index key and queries rotated whole, the indexer without its ReLU) is far
    from the program, which is the sound reference's to 3e-5."""
    cfg, params, ids, want = model
    broken = np.asarray(reference_dots3.forward(params, ids, TINY, faults=(fault,)))[0]
    got = np.asarray(llama.forward(params, jnp.asarray(ids), cfg, remat=False, **F32)[0])
    assert np.linalg.norm(got - broken) > 2e-2 * np.linalg.norm(want)
    assert np.linalg.norm(got - want) < 1e-4 * np.linalg.norm(want)


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """A routed full layer cut eight ways (one of eight experts a share; the
    router, the shared expert and attention whole on each): the shares' outputs
    by the program, with what every share holds counted once, add up to the
    reference's uncut layer."""
    raw = dict(TINY, num_local_experts=None)
    whole = llama.init_params(jax.random.key(2), LlamaConfig.from_dict({**raw, "num_local_experts": 8}))
    w = jax.tree.map(lambda a: a[0], whole["layers"]["attention"])
    h = jax.random.normal(jax.random.key(3), (1, 24, 64), jnp.float32)
    uncut, _ = reference_dots3.layer_step(h[0], w, raw, "attention")
    no_expert = dict(w, **{k: jnp.zeros_like(w[k]) for k in ("gate_proj", "up_proj", "down_proj")})
    once, _ = reference_dots3.layer_step(h[0], no_expert, raw, "attention")  # h + attention + shared
    positions = jnp.arange(24)[None]
    total = 0.0
    for i in range(8):
        cfg = LlamaConfig.from_dict({**raw, "num_local_experts": 1, "first_local_expert": i})
        view = llama.kind_view(cfg, "attention")
        share = dict(w, **{k: w[k][i : i + 1] for k in ("gate_proj", "up_proj", "down_proj")})
        out, _ = llama.decoder_block(
            view, h, share, *llama._rope(view, positions),
            attend=llama.latent_attend(view, None),
        )
        total = total + (np.asarray(out[0]) - np.asarray(once))
    np.testing.assert_allclose(total + np.asarray(once), np.asarray(uncut), atol=2e-5)


def test_the_published_file_is_the_catalogs_and_counts_as_reckoned():
    with open(os.path.join(ROOT, "benchmark", "configs", "dots3-note-prev.json")) as f:
        raw = json.load(f)
    cfg = LlamaConfig.from_dict(raw)
    assert cfg.layer_kinds == ("dense", "attention", "sliding", "sliding", "sliding")
    assert cfg.num_params() == raw["parameters"]["as_run"] == costs_dots3.param_count(raw) == 4_087_154_176
    assert (cfg.num_full_layers, cfg.num_sliding_layers, cfg.held_experts, cfg.num_experts) == (2, 3, 32, 256)
    full, swa = llama.kind_view(cfg, "attention"), llama.kind_view(cfg, "sliding")
    assert (full.num_attention_heads, full.latent_row_dim, full.qk_head_dim, full.index_topk) == (128, 576, 192, 2048)
    assert (swa.num_attention_heads, swa.latent_row_dim, swa.qk_head_dim, swa.rope_theta) == (64, 1088, 256, 50000)
    assert swa.sliding_window_size == 513 and not swa.sparse and not full.sliding_window_size
    assert llama.latent_rescale(full) == (5**0.5, 10**0.5) and llama.latent_rescale(swa) == (5**0.5, 5**0.5)
    assert ring_cache.sliding_ring_rows(cfg) == 1024
    assert abs(costs_dots3.published_param_count(raw) - 279.55e9) < 0.01e9
    assert costs_dots3.ring_bytes(raw, 12, 25088, 1024)["all"] == raw["parameters"]["ring_bytes_12_slots_of_25088_rows"]
    again = LlamaConfig.from_dict(cfg.to_dict())
    assert again.layer_kinds == cfg.layer_kinds and again.num_params() == cfg.num_params()


@pytest.mark.parametrize("wrong, says", [
    (dict(swa_kv_lora_rank=0), "sliding latent layers need the swa_"),
    (dict(layer_types=["full_attention", "linear_attention"], num_hidden_layers=2), "full_attention"),
    (dict(attention_gate_type="elementwise"), "attention gate"),
    (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
])
def test_what_the_block_is_not_written_for_is_refused(wrong, says):
    with pytest.raises(ValueError, match=says):
        LlamaConfig.from_dict({**TINY, **wrong})


def test_the_lm_loss_trains_every_leaf_but_the_indexers(model):
    cfg, params, ids, _ = model

    def loss(p):
        logits = llama.forward(p, jnp.asarray(ids), cfg, remat=True, **F32)
        return llama.causal_lm_loss(logits, jnp.asarray(ids))

    grads = jax.grad(loss)(params)
    assert set(llama.untrained_by_the_lm_loss(cfg)) == set(llama.INDEXER_LEAVES)
    for kind, stack in grads["layers"].items():
        for name, g in stack.items():
            assert np.all(np.isfinite(np.asarray(g)))
            if name != "router_bias":  # the selection bias is chosen under, never weighed by
                assert bool(jnp.any(g != 0)) != (name in llama.INDEXER_LEAVES), (kind, name)


def test_the_engine_holds_one_copy_and_serves_through_the_batcher(model, monkeypatch):
    """``adopt_params``: a leaf that arrives in the compute dtype is the
    engine's own, no copy; a float32 leaf is copied as ever. Every prompt goes
    in chunks (no whole-prompt insert into a ring that wraps); four requests
    over three slots, chunks between decode steps, give the forward's greedy
    tokens; the counters, ``GET /stats``'s parts and the spans carry both
    kinds' rows."""
    cfg, params, _, _ = model
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "8")
    given = dict(params, final_norm=params["final_norm"].astype(jnp.bfloat16))
    engine = ServeEngine(
        cfg, given, num_slots=3, max_context=64, prefill_buckets=(32,), decode_kernel="pallas",
        adopt_params=True, **F32,
    )
    leaves = len(jax.tree.leaves(params))
    assert engine.weights_adopted == leaves - 1
    assert engine.params["embed_tokens"] is params["embed_tokens"]
    assert engine.params["final_norm"].dtype == jnp.float32
    assert ServeEngine(cfg, params, num_slots=1, max_context=16, **F32).weights_adopted == 0
    assert engine.needs_chunks(4) and engine.latent_forms["sliding"] == {
        "decode": "pallas", "chunk": "absorbed-xla", "block_t": 8, "rows_written_back": 8}
    batcher = ContinuousBatcher(engine).start()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(3, 128, n).tolist() for n in (43, 9, 30, 17)]
    reqs = [batcher.submit(p, max_new_tokens=6) for p in prompts]
    for req, prompt in zip(reqs, prompts):
        assert req.wait(120) and req.error is None, req.error
        seq = jnp.asarray([prompt + req.tokens[:-1]])
        want = jnp.argmax(llama.forward(params, seq, cfg, remat=False, **F32)[0, len(prompt) - 1 :], -1)
        assert req.tokens == np.asarray(want).tolist()
    stats = batcher.stats()
    batcher.stop()
    assert stats["latent"]["forms"] == engine.latent_forms
    assert stats["latent"]["swa_rows_read"] > 0 and stats["latent"]["latent_rows_read"] > 0
    assert stats["latent"]["swa_cache_resident_bytes"] == engine.cache_v.nbytes
    assert stats["dsa"]["prefill_chunks"] == sum(-(-len(p) // 8) for p in prompts)
    plan = stats["decode_plan"]
    assert plan["serve_decode_plan_mla_block_t"] == plan["serve_decode_plan_swa_block_t"] == 8
    assert plan["serve_decode_plan_mla_rows_written_back"] == 8
    assert plan["serve_decode_plan_swa_rows_written_back"] == 8
    assert plan["serve_swa_ring_rows"] == 16 and plan["serve_mla_ring_rows"] == 64


def test_an_engine_asked_for_the_kernels_runs_its_full_layers_chunks_through_the_latent_kernel(model, monkeypatch):
    """``odtp_latent_chunk_attn`` interpreted under the engine (PR 63): a prompt
    admitted in chunks gives the XLA engine's logits and chosen rows, and
    ``latent_forms`` says which form each kind's chunk took: the full layers'
    the kernel, the sliding layers' over their ring that wraps the XLA form. By
    the bytes rule a stack this small keeps the XLA form for both."""
    from opendiloco_tpu.ops import decode_kernels

    cfg, params, ids, want = model
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "8")
    make = lambda kernel: ServeEngine(
        cfg, params, num_slots=3, max_context=64, prefill_buckets=(), decode_kernel=kernel, **F32)
    assert make("pallas").latent_forms["full"]["chunk"] == "absorbed-xla"  # 4 heads x 8 x 8 float32 scores
    monkeypatch.setattr(decode_kernels, "_PREFILL_SCORE_BYTES", 0)
    engines = {kernel: make(kernel) for kernel in ("xla", "pallas")}
    forms = {kernel: {kind: f["chunk"] for kind, f in eng.latent_forms.items()} for kernel, eng in engines.items()}
    assert forms["xla"] == {"full": "absorbed-xla", "sliding": "absorbed-xla"}
    assert forms["pallas"] == {"full": "absorbed-pallas", "sliding": "absorbed-xla"}
    got, traced = {}, []  # the kernel's calls as the chunk program is traced: the queries' shapes
    real = llama.latent_chunk_attention
    monkeypatch.setattr(
        llama, "latent_chunk_attention", lambda q, *a, **kw: traced.append(q.shape) or real(q, *a, **kw))
    for kernel, eng in engines.items():
        eng.keep_row_choices()
        tok, logits = eng.admit(1, ids[0, :P].tolist())
        got[kernel] = (tok, np.asarray(logits), np.asarray(eng.row_choices))
        assert eng.prefill_chunks == -(-P // cfg.q_chunk_size)
        assert set(traced) == ({(8, 4, 24)} if kernel == "pallas" else set())  # the full layers' alone
    close(got["pallas"][1], want[P - 1])
    close(got["pallas"][1], got["xla"][1])
    assert got["pallas"][0] == got["xla"][0] == int(np.argmax(want[P - 1]))
    assert (got["pallas"][2] == got["xla"][2]).all() and got["pallas"][2].sum() == 2 * cfg.index_topk


def test_a_ring_the_kernel_cannot_tile_is_refused_at_construction(model):
    cfg = LlamaConfig.from_dict({**TINY, "qk_rope_head_dim": 4})  # rows of 20 values
    params = llama.init_params(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="no tile for this stack's latent rings"):
        ServeEngine(cfg, params, num_slots=2, max_context=64, decode_kernel="pallas", **F32)
    with pytest.raises(ValueError, match="under an indexer"):
        ServeEngine(LlamaConfig.from_dict({**TINY, "index_topk": 0, "index_n_heads": 0, "index_head_dim": 0}),
                    params, num_slots=2, max_context=64, **F32)
