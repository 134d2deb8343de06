"""MiniCPM-SALA's block (PR 61): lightning linear-attention layers (a decaying
float32 state a layer and slot, carried from chunk to chunk of a prompt)
beside NoPE grouped-query layers under a selection by blocks (a pooled-key
ring beside K and V), the family's three constant scalings, an elementwise
gate. At a small size, in float32, against
``benchmark/odbench/reference_sala.py`` (written from the equations, nothing
of the program's in it; its lightning layers run the recurrence token by
token): the five forwards (training, whole-prompt prefill, a prompt in chunks
that ends inside a chunk and inside a pooling window, the decode step in XLA
and under the interpreted kernels) on each side of ``dense_len``; the chunked
lightning form against the recurrence; each assumed equation against the
reference with that equation broken; the configuration's file; the engine's
chunks, its counters and what it refuses."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from odbench import costs_sala, reference_sala  # noqa: E402

from opendiloco_tpu.models import lightning, llama, ring_cache  # noqa: E402
from opendiloco_tpu.models.llama import LlamaConfig  # noqa: E402
from opendiloco_tpu.serve import ContinuousBatcher, ServeEngine  # noqa: E402

F32 = dict(compute_dtype=jnp.float32)
SIZES = dict(kernel_size=8, kernel_stride=4, block_size=8, topk=4, init_blocks=1, window_size=16,
             dense_len=32)
TINY = dict(
    model_type="minicpm_sala", vocab_size=128, hidden_size=64, intermediate_size=96,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    # six published layers of which the cut runs four: S L L S
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4", "lightning-attn", "minicpm4"],
    qk_norm=True, attn_use_rope=False, lightning_use_rope=True, lightning_nh=4, lightning_nkv=4,
    lightning_head_dim=16, lightning_scale="1/sqrt(d)", scale_emb=12, scale_depth=1.4,
    dim_model_base=16, use_output_gate=True, use_output_norm=True, attn_use_output_gate=True,
    rms_norm_eps=1e-6, max_position_embeddings=256, norm_init_std=0.1, sparse_config=SIZES,
)
CHUNK, RING, SLOTS = 16, 96, 3
REL = 2e-6  # float32 against float32: two orders under the least a fault moves


def rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a) - jnp.asarray(b)) / jnp.linalg.norm(jnp.asarray(b)))


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig.from_dict(TINY)
    return cfg, llama.init_params(jax.random.key(0), cfg)


def tokens(seed, n):
    return np.random.default_rng(seed).integers(3, 128, n)


@pytest.fixture(scope="module")
def long_forward(model):
    """A sequence past ``dense_len``: the program's logits and the reference's."""
    cfg, params = model
    seq = tokens(70, 70)
    got = llama.forward(params, jnp.asarray(seq[None]), cfg, remat=False, **F32)[0]
    return seq, got, reference_sala.forward(params, jnp.asarray(seq[None]), TINY)[0]


def test_the_configuration_reads_the_published_keys(model):
    cfg, params = model
    assert cfg.layer_kinds == ("attention", "lightning", "lightning", "attention")
    assert cfg.traits == ("linear", "blocks") and cfg.position_embedding_type == "nope"
    assert cfg.embedding_multiplier == 12 and cfg.logits_scaling == 4.0
    assert cfg.residual_multiplier == pytest.approx(1.4 / 6**0.5)  # the published depth, not the cut's
    assert cfg.qk_norm_per_head and not cfg.qk_norm and cfg.attention_gate_type == "elementwise"
    # the decays by the family's rule, by the layer's index in the published list
    want = lightning.decay_rates([1, 2], 4, 6)
    assert cfg.lightning_decays == want
    assert want[0][0] == pytest.approx(2 ** (-8 / 4) * (1 - 1 / 5 + 1e-5))
    np.testing.assert_allclose(reference_sala.decay_rates(TINY), np.asarray(want), rtol=1e-6)
    assert set(params["layers"]) == {"attention", "lightning"}
    assert params["layers"]["lightning"]["out_norm"].shape == (2, 64)
    assert params["layers"]["attention"]["attn_gate"].shape == (2, 64, 64)
    assert cfg.num_params() == costs_sala.param_count(TINY)
    assert LlamaConfig.from_dict(cfg.to_dict()) == cfg
    # a table in the file replaces the rule without a change to the program
    table = [[0.5, 0.25, 0.125, 0.0625]] * 2
    assert LlamaConfig.from_dict({**TINY, "lightning_decays": table}).lightning_decays[1][2] == 0.125
    for key, bad in (("lightning_use_rope", False), ("attn_use_rope", True), ("lightning_nkv", 2),
                     ("use_output_norm", False), ("lightning_scale", "1")):
        with pytest.raises(ValueError, match=f"written for {key}"):
            LlamaConfig.from_dict({**TINY, key: bad})
    with pytest.raises(ValueError, match="sparse_config"):
        LlamaConfig.from_dict({**TINY, "sparse_config": {**SIZES, "topk": 2}})  # under the forced blocks


def test_the_benchmarks_configuration_is_the_catalog_rows():
    with open(os.path.join(ROOT, "benchmark", "configs", "minicpm-sala.json")) as f:
        raw = json.load(f)
    cfg = LlamaConfig.from_dict(raw)
    assert (cfg.num_attention_layers, cfg.num_lightning_layers) == (4, 14)
    assert cfg.num_params() == 5_609_898_496 == raw["parameters"]["as_run"]
    assert cfg.residual_multiplier == pytest.approx(1.4 / 32**0.5) and cfg.logits_scaling == 16.0
    assert cfg.block_sizes.topk == 64 and cfg.block_sizes.dense_len == 8192
    assert len(cfg.lightning_decays) == 14 and len(cfg.lightning_decays[0]) == 32
    # layer 1 of the published 32 is the first lightning layer
    assert cfg.lightning_decays[0][31] == pytest.approx(2**-8 * (1 - 1 / 31 + 1e-5))


@pytest.mark.parametrize("n", [20, 70])
def test_the_training_forward_is_the_references(model, n):
    """On each side of ``dense_len`` (32): every row read, and the selection."""
    cfg, params = model
    seq = tokens(n, n)
    got = llama.forward(params, jnp.asarray(seq[None]), cfg, remat=False, **F32)[0]
    assert rel(got, reference_sala.forward(params, jnp.asarray(seq[None]), TINY)[0]) < REL


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("n", [21, 53])
def test_the_five_forwards_agree_on_this_block(model, n, kernel, monkeypatch):
    """Training forward, whole-prompt prefill, the prompt in chunks (21 and 53
    end inside a chunk of 16 and inside a pooling window; 21 is under
    ``dense_len``, 53 past it), the decode steps through both rings and the
    state: one block, the reference's logits for the same tokens. A slot that
    holds no sequence keeps the state a former tenant left, and a prompt's
    first chunk starts from zeros whatever its slot held."""
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "16")
    cfg, params = model
    seq = tokens(n, n + 10)
    want = reference_sala.forward(params, jnp.asarray(seq[None]), TINY, prompt_len=n)[0]
    ids = np.zeros((1, 64), np.int32)
    ids[0, :n] = seq[:n]
    whole, ks, vs, left = llama.prefill_forward(params, jnp.asarray(ids), jnp.int32(n), cfg, **F32)
    assert rel(whole[0], want[n - 1]) < REL and ks.shape == (2, 64, 2, 16)
    cache = ring_cache.init_kv_cache(cfg, SLOTS, RING, jnp.float32)
    rings = (cache["k"], cache["v"], ring_cache.init_pooled_cache(cfg, SLOTS, RING, jnp.float32),
             ring_cache.init_lightning_state(cfg, SLOTS) + 3.0)
    chunk = jax.jit(lambda ids, plen, count, ck, cv, pc, ls: llama.chunk_prefill_forward(
        params, ids, plen, count, 1, ck, cv, None, cfg, pooled_cache=pc, lightning_state=ls,
        total=n, **F32))
    for plen in range(0, n, CHUNK):
        count = min(CHUNK, n - plen)
        ids = np.zeros((1, CHUNK), np.int32)
        ids[0, :count] = seq[plen : plen + count]
        logits, ck, cv, _, pc, ls = chunk(jnp.asarray(ids), plen, count, *rings)
        rings = (ck, cv, pc, ls)
        assert rel(logits[0], want[plen + count - 1]) < REL
    assert rel(rings[3][:, 1], left) < REL  # what the chunks left is what the whole prompt leaves
    step = jax.jit(lambda tok, lens, ck, cv, pc, ls: llama.decode_forward(
        params, tok, lens, ck, cv, cfg, pooled_cache=pc, lightning_state=ls, decode_kernel=kernel,
        return_block_tiles=True, **F32))
    for pos in range(n, n + 10):  # past a window's close (a multiple of 4 less one) and a block's edge
        tok, lens = jnp.asarray([0, seq[pos], 0], jnp.int32), jnp.asarray([0, pos, 0], jnp.int32)
        logits, *rings, tiles = step(tok, lens, *rings)
        assert rel(logits[1], want[pos]) < REL
        assert 0 < int(tiles[0]) <= 2 * 2 * -(-pos // 16)
    assert float(jnp.abs(rings[3][:, 0] - 3.0).max()) == 0.0  # a slot at ``lens`` 0 is written nothing


@pytest.mark.parametrize("t,block,entering,length", [
    (50, 16, True, None),  # not a multiple of the block, with an entering state
    (32, 16, False, None), (37, 8, True, 29), (5, 16, True, 5),
])
def test_the_chunked_lightning_form_is_the_recurrence(t, block, entering, length):
    key = jax.random.key(t)
    q, k, v = (jax.random.normal(kk, (2, t, 3, 8), jnp.float32) for kk in jax.random.split(key, 3))
    g = jnp.asarray([0.8, 0.1, 0.004], jnp.float32)
    state = jax.random.normal(key, (2, 3, 8, 8), jnp.float32) if entering else None
    o, left = lightning.chunked(q, k, v, g, state, length, block=block)
    n = t if length is None else length
    want, want_left = lightning.recurrence(q[:, :n], k[:, :n], v[:, :n], g, state)
    assert rel(o[:, :n], want) < 1e-5 and rel(left, want_left) < 1e-5
    # one token a slot is the recurrence's step, and the scale is 8^-1/2
    s0 = jnp.zeros((2, 3, 8, 8)) if state is None else state
    o1, s1 = lightning.step(q[:, 0], k[:, 0], v[:, 0], g, s0, jnp.asarray([True, False]))
    assert rel(o1, want[:, 0]) < 1e-5 and bool(jnp.all(s1[1] == s0[1]))  # no sequence: the state stays
    lam = jnp.exp(-g)[:, None, None]
    assert rel(o1[0], jnp.einsum("hd,hde->he", q[0, 0], lam * s0[0] + k[0, 0][..., None] * v[0, 0][:, None]) * 8**-0.5) < 1e-5


# each assumed equation, broken in the reference: the program's logits must move
# away from it (and lie on the sound reference: ``long_forward``)
FAULTS = [
    "no_emb_scale", "depth_cut", "no_head_scale",  # the three scalings; s from the cut's depth
    "decay_next_layer",  # the decays' rule by the layer's published index
    "norm_after_rope", "no_qk_norm",  # the norm per head, before the rotation
    "sparse_rope", "no_lightning_rope",  # NoPE in the sparse layers, rope in the lightning ones
    "norm_per_head", "gate_before_norm",  # the output norm's width and its order with the gate
    "no_out_gate", "no_attn_gate",  # both gates
    "block_means", "topk_beside_forced", "first_blocks",  # maxima; the forced blocks inside topk
    "early_windows",  # a window is seen when its last row is
    "zero_state_chunks",  # a chunk enters with the state the chunk before left
]


@pytest.mark.parametrize("fault", FAULTS)
def test_an_assumed_equation_left_out_fails(model, long_forward, fault, monkeypatch):
    cfg, params = model
    seq, got, sound = long_forward
    assert rel(got, sound) < REL
    monkeypatch.setattr(reference_sala, "CHUNK", 16)
    broken = reference_sala.forward(params, jnp.asarray(seq[None]), TINY, faults=(fault,))[0]
    assert rel(got, broken) > 100 * REL, fault


def test_the_lightning_scale_shows_before_the_output_norm_alone():
    """``lightning_scale``: a constant factor on o is what the norm over all
    heads' values takes out again, so no logit shows it; the mix itself does."""
    q, k, v = (jax.random.normal(jax.random.key(i), (1, 9, 2, 8), jnp.float32) for i in range(3))
    o, _ = lightning.chunked(q, k, v, jnp.asarray([0.3, 0.01]))
    unscaled = jnp.einsum("bthd,bshd->bhts", q, k)
    assert rel(o[0, 0], jnp.einsum("hd,hd,he->he", q[0, 0], k[0, 0], v[0, 0]) * 8**-0.5) < 1e-5
    assert unscaled.shape == (1, 2, 9, 9)


def test_the_walks_choice_is_the_programs_and_the_exchange_distance_reads_it(model):
    """The reference hands back its own choice and block scores at the rows
    compared; the program's blocks there are the same (float32), a wrong choice
    lies far off in the reference's scores, and ``follow`` makes the walk read
    what it is handed."""
    cfg, params = model
    n = 60
    seq = tokens(5, n)
    ids = jnp.asarray(seq[None])
    logits, own, scores = reference_sala.forward(
        params, ids, TINY, rows=(n - 3, 3), with_choices=True)
    assert own.shape == scores.shape == (3, 2, 2, 8)
    assert (np.asarray(own).sum(-1) == 4).all() and np.asarray(own)[..., 0].all()
    cache = ring_cache.init_kv_cache(cfg, 1, RING, jnp.float32)
    rings = (cache["k"], cache["v"], ring_cache.init_pooled_cache(cfg, 1, RING, jnp.float32),
             ring_cache.init_lightning_state(cfg, 1))
    for plen in range(0, n, CHUNK):
        count = min(CHUNK, n - plen)
        part = np.zeros((1, CHUNK), np.int32)
        part[0, :count] = seq[plen : plen + count]
        _, ck, cv, _, pc, ls, last = llama.chunk_prefill_forward(
            params, jnp.asarray(part), plen, count, 0, *rings[:2], None, cfg,
            pooled_cache=rings[2], lightning_state=rings[3], total=n, return_row_choices=True, **F32)
        rings = (ck, cv, pc, ls)
    assert last.shape == (2, 2, 12) and (np.asarray(last)[..., :8] == np.asarray(own)[-1]).all()
    at = np.full((2, 2), n - 1)
    d, dist = reference_sala.exchange_distance(own[-1], np.asarray(last)[..., :8], scores[-1], at, SIZES)
    assert d.sum() == 0 and dist.sum() == 0
    wrong = np.zeros((2, 2, 8), bool)
    wrong[..., :4] = True  # the first four blocks
    d, dist = reference_sala.exchange_distance(own[-1], wrong, scores[-1], at, SIZES)
    assert (d > 0).all() and dist.max() > 0.3
    follow = np.broadcast_to(wrong, (3, 2, 2, 8))
    moved = reference_sala.forward(params, ids, TINY, rows=(n - 3, 3), follow=follow)
    assert rel(moved, logits) > 1e-4


@pytest.fixture(scope="module")
def engine(model):
    cfg, params = model
    return ServeEngine(cfg, params, num_slots=SLOTS, max_context=RING, prefill_buckets=(),
                       prefill_chunk=CHUNK, **F32)


def test_the_engine_admits_every_prompt_in_chunks_and_counts(model, engine):
    cfg, params = model
    assert engine.needs_chunks(5) and engine.cfg.q_chunk_size == CHUNK
    assert engine.block_forms["decode"] == "block-gather-xla" and engine.block_forms["chunk"] == "tiled-xla"
    assert engine.pooled_cache_resident_bytes == 2 * SLOTS * 2 * 16 * 24 * 4
    assert engine.lightning_state_resident_bytes == 2 * SLOTS * 4 * 16 * 16 * 4
    prompt = tokens(9, 53).tolist()
    tok, logits = engine.admit(1, prompt)
    want = reference_sala.forward(params, jnp.asarray([prompt]), TINY)[0]
    assert rel(logits, want[-1]) < REL and tok == int(np.argmax(want[-1]))
    assert engine.prefill_chunks == 4 and engine.prefill_chunk_tokens == 53
    assert engine.lightning_tokens == 2 * 53 and engine.dense_len_calls == 0
    # a query at t scores the windows closed before it, a KV head and sparse layer
    seen = sum(max((t - 7) // 4 + 1, 0) for t in range(53))
    assert engine.pooled_keys_scored == 2 * 2 * seen
    assert engine.blocks_chosen == 2 * 2 * sum(min(t // 8 + 1, 4) for t in range(53))
    assert 0 < engine.block_tiles_read <= engine.block_tiles_live
    toks, _ = engine.decode_step(np.asarray([0, tok, 0]), np.asarray([0, 53, 0]))
    full = reference_sala.forward(params, jnp.asarray([prompt + [tok]]), TINY, prompt_len=53)[0]
    assert toks[1] == int(np.argmax(full[-1])) and engine.lightning_tokens == 2 * 54
    stats = engine.decode_plan_stats()
    assert stats["pooled_ring_rows"] == 24 and stats["lightning_state_bytes"] == engine.lightning_state_resident_bytes


def test_an_engine_asked_for_the_kernels_runs_its_chunks_through_the_chunk_kernel(model, monkeypatch):
    """``odtp_chunk_attn`` interpreted under the engine (rings of three tiles of
    32 rows): the XLA engine's greedy tokens and its tiles, and the forms say
    which ran. By the bytes rule a stack this small keeps the XLA form."""
    from opendiloco_tpu.ops import decode_kernels

    cfg, params = model
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "16")
    monkeypatch.setattr(llama, "_SUFFIX_TILE", 32)
    make = lambda kernel: ServeEngine(
        cfg, params, num_slots=SLOTS, max_context=RING, prefill_buckets=(), prefill_chunk=CHUNK,
        decode_kernel=kernel, **F32)
    assert make("pallas").block_forms["chunk"] == "tiled-xla"  # 4 heads x 16 x 32 float32 scores
    monkeypatch.setattr(decode_kernels, "_PREFILL_SCORE_BYTES", 0)
    engines = {kernel: make(kernel) for kernel in ("xla", "pallas")}
    assert engines["xla"].block_forms["chunk"] == engines["xla"].chunk_form == "tiled-xla"
    assert engines["pallas"].block_forms["chunk"] == engines["pallas"].chunk_form == "tiles-pallas"
    prompt = tokens(9, 53).tolist()
    got = {}
    for kernel, eng in engines.items():
        tok, logits = eng.admit(1, prompt)
        toks, lens, out = np.asarray([0, tok, 0]), np.asarray([0, 53, 0]), [tok]
        for _ in range(4):
            nxt, _ = eng.decode_step(toks, lens)
            toks, lens = np.asarray([0, nxt[1], 0]), lens + np.asarray([0, 1, 0])
            out.append(int(nxt[1]))
        got[kernel] = (out, np.asarray(logits), eng.block_tiles_read, eng.block_tiles_live)
    assert got["pallas"][0] == got["xla"][0] and got["pallas"][2:] == got["xla"][2:]
    assert rel(got["pallas"][1], got["xla"][1]) < 1e-5


def test_the_batcher_serves_it_and_says_so_on_stats(model, engine):
    cfg, params = model
    batcher = ContinuousBatcher(engine).start()
    try:
        prompts = [tokens(i, n).tolist() for i, n in enumerate((40, 33, 18, 60))]
        reqs = [batcher.submit(p, max_new_tokens=5) for p in prompts]
        for r in reqs:
            assert r.wait(300) and r.error is None, r.error
        for p, r in zip(prompts, reqs):
            seq = p + r.tokens[:-1]
            want = reference_sala.forward(params, jnp.asarray([seq]), TINY, prompt_len=len(p))[0]
            assert r.tokens == [int(t) for t in np.argmax(np.asarray(want)[len(p) - 1 :], axis=-1)]
        late = batcher.submit(tokens(1, 90).tolist(), max_new_tokens=8)  # past the ring: no block wraps
        assert late.wait(60) and "exceed max_context" in late.error
        sala = batcher.stats()["sala"]
    finally:
        batcher.stop()
    assert sala["forms"]["decode"] == "block-gather-xla" and sala["dense_len_calls"] > 0
    assert sala["lightning_tokens"] > 0 and sala["block_tiles_read"] <= sala["block_tiles_live"]


def test_what_is_refused_says_so(model):
    cfg, params = model
    with pytest.raises(ValueError, match="give the engine a prefill_chunk"):
        ServeEngine(cfg, params, num_slots=2, max_context=RING, prefill_buckets=(32,), **F32)
    with pytest.raises(ValueError, match="holds more than a chunk"):
        ServeEngine(cfg, params, num_slots=2, max_context=16, prefill_buckets=(), prefill_chunk=16, **F32)
    with pytest.raises(ValueError, match="attn_impl='pallas' is refused for a configuration with lightning"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg, attn_impl="pallas")
    engine = ServeEngine(cfg, params, num_slots=2, max_context=RING, prefill_buckets=(),
                         prefill_chunk=CHUNK, **F32)
    with pytest.raises(ValueError, match="refused for a configuration with lightning"):
        engine.admit(1, list(range(3, 40)), prefix_src=0, prefix_len=16)
    with pytest.raises(ValueError, match="no routed experts"):
        LlamaConfig.from_dict({**TINY, "num_experts": 4})
    from opendiloco_tpu.models import hf_io
    from opendiloco_tpu.parallel.pipeline import pipeline_hidden

    with pytest.raises(ValueError, match="no lightning linear-attention layers"):
        hf_io.save_params(params, cfg, "/nonexistent")
    with pytest.raises(ValueError, match="pp pipeline is refused for a configuration with lightning"):
        pipeline_hidden(params, jnp.zeros((2, 8, 64)), jnp.zeros((2, 8), jnp.int32), cfg, None,
                        microbatches=2, attn_fn=None)


def test_the_training_forward_differentiates(model):
    """Training and evaluation run the XLA forms: a loss over the forward gives
    every leaf of both kinds of layer a finite gradient (the selection under
    ``stop_gradient``, the decays no leaf at all)."""
    cfg, params = model
    ids = jnp.asarray(tokens(2, 2 * 40).reshape(2, 40))

    def loss(p):
        return llama.causal_lm_loss(llama.forward(p, ids, cfg, remat=True, **F32), ids)

    value, grads = jax.value_and_grad(loss)(params)
    assert np.isfinite(float(value))
    for kind in ("attention", "lightning"):
        for name, g in grads["layers"][kind].items():
            assert bool(jnp.all(jnp.isfinite(g))) and float(jnp.abs(g).max()) > 0, (kind, name)
