"""Keye-VL-2.0's language-model block (a Qwen3-MoE block under a learned
indexer: 16 index queries a token score every row before it, the 2,048 of
largest score are what its attention reads; an index-key ring beside K and V;
a prompt admitted in chunks between decode steps) through every path of the
program, against the float32 reference written from its equations
(``benchmark/odbench/reference_keye.py``: a full forward, the selection by a
sort, nothing imported from the program). Tiny sizes, seeded random weights,
everything float32 on the CPU.

Tolerances. Program and reference both compute in float32 here and differ in
the order of accumulation (a ring read in tiles under an online softmax
against one softmax over the sequence; the k-th score found bit by bit against
a sort; grouped matmuls against every expert masked): 2e-7 relative L2 on
these sizes, and REL_L2 = 1e-4 leaves two orders of magnitude. The reference
keeps the published constants Hi^-1/2 Di^-1/2 on the index scores and the
program leaves them out: they are positive, change no order and so no set,
which ``test_the_indexer_and_the_selection_alone`` holds to the row. Anything
structural gives 1e-3 and more (the last test: an indexer without its ReLU,
the first rows in place of the largest, one chosen row in a hundred dropped,
chunks blind to the rows before them, operands below float32).
"""

import dataclasses
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opendiloco_tpu.models import llama
from opendiloco_tpu.models.llama import (
    LlamaConfig, chunk_prefill_forward, decode_forward, forward, init_params, prefill_forward,
)
from opendiloco_tpu.models.ring_cache import (
    cache_insert, index_insert, init_index_cache, init_kv_cache, rows_first,
)
from opendiloco_tpu.ops import attention
from opendiloco_tpu.serve import ContinuousBatcher, ServeEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from odbench import reference_keye as reference  # noqa: E402

REL_L2 = 1e-4
TOPK, CHUNK = 12, 8
F32 = dict(compute_dtype=jnp.float32)

# the catalog's ``config`` for Keye-VL-2.0-30B-A3B (model-configs guide), key for key
CATALOG = {
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


def published(**over) -> dict:
    """The published keys at a tiny size: 4 query heads over 2 KV heads of 16,
    8 experts of 32 (2 a token), an indexer of 4 heads of 8 that keeps 12 rows,
    chunks of 8, the rotation's 8 pairs in runs of 2, 3 and 3."""
    raw = dict(
        CATALOG, hidden_size=64, head_dim=16, num_attention_heads=4, num_key_value_heads=2,
        num_hidden_layers=3, moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
        num_local_experts=8, vocab_size=128, max_position_embeddings=512, norm_init_std=0.02,
        rope_scaling={"mrope_section": [2, 3, 3], "rope_type": "default", "type": "default"},
        sa_config={"indexer_head_dim": 8, "indexer_num_heads": 4, "indexer_num_kv_heads": 1,
                   "kv_chunk_size": CHUNK, "q_chunk_size": CHUNK, "topk": TOPK},
    )
    raw.update(over)
    return raw


def model(seed: int = 0, **over):
    raw = published(**over)
    cfg = LlamaConfig.from_dict(raw)
    return raw, cfg, init_params(jax.random.key(seed), cfg)


def tokens(seed: int, shape) -> np.ndarray:
    return np.asarray(jax.random.randint(jax.random.key(seed), shape, 3, 128), np.int32)


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def rings_for(cfg, slots, rows):
    cache = init_kv_cache(cfg, slots, rows, jnp.float32)
    return cache["k"], cache["v"], init_index_cache(cfg, slots, rows, jnp.float32)


def engine_for(cfg, params, *, num_slots=3, max_context=64, buckets=(16,), **kw):
    return ServeEngine(
        cfg, params, num_slots=num_slots, max_context=max_context, prefill_buckets=buckets,
        compute_dtype=jnp.float32, **kw,
    )


def ref_logits(params, raw, seq, **kw):
    return np.asarray(reference.forward(params, jnp.asarray([seq]), raw, **kw))[0]


# -- the configuration -----------------------------------------------------------


def test_norm_init_std_spreads_the_norms_and_nothing_else():
    """``norm_init_std``: a fresh model's norms are 1 (and the index key's
    LayerNorm bias 0), whatever its attention; the key spreads every norm's
    weight about 1 and the bias about 0, so that a test or a benchmark sees
    them act, and moves no other leaf's draw."""
    plain = init_params(jax.random.key(3), LlamaConfig.from_dict(published(norm_init_std=0.0)))
    spread = init_params(jax.random.key(3), LlamaConfig.from_dict(published(norm_init_std=0.05)))
    for name, leaf in {**spread["layers"], "final_norm": spread["final_norm"]}.items():
        was = plain["final_norm"] if name == "final_norm" else plain["layers"][name]
        if "norm" not in name:
            np.testing.assert_array_equal(leaf, was)
            continue
        about = 0.0 if name.endswith("bias") else 1.0
        assert np.all(np.asarray(was) == about), name
        assert 0.02 < float(jnp.std(leaf - about)) < 0.08 and abs(float(jnp.mean(leaf)) - about) < 0.03, name


def test_published_keys_mean_this_block():
    cfg = LlamaConfig.from_dict(CATALOG)
    assert cfg.num_params() == 30_640_656_384
    assert cfg.sparse and cfg.qk_norm_per_head and not cfg.qk_norm
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (16, 64, 2048)
    assert (cfg.q_chunk_size, cfg.mrope_section) == (512, (16, 24, 24))
    assert cfg.to_dict()["sa_config"] == CATALOG["sa_config"]  # kv_chunk_size: the raw round trip's alone
    assert cfg.norm_init_std == 0.0  # a fresh model's norms are 1; the benchmark's file asks for a spread
    assert cfg.num_local_experts is None and cfg.expert_width == 768 and cfg.norm_topk_prob
    assert cfg.router_aux_loss_coef == 0.0 and cfg.shared_width == 0
    layer = llama.shapes(cfg)["layers"]
    sizes = {name: int(np.prod(leaf.shape[1:])) for name, leaf in layer.items()}
    indexer = sum(sizes[name] for name in llama.INDEXER_LEAVES)
    attn = sum(sizes[name] for name in ("q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm"))
    assert (attn, indexer, sizes["router"]) == (18_874_368 + 256, 2_261_120, 262_144)
    assert sizes["gate_proj"] + sizes["up_proj"] + sizes["down_proj"] == 128 * 4_718_592
    # the cut the benchmark runs: 16 layers, 16 of 128 experts, an eighth of the vocabulary
    cut = LlamaConfig.from_dict(
        dict(CATALOG, num_hidden_layers=16, num_local_experts=16, vocab_size=18_992)
    )
    assert cut.held_experts == 16 and cut.num_params() == 1_628_184_576
    # and back through ``to_dict``
    again = LlamaConfig.from_dict(cut.to_dict())
    assert again == cut


@pytest.mark.parametrize("key,value,says", [
    ("sa_config", dict(CATALOG["sa_config"], indexer_num_kv_heads=2), "indexer_num_kv_heads"),
    ("attention_bias", True, "attention_bias"),
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("mlp_only_layers", [0], "mlp_only_layers"),
    ("use_sliding_window", True, "use_sliding_window"),
    ("rope_scaling", {"rope_type": "yarn", "mrope_section": [16, 24, 24]}, "rope_scaling"),
    ("rope_scaling", {"rope_type": "default", "mrope_section": [16, 24, 25]}, "mrope_section"),
    ("qk_norm", True, "no qk_norm over"),
])
def test_from_dict_refuses_what_the_block_is_not_written_for(key, value, says):
    with pytest.raises(ValueError, match=says):
        LlamaConfig.from_dict(dict(CATALOG, **{key: value}))


# -- the indexer and the selection -------------------------------------------------


def by_a_sort(scores, valid, k):
    """The selection as a stable sort has it: ties to the lower index."""
    out = np.zeros(scores.shape, bool)
    for i, (row, ok) in enumerate(zip(np.asarray(scores), np.asarray(valid))):
        order = np.argsort(-np.where(ok, row, -np.inf), kind="stable")
        out[i, order[: min(k, int(ok.sum()))]] = True
    return out & np.asarray(valid)


@pytest.mark.parametrize("case", ["random", "ties", "fewer_than_k", "exactly_k", "negative", "zeros"])
def test_the_selection_alone(case):
    rng = np.random.default_rng(3)
    scores = rng.normal(size=(6, 40)).astype(np.float32)
    valid = np.tril(np.ones((40, 40), bool))[[39, 30, 20, 12, 11, 3]]
    if case == "ties":  # few distinct values: the k-th score is shared by many rows
        scores = rng.integers(0, 3, size=(6, 40)).astype(np.float32)
    if case == "fewer_than_k":
        valid = valid[[5, 5, 4, 4, 5, 4]]
    if case == "exactly_k":  # a slot at exactly topk rows keeps them all
        valid = np.tril(np.ones((40, 40), bool))[[11] * 6]
    if case == "negative":
        scores = -np.abs(scores)
    if case == "zeros":  # what a ReLU leaves where every head is negative
        scores = np.where(rng.random((6, 40)) < 0.6, 0.0, scores).astype(np.float32)
        scores[:, ::7] = -0.0
    got = np.asarray(jax.jit(lambda s, v: attention.select_rows(s, v, 12))(scores, valid))
    want = by_a_sort(scores, valid, 12)
    assert (got == want).all()
    assert (got.sum(-1) == np.minimum(12, valid.sum(-1))).all()


def test_the_indexer_and_the_selection_alone():
    """One layer's indexer in the program and in the reference: the scores up
    to the two positive constants the program leaves out, and so the same sets
    (the reference's by ``lax.top_k``), position by position, also where t <
    topk and at exactly topk rows."""
    raw, cfg, params = model(seed=1)
    w = jax.tree.map(lambda x: x[0], params["layers"])
    t = 40
    x = jax.random.normal(jax.random.key(2), (1, t, cfg.hidden_size), jnp.float32)
    positions = jnp.arange(t)[None]
    qi, ki, wi = llama._index_qkw(cfg, x, w, *llama._index_rope(cfg, positions))
    rqi, rki, rwi = reference.index_parts(x[0], w, raw, positions[0])
    for got, want in ((qi, rqi), (ki, rki), (wi, rwi)):
        assert rel(got[0], want) < REL_L2
    scores = attention.index_scores(qi, wi, jnp.swapaxes(ki, 1, 2))[0]
    want = reference.index_scores(rqi, rki, rwi, raw)
    scale = cfg.index_n_heads ** -0.5 * cfg.index_head_dim ** -0.5
    assert rel(scores * scale, want) < REL_L2
    causal = jnp.tril(jnp.ones((t, t), bool))
    ours = attention.causal_selection(qi, wi, ki, TOPK)[0]
    theirs, gap = reference.select(want, causal, TOPK)
    assert (np.asarray(ours) == np.asarray(theirs)).all()
    assert (np.asarray(ours).sum(-1) == np.minimum(TOPK, np.arange(t) + 1)).all()
    assert np.isinf(np.asarray(gap)[:TOPK]).all() and np.isfinite(np.asarray(gap)[TOPK:]).all()
    # a sequence no longer than topk keeps every causal row and scores nothing
    short = attention.causal_selection(qi[:, :TOPK], wi[:, :TOPK], ki[:, :TOPK], TOPK)[0]
    assert (np.asarray(short) == np.asarray(causal[:TOPK, :TOPK])).all()


# -- training and evaluation -------------------------------------------------------


@pytest.mark.parametrize("t", [TOPK, TOPK + 1, 45])
def test_forward_logits_against_the_reference(t):
    raw, cfg, params = model(seed=3)
    ids = tokens(4, (2, t))
    got = forward(params, jnp.asarray(ids), cfg, remat=False, **F32)
    for b in range(2):
        assert rel(got[b], ref_logits(params, raw, ids[b])) < REL_L2


def test_sectioned_rope_with_three_differing_position_rows():
    """An image span's positions: the temporal row stands still over a 4 x 4
    patch grid while height and width count. Three equal rows are plain RoPE."""
    raw, cfg, params = model(seed=5)
    t = 30
    ids = tokens(6, (1, t))
    text = np.arange(t)
    rows = np.stack([text, text, text])
    grid = np.arange(16)
    rows[:, 6:22] = np.stack([np.full(16, 6), 6 + grid // 4, 6 + grid % 4])
    rows[:, 22:] = rows[:, 22:] - 22 + 10  # text goes on past the span's extent
    got = forward(params, jnp.asarray(ids), cfg, positions=jnp.asarray(rows)[:, None], remat=False, **F32)
    want = ref_logits(params, raw, ids[0], positions=jnp.asarray(rows))
    assert rel(got[0], want) < REL_L2
    plain = forward(params, jnp.asarray(ids), cfg, remat=False, **F32)
    assert rel(got[0], plain[0]) > 1e-2  # the rows matter
    same = forward(params, jnp.asarray(ids), cfg, remat=False, **F32,
                   positions=jnp.broadcast_to(jnp.asarray(text), (3, 1, t)))
    assert rel(same[0], plain[0]) < 1e-6
    with pytest.raises(ValueError, match="need a configuration with an mrope_section"):
        dense = LlamaConfig(vocab_size=64, hidden_size=32, num_hidden_layers=1, num_attention_heads=2)
        forward(init_params(jax.random.key(0), dense), jnp.asarray(ids) % 64, dense,
                positions=jnp.asarray(rows)[:, None])


def test_train_step_loss_and_gradient_with_the_indexer_untrained():
    """Through ``InnerTrainer.train_step`` in float32 on the CPU mesh: the loss
    against the reference's, every leaf but the indexer's with a gradient, the
    indexer's exactly zero and named by the trainer."""
    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

    raw, cfg, params = model(seed=7)
    tc = TrainerConfig(precision="fp32", remat=False, total_steps=10, warmup_steps=2)
    trainer = InnerTrainer(cfg, tc, build_mesh("NO_SHARD"))
    assert trainer.tc.attn_impl == "xla"
    assert trainer.untrained_leaves == llama.INDEXER_LEAVES
    ids = tokens(8, (8, 40))
    state = trainer.init_state(jax.random.key(0))
    state["params"] = jax.device_put(
        jax.tree.map(jnp.copy, params), jax.tree.map(lambda x: x.sharding, state["params"]))
    _, m = trainer.train_step(state, trainer.shard_batch(ids, ids.copy(), accum=1))
    ref_loss = jax.jit(lambda i: reference.loss(params, i, i, raw))
    want = np.mean([float(ref_loss(ids[b : b + 1])) for b in range(8)])
    np.testing.assert_allclose(float(m["loss"]), want, rtol=1e-5)

    def lm_loss(p):
        return llama.causal_lm_loss(forward(p, jnp.asarray(ids), cfg, remat=False, **F32), jnp.asarray(ids))

    grads = jax.grad(lm_loss)(params)
    for name, g in grads["layers"].items():
        norm = float(jnp.linalg.norm(g))
        assert (norm == 0.0) == (name in llama.INDEXER_LEAVES), (name, norm)


def test_fsdp_sees_the_new_leaves():
    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.parallel.sharding import param_specs

    _, cfg, _ = model()
    specs = param_specs(cfg, build_mesh("FULL_SHARD"))
    for name in (*llama.INDEXER_LEAVES, "q_norm", "k_norm"):
        assert name in specs["layers"]


# -- serving -----------------------------------------------------------------------


EDGES = [CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK, 2 * CHUNK + 1, 5 * CHUNK, 5 * CHUNK + 1, 37]  # one test: an engine a length


_MODEL = {}


def shared_model(seed):
    """One model a seed for the parametrised cases below: the draw and the
    whole-prompt program compile once."""
    if seed not in _MODEL:
        raw, cfg, params = model(seed=seed)
        whole = jax.jit(lambda ids, n: prefill_forward(params, ids, n, cfg, **F32))
        _MODEL[seed] = (raw, cfg, params, whole)
    return _MODEL[seed]


@pytest.mark.parametrize("n", EDGES)
def test_whole_prompt_prefill_against_chunked_prefill(n):
    """The same prompt whole in a bucket and in chunks of 8 over a slot's
    rings: the same logits, the same three rings' rows, at every chunk edge and
    one token past it; and both the reference's."""
    raw, cfg, params, whole = shared_model(9)
    prompt = tokens(10, (48,))[:n]
    ids = np.zeros((1, 48), np.int32)
    ids[0, :n] = prompt
    logits, ks, vs, iks = whole(jnp.asarray(ids), jnp.int32(n))
    padded = np.asarray(ids[0])  # the reference over the bucket, one compile: causal
    want = ref_logits(params, raw, padded)[n - 1]
    assert rel(logits[0], want) < REL_L2
    engine = engine_for(cfg, params, buckets=())
    assert engine.needs_chunks(n)
    tok, row = engine.admit(1, prompt.tolist())
    assert rel(row, want) < REL_L2 and tok == int(np.argmax(want))
    assert engine.prefill_chunks == -(-n // CHUNK) and engine.prefill_chunk_tokens == n
    for ring, rows in ((engine.cache_k, ks), (engine.cache_v, vs)):
        assert rel(rows_first(ring[:, 1])[:, :n], rows[:, :n]) < 1e-5
        assert not np.asarray(ring[:, 1, ..., n:]).any()  # a padding row is never written
        assert not np.asarray(ring[:, 0]).any() and not np.asarray(ring[:, 2]).any()
    index = engine._index[0]
    assert rel(index[:, 1, :, :n], jnp.swapaxes(iks[:, :n], 1, 2)) < 1e-5
    assert not np.asarray(index[:, 1, :, n:]).any()


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("lengths,steps,context", [
    ((TOPK - 3, TOPK + 9), 8, 64),  # across the topk-th row: from all rows to a choice
    ((37, 5), 8, 64),  # a chunked prompt beside a bucketed one
])
def test_engine_prefill_then_decode_against_the_reference(lengths, steps, context, kernel, monkeypatch):
    """``pallas``: the decode kernel under its selection operand, interpreted,
    tiles of 8 rows (some hold no chosen row, and the step's own row is not
    always among the chosen)."""
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "8")
    raw, cfg, params = model(seed=11)
    engine = engine_for(cfg, params, max_context=context, decode_kernel=kernel)
    prompts = [tokens(12 + i, (n,)).tolist() for i, n in enumerate(lengths)]
    toks, lens = np.zeros(3, np.int32), np.zeros(3, np.int32)
    seqs, got = [], []
    for slot, prompt in enumerate(prompts):
        tok, row = engine.admit(slot, prompt)
        toks[slot], lens[slot] = tok, len(prompt)
        seqs.append(prompt + [tok])
        got.append([row])
    for step in range(steps):
        nxt, logits = engine.decode_step(toks, lens)
        for slot in range(2):
            got[slot].append(np.asarray(logits[slot]))
            toks[slot], lens[slot] = nxt[slot], lens[slot] + 1
            if step < steps - 1:
                seqs[slot].append(int(nxt[slot]))
    for slot, prompt in enumerate(prompts):
        want = ref_logits(params, raw, seqs[slot])[len(prompt) - 1 :]
        assert rel(np.stack(got[slot]), want) < REL_L2


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_decode_across_the_rings_wrap(kernel, monkeypatch):
    """A slot decodes past its ring's 32 rows: the three rows written at ``lens
    % T`` replace the oldest, every row of the ring is live and the indexer
    chooses among all of them. Until the wrap the reference agrees; past it
    the full-sequence reference sees tokens the ring dropped."""
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "8")
    raw, cfg, params = model(seed=11)
    engine = engine_for(cfg, params, max_context=32, decode_kernel=kernel)
    engine.keep_row_choices()
    prompt = tokens(12, (26,)).tolist()
    tok, row = engine.admit(0, prompt)
    toks, lens = np.array([tok, 0, 0], np.int32), np.array([26, 0, 0], np.int32)
    seq, rows = prompt + [tok], [row]
    for step in range(12):  # positions 26 .. 37
        before = np.asarray(engine._index[0][:, 0])
        nxt, logits = engine.decode_step(toks, lens)
        at = int(lens[0]) % 32
        changed = np.asarray(engine._index[0][:, 0]) != before  # [L, Di, T]
        assert changed[:, :, at].any() and not np.delete(changed, at, axis=2).any()
        assert not np.asarray(engine._index[0][:, 1:]).any()  # the empty slots are written nothing
        chosen = np.asarray(engine.row_choices)[:, 0]
        assert (chosen.sum(-1) == TOPK).all()
        if lens[0] >= 32:  # wrapped: stale-looking rows behind the write are live again
            assert chosen[:, at + 1 :].any()
        else:
            assert not chosen[:, at + 1 :].any()
        rows.append(np.asarray(logits[0]))
        toks[0], lens[0] = nxt[0], lens[0] + 1
        seq.append(int(nxt[0]))
    want = ref_logits(params, raw, seq[:-1])[25:]
    assert rel(np.stack(rows[:7]), want[:7]) < REL_L2  # positions 25 .. 31
    assert rel(np.stack(rows[7:]), want[7:]) > REL_L2


def test_padding_rows_change_nothing():
    raw, cfg, params = model(seed=13)
    prompt = tokens(14, (21,))
    outs = []
    whole = jax.jit(lambda ids: prefill_forward(params, ids, jnp.int32(21), cfg, **F32))
    for bucket in (24, 48):
        ids = np.full((1, bucket), 77, np.int32)  # rubbish behind the prompt
        ids[0, :21] = prompt
        outs.append(whole(jnp.asarray(ids)))
    for logits, ks, vs, iks in outs[1:]:
        assert rel(logits, outs[0][0]) < 1e-6
        assert rel(ks[:, :21], outs[0][1][:, :21]) < 1e-6 and rel(iks[:, :21], outs[0][3][:, :21]) < 1e-6
    # and in a last chunk: the tokens behind ``count`` are not read
    rings = rings_for(cfg, 2, 32)
    res = []
    chunk = jax.jit(lambda ids: chunk_prefill_forward(params, ids, 0, 5, 1, *rings, cfg, **F32))
    for fill in (0, 77):
        ids = np.full((1, CHUNK), fill, np.int32)
        ids[0, :5] = prompt[:5]
        res.append(chunk(jnp.asarray(ids)))
    for a, b in zip(*res):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_slot_reuse_starts_clean():
    """A long tenant, then a short one in the same slot: the stale rows behind
    it, index keys among them, are never read."""
    raw, cfg, params = model(seed=15)
    engine = engine_for(cfg, params)
    engine.admit(0, tokens(16, (45,)).tolist())
    assert np.asarray(engine._index[0][:, 0, :, 44]).any()
    prompt = tokens(17, (TOPK + 2,)).tolist()
    tok, row = engine.admit(0, prompt)
    seq = prompt + [tok]
    toks, lens = np.array([tok, 0, 0], np.int32), np.array([len(prompt), 0, 0], np.int32)
    rows = [row]
    for _ in range(4):
        nxt, logits = engine.decode_step(toks, lens)
        rows.append(np.asarray(logits[0]))
        toks[0], lens[0] = nxt[0], lens[0] + 1
        seq.append(int(nxt[0]))
    assert rel(np.stack(rows), ref_logits(params, raw, seq[:-1])[len(prompt) - 1 :]) < REL_L2


def greedy(params, cfg, prompt, n):
    """``n`` greedy tokens by the training forward, one compile: the sequence
    padded to 64 (causal: what lies behind a position does not reach it)."""
    fwd = jax.jit(lambda ids: forward(params, ids, cfg, remat=False, **F32))
    seq = list(prompt)
    for _ in range(n):
        ids = np.zeros((1, 64), np.int32)
        ids[0, : len(seq)] = seq
        seq.append(int(jnp.argmax(fwd(jnp.asarray(ids))[0, len(seq) - 1])))
    return seq[len(prompt):]


def test_batcher_interleaves_chunks_with_decode_steps():
    """Two prompts that go in chunks while a third slot decodes: one chunk an
    iteration between two steps, the prefilling slots ride no step, and every
    request's tokens are what serving it alone gives."""
    _, cfg, params = model(seed=19)
    engine = engine_for(cfg, params, num_slots=3)
    steps, chunks = [], []
    step_ahead, admit_chunk = engine.step_ahead, engine.admit_chunk

    def watched(toks=None, lens=None):
        if lens is not None:
            steps.append((len(chunks), np.array(lens)))
        return step_ahead(toks, lens)

    def watched_chunk(adm):
        chunks.append((adm.slot, len(steps)))
        return admit_chunk(adm)

    engine.step_ahead, engine.admit_chunk = watched, watched_chunk
    batcher = ContinuousBatcher(engine).start()
    try:
        short = tokens(20, (6,)).tolist()
        longs = [tokens(21 + i, (n,)).tolist() for i, n in enumerate((45, 38))]
        last = tokens(25, (29,)).tolist()
        first = batcher.submit(short, max_new_tokens=50)
        while not first.tokens:  # the third slot is decoding before the long prompts arrive
            time.sleep(0.001)
        reqs = [batcher.submit(p, max_new_tokens=5) for p in longs]
        one = batcher.submit(last, max_new_tokens=1)  # ends on its first token
        for r in (first, *reqs, one):
            assert r.wait(300) and r.error is None, r.error
    finally:
        batcher.stop()
    assert batcher.loop_error is None
    assert first.tokens == greedy(params, cfg, short, 50)
    for prompt, r in zip(longs, reqs):
        assert r.tokens == greedy(params, cfg, prompt, 5)
    assert one.tokens == greedy(params, cfg, one.prompt, 1)
    assert engine.prefill_chunks == 6 + 5 + 4 and engine.prefill_chunk_tokens == 45 + 38 + 29
    # steps were enqueued while prompts were arriving, and none took a prefilling slot along
    during = [lens for done, lens in steps if 0 < done < 15]
    assert len(during) >= 10 and all(lens[0] > 0 for lens in during)
    # one chunk an iteration, the oldest prompt's first: between two chunks lies a step
    assert [slot for slot, _ in chunks] == [1] * 6 + [2] * 5 + [1] * 4
    assert all(b[1] - a[1] == 1 for a, b in zip(chunks, chunks[1:]))
    # a prefilling slot rides no step until its last chunk is enqueued
    for done, lens in steps:
        assert lens[1] == 0 if done < 6 else True
        assert lens[2] == 0 if done < 11 else True
    stats = batcher.stats()
    assert stats["dsa"]["prefill_chunks"] == 15 and stats["step_drains"] == {}
    assert stats["admissions_deferred"] >= 3  # the long prompts' first tokens stayed on the device


def test_a_chunk_under_a_selection_by_rows_keeps_the_xla_form_by_its_bytes(monkeypatch):
    """A chunk of 8 queries of 4 heads over a tile of 8 rows is 1 KB of scores:
    an engine asked for the kernels keeps the tiled XLA form (as the published
    widths do: 32 heads x 512 x 512 x 4 B = 33.5 MB under the line of 96 MB).
    With the line at 0 the selection goes through ``odtp_chunk_attn`` as its
    choice-a-row operand, interpreted, and the tokens are the XLA engine's."""
    from opendiloco_tpu.ops import decode_kernels

    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "8")
    _, cfg, params = model(seed=11)
    assert decode_kernels.chunk_form(512, 32, 4, 128, 35840, 512, "pallas") == "tiled-xla"
    assert engine_for(cfg, params, decode_kernel="pallas").chunk_form == "tiled-xla"
    monkeypatch.setattr(decode_kernels, "_PREFILL_SCORE_BYTES", 0)
    prompt = tokens(12, (37,)).tolist()  # past the bucket: in chunks, past ``index_topk`` rows
    got = {}
    for kernel in ("xla", "pallas"):
        engine = engine_for(cfg, params, decode_kernel=kernel)
        assert engine.chunk_form == ("tiles-pallas" if kernel == "pallas" else "tiled-xla")
        tok, logits = engine.admit(0, prompt)
        toks, lens, out = np.array([tok, 0, 0], np.int32), np.array([37, 0, 0], np.int32), [tok]
        for _ in range(4):
            nxt, _ = engine.decode_step(toks, lens)
            toks[0], lens[0] = nxt[0], lens[0] + 1
            out.append(int(nxt[0]))
        got[kernel] = (out, np.asarray(logits))
    assert got["pallas"][0] == got["xla"][0]
    assert rel(got["pallas"][1], got["xla"][1]) < 1e-5


def test_the_five_forwards_agree_on_this_block():
    """Training forward, whole-prompt prefill, chunked prefill, the decode step
    through the three rings and the engine's programs: one block, one set of
    logits for the same tokens."""
    _, cfg, params = model(seed=27)
    seq = tokens(28, (44,))
    n = 40
    train = forward(params, jnp.asarray(seq[None]), cfg, remat=False, **F32)[0]
    ids = np.zeros((1, 48), np.int32)
    ids[0, :n] = seq[:n]
    whole, ks, vs, iks = prefill_forward(params, jnp.asarray(ids), jnp.int32(n), cfg, **F32)
    assert rel(whole[0], train[n - 1]) < REL_L2
    ck, cv, ci = rings_for(cfg, 2, 64)
    rings = (*cache_insert(ck, cv, ks, vs, 0), index_insert(ci, iks, 0))
    chunk = jax.jit(lambda ids, c, *r: chunk_prefill_forward(params, ids, c, CHUNK, 1, *r, cfg, **F32))
    for c in range(0, n, CHUNK):  # slot 1 in chunks
        logits, *rings = chunk(jnp.asarray(seq[None, c : c + CHUNK]), c, *rings)
        assert rel(logits[0], train[c + CHUNK - 1]) < REL_L2
    ck, cv, ci = rings
    step = jax.jit(lambda tok, lens, k, v, i: decode_forward(
        params, tok, lens, k, v, cfg, index_cache=i, **F32))
    for pos in range(n, 44):
        tok = jnp.full((2,), seq[pos], jnp.int32)
        logits, ck, cv, ci = step(tok, jnp.full((2,), pos, jnp.int32), ck, cv, ci)
        assert rel(logits[0], train[pos]) < REL_L2 and rel(logits[1], train[pos]) < REL_L2


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight shares of one expert each (of 8; the benchmark's are 16 of 128)
    give the uncut layer: in the program, and against the reference's whole
    layer and its shares."""
    raw, cfg, params = model(seed=29)
    w = jax.tree.map(lambda x: x[1], params["layers"])
    m = jax.random.normal(jax.random.key(30), (1, 19, cfg.hidden_size), jnp.float32)
    whole, _, counts = llama._ffn(cfg, m, w)
    want, chose = reference.routed_ffn(m[0], w, raw)
    assert rel(whole[0], want) < REL_L2
    # the readings tool's witness: a walk handed experts takes them (its own back: nothing moves)
    assert chose.shape == (19, 2) and rel(reference.routed_ffn(m[0], w, raw, experts=chose)[0], want) < 1e-6
    assert rel(reference.routed_ffn(m[0], w, raw, experts=(chose + 1) % 8)[0], want) > 0.1
    parts, pairs = [], 0
    for first in range(8):
        share = dataclasses.replace(cfg, num_local_experts=1, first_local_expert=first)
        held = {name: (leaf[first : first + 1] if name in llama.EXPERT_LEAVES else leaf)
                for name, leaf in w.items()}
        out, _, c = llama._routed_ffn(share, m, held, None)
        ref_share, _ = reference.routed_ffn(m[0], held, dict(raw, first_local_expert=first))
        assert rel(out[0], ref_share) < REL_L2
        parts.append(out)
        pairs += int(c[0])
        assert int(c[3]) == 19 * 2
    assert pairs == int(counts[0]) == 19 * 2
    assert rel(sum(parts)[0], want) < REL_L2


def test_what_is_refused_says_so(tmp_path):
    from opendiloco_tpu.models import hf_io
    from opendiloco_tpu.parallel.pipeline import pipeline_hidden
    from opendiloco_tpu.serve.kvcache import HostKVTier

    refused = "refused for a configuration with learned sparse attention"
    _, cfg, params = model(seed=31)
    engine = engine_for(cfg, params)
    with pytest.raises(ValueError, match=f"prefix_cache is {refused}"):
        ContinuousBatcher(engine, prefix_cache=True)
    with pytest.raises(ValueError, match=f"kv_tier is {refused}"):
        ContinuousBatcher(engine, kv_tier=HostKVTier(host_slots=2))
    engine.admit(0, tokens(32, (12,)).tolist())
    with pytest.raises(ValueError, match=f"prefix reuse.*{refused}"):
        engine.admit(1, tokens(32, (16,)).tolist(), prefix_src=0, prefix_len=8)
    with pytest.raises(ValueError, match=f"page-out is {refused}"):
        engine.fetch_slot_pages(0, 12)
    with pytest.raises(ValueError, match=f"page-in is {refused}"):
        engine.install_slot_pages(0, np.zeros((3, 16, 2, 16)), np.zeros((3, 16, 2, 16)))
    with pytest.raises(ValueError, match="index ring goes with learned sparse attention"):
        llama.chunk_prefill_forward(params, jnp.zeros((1, 2), jnp.int32), 0, 2, 0,
                                    engine.cache_k, engine.cache_v, None, cfg)
    with pytest.raises(ValueError, match="pp pipeline is refused for a configuration with learned sparse"):
        pipeline_hidden(params, jnp.zeros((2, 8, 64)), None, cfg, None, microbatches=2, attn_fn=None)
    with pytest.raises(ValueError, match="no indexer"):
        hf_io.save_params(params, cfg, str(tmp_path))
    for impl in ("pallas", "ring"):
        with pytest.raises(ValueError, match=f"attn_impl='{impl}'.*{refused}"):
            forward(params, jnp.asarray(tokens(33, (1, 8))), cfg, attn_impl=impl)
    spans = np.stack([np.arange(20)] * 3)
    for admit in (engine.admit_enqueue, engine.admit_begin):
        with pytest.raises(ValueError, match="positions are refused by the serving engine.*image span"):
            admit(1, tokens(34, (20,)).tolist(), positions=spans)
    with pytest.raises(ValueError, match="not whole chunks of q_chunk_size"):
        engine_for(cfg, params, max_context=60)
    with pytest.raises(ValueError, match="keep_row_choices needs learned sparse attention"):
        dense = LlamaConfig(vocab_size=64, hidden_size=32, num_hidden_layers=1, num_attention_heads=2)
        ServeEngine(dense, init_params(jax.random.key(0), dense)).keep_row_choices()
    # what works unchanged is not refused: the plan's numbers, a weight swap
    assert "decode_plan_heads" in engine.decode_plan_stats()
    engine.install_params(1, params)
    assert engine.weight_binds == 2


def test_counters_and_spans_carry_what_the_indexer_did():
    from opendiloco_tpu import obs

    _, cfg, params = model(seed=35)
    L = cfg.num_hidden_layers
    engine = engine_for(cfg, params)
    obs.capture.start()
    try:
        engine.keep_row_choices()
        tok, _ = engine.admit(0, tokens(36, (21,)).tolist())  # three chunks: 8, 8, 5
        scored = sum(range(1, 22))
        assert engine.dsa_rows_scored == L * scored
        assert engine.dsa_rows_selected == L * sum(min(TOPK, t) for t in range(1, 22))
        assert engine.row_choices.shape == (L, 64) and int(engine.row_choices.sum()) == L * TOPK
        toks, lens = np.array([tok, 0, 0], np.int32), np.array([21, 0, 0], np.int32)
        engine.decode_step(toks, lens)
        assert engine.dsa_rows_scored == L * (scored + 22)
        assert engine.row_choices.shape == (L, 3, 64)
        assert (np.asarray(engine.row_choices.sum(-1))[:, 0] == TOPK).all()
        row = 2 * cfg.kv_heads * cfg.head_dim * 4
        assert engine.dsa_kv_bytes_read == L * (8 + 16 + 21 + 22) * row
        assert engine.dsa_index_bytes_read == L * (8 + 16 + 21 + 22) * cfg.index_head_dim * 4
        assert engine.index_cache_resident_bytes == L * 3 * cfg.index_head_dim * 64 * 4
        assert (engine.prefill_chunks, engine.prefill_chunk_tokens) == (3, 21)
    finally:
        cap = obs.capture.stop()
    if True:
        spans = [s for s in cap.spans if s["name"] == "serve_prefill"]
        assert [s["args"]["chunk"] for s in spans] == [0, 1, 2]
        assert [s["args"]["rows_before"] for s in spans] == [0, 8, 16]
        assert [s["args"]["tokens"] for s in spans] == [8, 8, 5]
        assert sum(s["args"]["dsa_rows_scored"] for s in spans) == L * scored
        assert all("moe_pairs" in s["args"] for s in spans)
        (step,) = [s for s in cap.spans if s["name"] == "serve_decode"]
        assert step["args"]["dsa_rows_scored"] == L * 22 and step["args"]["dsa_rows_selected"] == L * TOPK
    # zero and silent for every other configuration
    dense = LlamaConfig(vocab_size=64, hidden_size=32, num_hidden_layers=1, num_attention_heads=2)
    other = ServeEngine(dense, init_params(jax.random.key(0), dense))
    other.admit(0, [3, 4, 5])
    assert other.dsa_rows_scored == other.index_cache_resident_bytes == other.prefill_chunks == 0
    assert not other.needs_chunks(10_000) and not other.prompt_fits(10_000)


# -- what the tolerance catches ------------------------------------------------------


@pytest.mark.parametrize("fault", [
    "no_relu", "first_rows", "drop_rows", "chunk_blind", "bfloat16", "float8_e4m3fn",
])
def test_the_tolerance_catches_what_it_must(fault):
    """The reference with one equation broken, or its operands in a lower
    precision, against the sound reference: far outside the limit the program
    is held to."""
    raw, cfg, params = model(seed=37)
    seq = tokens(38, (45,))
    sound = ref_logits(params, raw, seq)
    if fault in ("bfloat16", "float8_e4m3fn"):
        broken = ref_logits(params, raw, seq, operands=getattr(jnp, fault))
    else:
        broken = ref_logits(params, raw, seq, faults=(fault,))
    assert rel(broken, sound) > 10 * REL_L2
    got = forward(params, jnp.asarray(seq[None]), cfg, remat=False, **F32)[0]
    assert rel(got, sound) < REL_L2 < rel(got, broken)


def test_the_reference_follows_the_engines_rows():
    """The check's form: the reference along the rows the engine chose for the
    positions compared, its own sets and the exchanged rows' distance beside."""
    raw, cfg, params = model(seed=39)
    engine = engine_for(cfg, params)
    engine.keep_row_choices()
    prompt = tokens(40, (30,)).tolist()
    tok, row = engine.admit(0, prompt)
    sets = [np.asarray(engine.row_choices)]  # [L, T]: the prompt's last token's
    toks, lens = np.array([tok, 0, 0], np.int32), np.array([30, 0, 0], np.int32)
    seq, rows = prompt + [tok], [row]
    for _ in range(3):
        nxt, logits = engine.decode_step(toks, lens)
        sets.append(np.asarray(engine.row_choices)[:, 0])
        rows.append(np.asarray(logits[0]))
        toks[0], lens[0] = nxt[0], lens[0] + 1
        seq.append(int(nxt[0]))
    follow = np.full((4, cfg.num_hidden_layers, TOPK), -1, np.int32)
    for r, chosen in enumerate(sets):
        for layer in range(cfg.num_hidden_layers):
            idx = np.nonzero(chosen[layer])[0]
            follow[r, layer, : idx.size] = idx
    logits, own, differing, distance, gap, worst = reference.forward(
        params, jnp.asarray([seq[:-1]]), raw, follow=follow, rows=(29, 4), with_choices=True)
    assert rel(np.stack(rows), logits[0]) < REL_L2
    assert own.shape == (4, cfg.num_hidden_layers, 33) and not np.asarray(differing).any()
    assert (np.asarray(own)[:, :, :33] == np.stack(sets)[:, :, :33]).all()
    # a wrong set is seen: swap a chosen row for one that was not
    wrong = follow.copy()
    left_out = int(np.nonzero(~sets[0][0][:30])[0][0])
    wrong[0, 0, 0] = left_out
    _, _, differing, distance, _, worst = reference.forward(
        params, jnp.asarray([seq[:-1]]), raw, follow=wrong, rows=(29, 4), with_choices=True)
    assert int(differing[0, 0]) == 1 and float(distance[0, 0]) > 0
    assert int(worst[0, 0, 1]) == left_out and sets[0][0][int(worst[0, 0, 0])]
