"""EvaByte's block (EVA attention: a query reads the rows of its own window
exactly and, under the same softmax, one pooled key and value per chunk of
every earlier window, so a slot's past is two rings of two lifetimes and the
pooling under way; RMSNorm under 1 + w; a float32 residual stream; a head of
several vocabularies of which the first samples) through every path of the
program, against the float32 reference written from its equations
(``benchmark/odbench/reference_evabyte.py``: the whole sequence's scores under
the mask, pooled rows by a loop over the chunks, nothing imported from the
program). Tiny sizes, seeded random weights, everything float32 on the CPU.

Tolerances. Program and reference both compute in float32 here and differ in
the order of accumulation only (windows cut out of the sequence against the
whole sequence under a mask; an online pooling against a chunk's softmax; two
softmaxes merged against one): 3e-7 relative L2 on these sizes, and 1e-4
leaves more than two orders of magnitude. Anything structural gives 3e-3 and
more: pooled rows read from their chunk's end on (the reference's
``visible="chunk"``) 7e-2, a ring that slides over the window before, a pooled
row left stale (7e-3: one chunk of eight), operands below float32 (the last
tests show it).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opendiloco_tpu.models.llama import (
    LlamaConfig, causal_lm_loss, chunk_prefill_forward, decode_forward, forward, init_params,
    prefill_forward,
)
from opendiloco_tpu.models.ring_cache import eva_insert, eva_pooled_rows, init_eva_state, init_kv_cache
from opendiloco_tpu.ops import attention, decode_kernels
from opendiloco_tpu.serve import ContinuousBatcher, ServeEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from odbench import reference_evabyte as reference  # noqa: E402

REL_L2 = 1e-4
WINDOW, CHUNK = 16, 4
F32 = dict(compute_dtype=jnp.float32)


def published(**over) -> dict:
    """The published ``config.json``'s keys at a tiny size: windows of 16,
    chunks of 4, two prediction heads over 64 tokens."""
    raw = {
        "model_type": "evabyte", "attention_class": "eva", "chunk_size": CHUNK,
        "window_size": WINDOW, "hidden_size": 32, "intermediate_size": 48,
        "num_attention_heads": 4, "num_key_value_heads": 4, "num_hidden_layers": 2,
        "vocab_size": 64, "num_pred_heads": 2, "norm_add_unit_offset": True,
        "fp32_skip_add": True, "fp32_logits": True, "fp32_ln": False, "mixedp_attn": True,
        "rope_theta": 100000, "rope_scaling": None, "init_std": 0.08, "rms_norm_eps": 1e-5,
        "max_position_embeddings": 256, "tie_word_embeddings": False,
        "attention_bias": False, "hidden_act": "silu",
    }
    raw.update(over)
    return raw


def model(seed: int = 0, **over):
    raw = published(**over)
    cfg = LlamaConfig.from_dict(raw)
    params = init_params(jax.random.key(seed), cfg)
    # the pooling's two vectors as large as a key, so that the pooled rows
    # weigh in the softmax and their offset matters
    stack = params["layers"]
    stack["adaptive_phi"] = stack["adaptive_phi"] * 6.0
    stack["adaptive_mu_k"] = stack["adaptive_mu_k"] * 6.0
    return raw, cfg, params


def rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_from_dict_of_the_catalog_row_counts_the_published_model():
    """The catalog row's ``config`` (model-configs guide, EvaByte), key for
    key: 32 layers of 202,391,552, the embedding, eight heads' vocabularies,
    the final norm."""
    cfg = LlamaConfig.from_dict({
        "attention_bias": False, "attention_class": "eva", "chunk_size": 16, "fp32_ln": False,
        "fp32_logits": True, "fp32_skip_add": True, "hidden_act": "silu", "hidden_size": 4096,
        "init_cutoff_factor": None, "init_fn": "v2", "init_std": 0.01275,
        "intermediate_size": 11008, "lazy_init": True, "max_position_embeddings": 32768,
        "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
        "norm_add_unit_offset": True, "num_attention_heads": 32, "num_chunks": None,
        "num_hidden_layers": 32, "num_key_value_heads": 32, "num_pred_heads": 8,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000,
        "tie_word_embeddings": False, "vocab_size": 320, "window_size": 2048,
    })
    assert cfg.eva and (cfg.window_size, cfg.chunk_size, cfg.eva_chunks_per_window) == (2048, 16, 128)
    assert cfg.initializer_range == 0.01275 and cfg.rope_theta == 100000
    assert cfg.num_params() == 6_488_330_240
    assert LlamaConfig.from_dict(cfg.to_dict()) == cfg


@pytest.mark.parametrize("key,value", [
    ("attention_class", "mha2"), ("attention_bias", True), ("rope_scaling", {"factor": 2.0}),
    ("hidden_act", "gelu"), ("window_size", 18), ("chunk_size", 0),
    ("tie_word_embeddings", True), ("qk_norm", True),
])
def test_from_dict_refuses_what_the_block_is_not_written_for(key, value):
    with pytest.raises(ValueError):
        LlamaConfig.from_dict(published(**{key: value}))


@pytest.mark.parametrize("length", [None, 13, 16, 17, 3])
def test_pooling_against_a_loop(length):
    """``eva_pool`` over chunks of 4 against one chunk at a time by hand: the
    softmax of phi . k over the chunk's live positions, k under it plus mu, v
    under it; the stats are the same sums unnormalised. A bucket's padding
    (positions from ``length`` on) enters no chunk."""
    rng = np.random.default_rng(0)
    t, heads, d = 18, 3, 8  # the last chunk is short: two positions
    k, v = rng.normal(size=(2, 1, t, heads, d)).astype(np.float32)
    phi, mu = rng.normal(size=(2, heads, d)).astype(np.float32)
    kbar, vbar, stats = attention.eva_pool(
        jnp.asarray(k), jnp.asarray(v), jnp.asarray(phi), jnp.asarray(mu), CHUNK,
        None if length is None else jnp.int32(length),
    )
    assert kbar.shape == (1, 5, heads, d) and stats.shape == (1, 5, heads, 2 * d + 2)
    live = t if length is None else length
    for j in range(5):
        rows = [m for m in range(CHUNK * j, min(CHUNK * (j + 1), live))]
        for h in range(heads):
            if not rows:  # nothing to pool: the offset alone, no value
                np.testing.assert_allclose(kbar[0, j, h], mu[h], rtol=1e-6)
                np.testing.assert_allclose(vbar[0, j, h], 0.0, atol=1e-7)
                assert float(stats[0, j, h, -1]) == 0.0
                continue
            s = np.array([phi[h] @ k[0, m, h] for m in rows], np.float64)
            a = np.exp(s - s.max()) / np.exp(s - s.max()).sum()
            np.testing.assert_allclose(
                kbar[0, j, h], sum(a[i] * k[0, m, h] for i, m in enumerate(rows)) + mu[h],
                rtol=2e-5, atol=2e-6)
            np.testing.assert_allclose(
                vbar[0, j, h], sum(a[i] * v[0, m, h] for i, m in enumerate(rows)),
                rtol=2e-5, atol=2e-6)
            np.testing.assert_allclose(float(stats[0, j, h, -2]), s.max(), rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(float(stats[0, j, h, -1]), np.exp(s - s.max()).sum(), rtol=1e-5)


def test_the_pooling_step_by_step_equals_the_pooling_at_once():
    """``eva_accumulate`` over 11 positions, two slots a chunk apart, against
    ``eva_pool`` of what each has seen: a position that starts a chunk drops
    whatever the stats held."""
    rng = np.random.default_rng(1)
    heads, d, t = 2, 8, 11
    k, v = rng.normal(size=(2, 2, t + CHUNK, heads, d)).astype(np.float32)
    phi, mu = rng.normal(size=(2, heads, d)).astype(np.float32)
    stats = jnp.full((2, heads, 2 * d + 2), 7.0)  # a former tenant's
    starts = np.array([0, CHUNK])
    for step in range(t):
        lens = jnp.asarray(starts + step, jnp.int32)
        rows = lambda x: jnp.asarray(np.stack([x[s, starts[s] + step] for s in range(2)]))
        kbar, vbar, stats = attention.eva_accumulate(stats, rows(k), rows(v), phi, mu, lens, CHUNK)
        for s in range(2):
            p = int(lens[s])
            want_k, want_v, want = attention.eva_pool(
                jnp.asarray(k[s : s + 1, : p + 1]), jnp.asarray(v[s : s + 1, : p + 1]), phi, mu, CHUNK)
            np.testing.assert_allclose(kbar[s], want_k[0, p // CHUNK], rtol=2e-5, atol=2e-6)
            np.testing.assert_allclose(vbar[s], want_v[0, p // CHUNK], rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("t", [WINDOW - 1, WINDOW, 2 * WINDOW + 1, 3 * WINDOW + 7])
def test_forward_against_the_reference(t):
    """The training forward over 1, 2 and 4 windows, all heads' logits; the
    reference with pooled rows readable from their chunk's end fails the
    tolerance wherever a chunk has ended."""
    raw, cfg, params = model()
    ids = jax.random.randint(jax.random.key(3), (2, t), 0, cfg.vocab_size)
    got = forward(params, ids, cfg, remat=False, **F32)
    assert got.shape == (2, t, cfg.num_pred_heads * cfg.vocab_size) and got.dtype == jnp.float32
    want = reference.forward(params, ids, raw)
    assert rel(got, want) < REL_L2
    assert rel(reference.forward(params, ids, raw, visible="chunk"), want) > 100 * REL_L2


def test_loss_and_gradients_against_the_reference():
    """``causal_lm_loss`` over both heads (head i held to the token i + 1
    ahead) and ``jax.grad`` of it through ``forward``, against ``jax.grad`` of
    the reference's loss, leaf by leaf: the pooling's two vectors and the
    norms under 1 + w among them."""
    raw, cfg, params = model(1)
    ids = jax.random.randint(jax.random.key(4), (2, 2 * WINDOW + 5), 0, cfg.vocab_size)

    def loss(p):
        logits = forward(p, ids, cfg, remat=False, **F32)
        return causal_lm_loss(logits, ids, pred_heads=cfg.num_pred_heads)

    got, grads = jax.value_and_grad(loss)(params)
    want, ref_grads = jax.value_and_grad(lambda p: reference.loss(p, ids, ids, raw))(params)
    assert abs(float(got) - float(want)) < 1e-5 * float(want)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), r in zip(flat, jax.tree.leaves(ref_grads)):
        assert float(jnp.linalg.norm(r)) > 0, path
        assert rel(g, r) < REL_L2, (jax.tree_util.keystr(path), rel(g, r))
    # and under full rematerialization the same loss
    remat = causal_lm_loss(forward(params, ids, cfg, remat=True, **F32), ids, pred_heads=2)
    assert abs(float(remat) - float(got)) < 1e-6


def _engine(cfg, params, kernel, slots=3, max_context=5 * WINDOW, buckets=(2 * WINDOW, 3 * WINDOW)):
    return ServeEngine(
        cfg, params, num_slots=slots, max_context=max_context, prefill_buckets=buckets,
        compute_dtype=jnp.float32, decode_kernel=kernel,
    )


def _decode_through(engine, prompts, steps):
    """Admit ``prompts`` into slots 0.., decode ``steps`` greedy steps -> (the
    sequences fed, the logits rows of each prompt's last position and of each
    step)."""
    tokens = np.zeros(engine.num_slots, np.int32)
    lens = np.zeros(engine.num_slots, np.int32)
    seqs, got = [], []
    for slot, prompt in enumerate(prompts):
        tok, logits = engine.admit(slot, prompt)
        tokens[slot], lens[slot] = tok, len(prompt)
        seqs.append(list(prompt) + [tok])
        got.append([np.asarray(logits)])
    for step in range(steps):
        nxt, logits = engine.decode_step(tokens.copy(), lens.copy())
        logits = np.asarray(logits)
        for slot in range(len(prompts)):
            got[slot].append(logits[slot])
            tokens[slot] = nxt[slot]
            lens[slot] += 1
            if step < steps - 1:
                seqs[slot].append(int(nxt[slot]))
    return seqs, [np.stack(rows) for rows in got]


# prompts that end before, on and after a window's edge and a chunk's edge:
# n % 4 in {0, 1, 3}, n % 16 in {0, 1, 15}
EDGES = [15, 16, 17, 31, 32, 33, 21, 44]


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize("lengths", [EDGES[0:3], EDGES[3:6], EDGES[6:8]])
def test_prefill_and_decode_through_both_rings_against_the_reference(kernel, lengths, monkeypatch):
    """The engine's own prefill (padded into a bucket), insert and decode
    steps, slots at different positions in one step, decoding across two
    restarts of the ring (36 steps over windows of 16), every head's logits
    against the reference's full forward on the tokens fed. Under ``pallas``
    the decode kernel runs (interpreted) over both rings, tiles of 4 rows."""
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "4")
    raw, cfg, params = model(2)
    engine = _engine(cfg, params, kernel, max_context=6 * WINDOW)
    calls = []
    if kernel == "pallas":
        paged = decode_kernels.paged_decode_attention
        monkeypatch.setattr(
            decode_kernels, "paged_decode_attention",
            lambda *a, **kw: calls.append(kw["eva_ring"]) or paged(*a, **kw))
    rng = np.random.default_rng(lengths[0])
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist() for n in lengths]
    steps = 36
    seqs, got = _decode_through(engine, prompts, steps)
    for prompt, seq, rows in zip(prompts, seqs, got):
        want = np.asarray(reference.forward(params, jnp.asarray([seq]), raw))[0, len(prompt) - 1 :]
        assert rows.shape == want.shape == (steps + 1, 2 * cfg.vocab_size)
        assert rel(rows, want) < REL_L2, (len(prompt), rel(rows, want))
        early = np.asarray(reference.forward(params, jnp.asarray([seq]), raw, visible="chunk"))
        assert rel(rows, early[0, len(prompt) - 1 :]) > 100 * REL_L2
    if kernel == "pallas":  # the kernel ran over both rings, not its XLA stand-in
        assert 0 in calls and cfg.eva_chunks_per_window in calls
        monkeypatch.delenv("ODTP_DECODE_BLOCK_T")  # no tile, no plan: refused, by name
        with pytest.raises(ValueError, match="'pallas' has no plan for EVA's rings"):
            _engine(cfg, params, kernel)
    assert engine.eva_forms == {"decode": kernel, "prefill": "xla"}
    # what the steps read, by the positions they were at
    local = pooled = restarts = chunks = 0
    for n in lengths:
        for p in range(n, n + steps):
            local += p % WINDOW + 1
            pooled += p // WINDOW * (WINDOW // CHUNK)
            restarts += p % WINDOW == 0
            chunks += p % CHUNK == CHUNK - 1
    L = cfg.num_hidden_layers
    assert engine.eva_local_rows_read == L * local and engine.eva_pooled_rows_read == L * pooled
    assert engine.eva_window_restarts == restarts
    assert engine.eva_chunks_pooled == chunks + sum(n // CHUNK for n in lengths)
    assert engine.eva_cache_resident_bytes == sum(x.nbytes for x in engine._eva) > 0
    assert engine.cache_k.shape[-1] == WINDOW
    assert engine._eva[0].shape[-1] == eva_pooled_rows(cfg, 6 * WINDOW) == 6 * WINDOW // CHUNK


def test_a_padded_bucket_equals_the_unpadded_prompt():
    """A prompt of 21 in buckets of 24, 32 and 48: the logits, the rows the
    slot receives (its last window's, at ring rows [0, 5)), the pooled rows of
    the chunks that ended and the pooling under way are one and the same."""
    _, cfg, params = model(3)
    n = 21
    prompt = np.random.default_rng(2).integers(1, cfg.vocab_size, n)
    seen = []
    for bucket in (24, 32, 48):
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :n] = prompt
        logits, ks, vs, pks, pvs, stats = prefill_forward(
            params, jnp.asarray(ids), jnp.int32(n), cfg, **F32)
        cache = init_kv_cache(cfg, 2, 64, jnp.float32)
        eva = init_eva_state(cfg, 2, 64, jnp.float32)
        ck, cv, pk, pv, st = eva_insert(
            cache["k"], cache["v"], eva["pool_k"], eva["pool_v"], eva["stats"],
            ks, vs, pks, pvs, stats, jnp.int32(1))
        assert ks.shape == (cfg.num_hidden_layers, WINDOW, cfg.kv_heads, cfg.head_dim)
        seen.append((
            logits, ck[:, 1, :, :, : n % WINDOW], cv[:, 1, :, :, : n % WINDOW],
            pk[:, 1, :, :, : n // CHUNK], pv[:, 1, :, :, : n // CHUNK], st[:, 1],
        ))
        assert not np.asarray(ck[:, 0]).any() and not np.asarray(pk[:, 0]).any()
    for other in seen[1:]:
        for a, b in zip(seen[0], other):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    # the rows are the last window's: position 16 at ring row 0
    whole = forward(params, jnp.asarray(prompt[None]), cfg, remat=False, **F32)
    np.testing.assert_allclose(seen[0][0][0], whole[0, -1], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.moveaxis(np.asarray(seen[0][1]), -1, 1), np.asarray(ks[:, : n - WINDOW]), rtol=1e-6)


def test_the_decode_kernel_pair_equals_the_xla_form(monkeypatch):
    """``eva_decode_attention`` (the decode kernel over the window's ring and
    over the pooled ring, merged under their softmaxes' maxima and sums)
    against ``eva_decode_step_attention`` on random rings: outputs to
    rounding, both rings and the stats bit for bit, slots in their first
    window (no pooled row to read), on a window's edge and deep in a third."""
    rng = np.random.default_rng(5)
    L, S, H, D, window, chunk = 2, 5, 4, 8, 16, 4
    shape = lambda rows: (L, S, H, D, rows)
    ck, cv = (jnp.asarray(rng.normal(size=shape(window)), jnp.float32) for _ in range(2))
    pk, pv = (jnp.asarray(rng.normal(size=shape(16)), jnp.float32) for _ in range(2))
    stats = jnp.asarray(np.abs(rng.normal(size=(L, S, H, 2 * D + 2))), jnp.float32)
    q, k, v = (jnp.asarray(rng.normal(size=(S, H, D)), jnp.float32) for _ in range(3))
    phi, mu = (jnp.asarray(rng.normal(size=(H, D)), jnp.float32) for _ in range(2))
    lens = jnp.asarray([0, 7, 16, 47, 63], jnp.int32)
    args = (q, k, v, phi, mu, ck, cv, pk, pv, stats, lens, jnp.int32(1))
    want = attention.eva_decode_step_attention(*args, window=window, chunk=chunk)
    # interpreted, a tile comes from ODTP_DECODE_BLOCK_T alone: without it the
    # kernel has no plan for these rings, and says so instead of standing in
    with pytest.raises(ValueError, match="no plan for EVA's rings"):
        decode_kernels.eva_decode_attention(*args, window=window, chunk=chunk, interpret=True)
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "4")
    got = decode_kernels.eva_decode_attention(*args, window=window, chunk=chunk, interpret=True)
    np.testing.assert_allclose(got[0], want[0], rtol=2e-5, atol=2e-6)
    for a, b in zip(got[1:], want[1:]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("t", [128, 300, 384])
def test_the_flash_prefill_form_equals_the_xla_form(t):
    """``eva_prefill_attention`` (each window's own rows through the flash
    kernel, interpreted; the pooled rows before it scored in XLA; merged under
    one softmax) against ``eva_attention`` over one, three (the last short)
    and three whole windows of 128; a window no tile divides keeps the XLA
    form itself."""
    rng = np.random.default_rng(t)
    h, d, window, chunk = 2, 8, 128, 16
    q, k, v = (jnp.asarray(rng.normal(size=(1, t, h, d)), jnp.float32) for _ in range(3))
    phi, mu = (jnp.asarray(rng.normal(size=(h, d)), jnp.float32) for _ in range(2))
    kbar, vbar, _ = attention.eva_pool(k, v, phi, mu, chunk)
    want = attention.eva_attention(q, k, v, kbar, vbar, window=window, chunk=chunk)
    got = decode_kernels.eva_prefill_attention(
        q, k, v, kbar, vbar, window=window, chunk=chunk, interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    untiled = decode_kernels.eva_prefill_attention(
        q[:, :40], k[:, :40], v[:, :40], kbar, vbar, window=16, chunk=4, interpret=True)
    np.testing.assert_array_equal(
        untiled, attention.eva_attention(q[:, :40], k[:, :40], v[:, :40], kbar, vbar, window=16, chunk=4))
    # the form is a function of the platform and the tiling, and has a name
    form = decode_kernels.eva_prefill_form
    assert form(window, d, interpret=True) == "flash" and form(16, d, interpret=True) == "xla"
    assert form(window, d) == "xla"  # off the chip nobody interprets a kernel unasked
    assert form(2048, 128, interpret=False) == "flash"  # compiled for the chip: the cell's shapes


@pytest.mark.parametrize("what", ["slides", "stale_pooled_row", "bfloat16"])
def test_faults_the_tolerance_catches(what):
    """What the tolerance must see, each at 3e-3 and more: a ring that slides
    over the window before instead of restarting, a pooled row left as the
    prefill wrote it (its chunk had not ended), bfloat16 in place of float32."""
    raw, cfg, params = model(4)
    n, steps = 30, 20  # the prompt ends mid-chunk, two positions before the edge
    prompt = np.random.default_rng(8).integers(1, cfg.vocab_size, n).tolist()
    dtype = jnp.bfloat16 if what == "bfloat16" else jnp.float32
    engine = ServeEngine(cfg, params, num_slots=2, max_context=4 * WINDOW,
                         prefill_buckets=(2 * WINDOW,), compute_dtype=dtype, decode_kernel="xla")
    if what == "slides":
        sound = attention.eva_decode_step_attention

        def slides(q, k, v, phi, mu, ck, cv, pk, pv, stats, lens, layer, *, window, chunk):
            # rows [0, window) all live once the ring has filled: the window before's
            out = sound(q, k, v, phi, mu, ck, cv, pk, pv, stats, lens, layer, window=window, chunk=chunk)
            at = jnp.mod(lens, window)
            full = attention.decode_attention(q, out[1][layer], out[2][layer], jnp.where(lens >= window, window, at))
            return (full, *out[1:])

        import opendiloco_tpu.models.llama as llama
        pytest.MonkeyPatch().setattr(llama, "eva_decode_step_attention", slides)
        engine = ServeEngine(cfg, params, num_slots=2, max_context=4 * WINDOW,
                             prefill_buckets=(2 * WINDOW,), compute_dtype=dtype, decode_kernel="xla")
    try:
        if what == "stale_pooled_row":
            decode_step = engine.decode_step

            def stale(tokens, lens):  # the pooled ring never takes a step's row
                keep = tuple(jnp.copy(x) for x in engine._eva[:2])  # the step donates its own
                out = decode_step(tokens, lens)
                engine._eva = (*keep, engine._eva[2])
                return out

            engine.decode_step = stale
        seqs, got = _decode_through(engine, [prompt], steps)
    finally:
        if what == "slides":
            import opendiloco_tpu.models.llama as llama
            llama.eva_decode_step_attention = attention.eva_decode_step_attention
    want = np.asarray(reference.forward(params, jnp.asarray([seqs[0]]), raw))[0, n - 1 :]
    assert rel(got[0], want) > 30 * REL_L2, rel(got[0], want)


def test_each_refusal_by_name():
    """What takes a slot's ring for its context is refused for EVA, each by
    its name: prefix reuse and its continued prefill, the host tier, the
    flash and ring kernels, the pp pipeline, the HF llama layout; and a
    request that its pooled ring cannot hold."""
    from opendiloco_tpu.models.hf_io import save_params
    from opendiloco_tpu.serve.kvcache import HostKVTier

    _, cfg, params = model()
    match = "refused for a configuration with EVA attention"
    make = lambda **kw: _engine(cfg, params, "xla", **kw)
    engine = make()
    for kw in ({"prefix_cache": True}, {"kv_tier": HostKVTier(host_slots=2)}):
        with pytest.raises(ValueError, match=match):
            ContinuousBatcher(engine, **kw)
    engine.admit(0, list(range(1, 20)))
    with pytest.raises(ValueError, match=match):
        engine.admit(1, list(range(1, 20)), prefix_src=0, prefix_len=8)
    with pytest.raises(ValueError, match=match):
        engine.fetch_slot_pages(0, 8)
    with pytest.raises(ValueError, match=match):
        engine.install_slot_pages(0, np.zeros(1), np.zeros(1))
    ids = jnp.zeros((1, 8), jnp.int32)
    vec = jnp.zeros((3,), jnp.int32)
    with pytest.raises(ValueError, match=match):
        chunk_prefill_forward(params, vec[None, :], 0, 3, 0, engine.cache_k, engine.cache_v, None, cfg, **F32)
    for impl in ("pallas", "ring"):
        with pytest.raises(ValueError, match=match):
            forward(params, ids, cfg, attn_impl=impl, **F32)
    with pytest.raises(ValueError, match="EVA attention"):
        save_params(params, cfg, "/nonexistent")
    # none wraps: a request longer than the pooled ring's context is refused
    batcher = ContinuousBatcher(engine)
    req = batcher.submit(list(range(1, 40)), max_new_tokens=5 * WINDOW)
    assert req.error is not None and "exceed max_context" in req.error
    assert batcher.stats()["eva"]["cache_resident_bytes"] == engine.eva_cache_resident_bytes


def test_counters_are_zero_for_every_other_configuration_and_spans_carry_them():
    """A dense engine reads 0 on every EVA counter and its spans carry no EVA
    attribute; an EVA engine's ``serve_prefill`` and ``serve_decode`` spans
    carry ``eva_local_rows``, ``eva_pooled_rows`` and ``eva_bytes`` while a
    tracer is armed, and ``decode_plan_stats()`` carries the rings' plans."""
    from opendiloco_tpu import obs

    dense = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=48,
                        num_hidden_layers=2, num_attention_heads=4)
    plain = ServeEngine(dense, init_params(jax.random.key(0), dense), num_slots=2, max_context=32,
                        prefill_buckets=(16,), compute_dtype=jnp.float32)
    plain.admit(0, [1, 2, 3])
    plain.decode_step(np.array([1, 0], np.int32), np.array([3, 0], np.int32))
    names = ("eva_local_rows_read", "eva_pooled_rows_read", "eva_chunks_pooled",
             "eva_window_restarts", "eva_cache_bytes_moved", "eva_cache_resident_bytes")
    assert all(getattr(plain, name) == 0 for name in names)
    _, cfg, params = model()
    engine = _engine(cfg, params, "xla")
    obs.capture.start()
    try:
        tok, _ = engine.admit(0, list(range(1, 36)))
        engine.decode_step(np.array([tok, 0, 0], np.int32), np.array([35, 0, 0], np.int32))
    finally:
        cap = obs.capture.stop()
    spans = {s["name"]: s for s in cap.spans if s["name"] in ("serve_prefill", "serve_decode")}
    L = cfg.num_hidden_layers
    assert spans["serve_prefill"]["args"]["eva_local_rows"] == L * (35 % WINDOW)
    assert spans["serve_prefill"]["args"]["eva_pooled_rows"] == L * 9
    assert spans["serve_decode"]["args"]["eva_local_rows"] == L * (35 % WINDOW + 1)
    assert spans["serve_decode"]["args"]["eva_pooled_rows"] == L * 2 * (WINDOW // CHUNK)
    assert spans["serve_decode"]["args"]["eva_bytes"] > 0
    assert engine.eva_cache_bytes_moved == (
        spans["serve_prefill"]["args"]["eva_bytes"] + spans["serve_decode"]["args"]["eva_bytes"])
    plan = engine.decode_plan_stats()
    assert engine.eva_cache_resident_bytes > 0
    assert "eva_pooled_plan_block_t" in plan and "decode_plan_heads" in plan
