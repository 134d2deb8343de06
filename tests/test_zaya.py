"""ZAYA1's block (CCA: q and k through two causal convolutions, values that
look one token back, so a per-slot state beside the ring; a router that is an
MLP fed by the layer before, top-1 under a selection bias beside the softmax;
a learned scale and bias per channel on stream and branch; ``head_dim`` as a
key and a partial rotation) through every path of the program, against the
float32 reference written from its equations
(``benchmark/odbench/reference_zaya.py``: convolutions as shifted sums over
the whole sequence, every expert on every token, nothing imported from the
program). Tiny sizes, seeded random weights, everything float32 on the CPU.

Tolerances. Program and reference both compute in float32 here and differ in
the order of accumulation only (grouped matmuls over sorted pairs against
every expert on every token; a decode step's state against a shift): 3e-7
relative L2 on these sizes, and 1e-4 leaves more than two orders of magnitude.
Anything structural -- a convolution left out, the means left out, values
that do not look back, the whole head rotated, a router that ignores the layer
before, a bias that weighs, a stale state, operands below float32 -- gives
2e-3 and more (the last tests show it). A flipped top-1 choice needs two
biased scores within float32 rounding of each other; the seeds here are fixed
and have none.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opendiloco_tpu.models import llama
from opendiloco_tpu.models.llama import (
    LlamaConfig, Run, decode_forward, forward, init_params, layer_runs, prefill_forward,
)
from opendiloco_tpu.models.ring_cache import cca_state_insert, init_cca_state, init_kv_cache
from opendiloco_tpu.parallel.mesh import build_mesh
from opendiloco_tpu.serve import ContinuousBatcher, ServeEngine
from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from odbench import reference_zaya as reference  # noqa: E402

REL_L2 = 1e-4
RING = 24  # rows of a slot's ring in the engine tests: three kernel tiles of 8
LAYERS = 3


def published(**over) -> dict:
    """The published ``config.json``'s keys at a tiny size: 4 query heads over
    2 KV heads of 16 (64 is not the hidden size), half of each head rotated."""
    raw = {
        "model_type": "zaya", "hidden_size": 32, "head_dim": 16, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_hidden_layers": LAYERS, "layer_types": ["hybrid"] * LAYERS,
        "cca_time0": 2, "cca_time1": 2, "partial_rotary_factor": 0.5, "router_hidden_size": 12,
        "num_experts": 8, "num_experts_per_tok": 1, "moe_intermediate_size": 16,
        "rope_parameters": {"hybrid": {"rope_theta": 5e6, "partial_rotary_factor": 0.5}},
        "vocab_size": 128, "max_position_embeddings": 256, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": True, "attention_bias": False, "hidden_act": "silu",
    }
    raw.update(over)
    return raw


def model(seed: int = 0, **over):
    raw = published(**over)
    cfg = LlamaConfig.from_dict(raw)
    params = init_params(jax.random.key(seed), cfg)
    # norms away from 1 and an FFN as large as the residual, so that every
    # leaf matters to the result (the scalings, gamma, the temperature and the
    # selection bias are drawn away from their neutral values already)
    keys = iter(jax.random.split(jax.random.key(seed + 100), 16))
    stack = params["layers"]
    for name in ("input_norm", "post_attn_norm", "router_norm"):
        stack[name] = 1.0 + 0.3 * jax.random.normal(next(keys), stack[name].shape)
    for name in ("gate_proj", "up_proj", "down_proj", "o_proj"):
        stack[name] = stack[name] * 4.0
    return raw, cfg, params


def rel_l2(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.sum((got - want) ** 2) / np.sum(want**2)))


def tokens(seed: int, shape) -> np.ndarray:
    return np.random.default_rng(seed).integers(3, 128, shape).astype(np.int32)


def one_layer(params, i=0) -> dict:
    return {name: leaf[i] for name, leaf in params["layers"].items()}


def test_published_keys_mean_this_block():
    raw = published()
    cfg = LlamaConfig.from_dict(raw)
    assert cfg.cca and not cfg.hybrid and not cfg.latent and not cfg.layers_by_kind
    assert cfg.layer_types is None and layer_runs(cfg) == [Run("attention", 0, LAYERS, 0)]
    assert (cfg.head_dim, cfg.rotary_dim, cfg.rope_theta) == (16, 8, 5e6)
    assert cfg.cca_state_dim == 2 * (4 + 2) * 16 + 16  # z, c, the values handed on
    assert cfg.residual_scaling and cfg.router_hidden_size == 12 and cfg.expert_width == 16
    assert cfg.router_aux_loss_coef == 0.0 and cfg.topk_method == "greedy"
    assert LlamaConfig.from_dict(cfg.to_dict()) == cfg
    shapes = llama.shapes(cfg)["layers"]
    L = LAYERS
    assert shapes["q_proj"].shape == (L, 32, 64) and shapes["o_proj"].shape == (L, 64, 32)
    assert shapes["k_proj"].shape == (L, 32, 32)
    assert shapes["v_proj"].shape == shapes["v_prev_proj"].shape == (L, 32, 16)
    assert shapes["cca_conv0_weight"].shape == (L, 2, 96) and shapes["cca_conv0_bias"].shape == (L, 96)
    assert shapes["cca_conv1_weight"].shape == (L, 6, 2, 16, 16) and shapes["cca_k_temp"].shape == (L, 2)
    assert shapes["router_down"].shape == (L, 32, 12) and shapes["router"].shape == (L, 12, 8)
    assert shapes["router_fc1"].shape == shapes["router_fc2"].shape == (L, 12, 12)
    assert shapes["router_bias"].shape == (L, 8) and shapes["router_gamma"].shape == (L, 12)
    assert shapes["gate_proj"].shape == (L, 8, 32, 16)
    scalings = [n for n in shapes if n.endswith(("_scale", "_bias")) and n[:4] in ("attn", "ffn_")]
    assert len(scalings) == 8 and all(shapes[n].shape == (L, 32) for n in scalings)
    # every term is drawn away from the value that would leave it untested
    drawn = init_params(jax.random.key(0), cfg)["layers"]
    for name in ("router_bias", "router_gamma", "cca_k_temp", "attn_stream_scale",
                 "ffn_branch_bias", "cca_conv0_weight", "cca_conv1_bias"):
        assert float(jnp.std(drawn[name])) > 1e-3, name
    # a layer type the block does not compute is refused, not run as another
    with pytest.raises(ValueError, match="'hybrid' layers alone"):
        LlamaConfig.from_dict(published(layer_types=["hybrid", "hybrid_sliding", "hybrid"]))
    with pytest.raises(ValueError, match="two convolutions over 2 tokens"):
        LlamaConfig.from_dict(published(cca_time1=3))
    with pytest.raises(ValueError, match="even number of values"):
        LlamaConfig.from_dict(published(partial_rotary_factor=0.45))


def test_the_catalog_rows_config_builds_the_40_layer_model():
    """``LlamaConfig.from_dict`` of the catalog row's ``config`` (the
    configuration file less its cut), and its parameters counted by leaf
    against the file's stated arithmetic."""
    import json

    with open(os.path.join(BENCH, "configs", "zaya1-8b.json")) as f:
        real = json.load(f)
    cfg = LlamaConfig.from_dict(real)
    counted = real["parameters"]
    assert cfg.num_params() == counted["as_run"] == 10 * counted["a_layer"] + counted["embedding_tied"] + 2048
    whole = LlamaConfig.from_dict({**real, **real["published"]})
    assert whole.num_hidden_layers == 40 and whole.num_params() == counted["published"]
    assert (whole.head_dim, whole.rotary_dim, whole.kv_heads, whole.num_experts) == (128, 64, 2, 16)
    assert whole.cca_state_dim == 2688 and whole.rope_theta == 5e6
    per_leaf = {name: int(np.prod(s.shape[1:])) for name, s in llama.shapes(whole)["layers"].items()}
    attention = sum(per_leaf[n] for n in ("q_proj", "k_proj", "v_proj", "v_prev_proj", "o_proj"))
    convs = sum(v for n, v in per_leaf.items() if n.startswith("cca_conv"))
    router = sum(v for n, v in per_leaf.items() if n.startswith("router"))
    experts = sum(per_leaf[n] for n in ("gate_proj", "up_proj", "down_proj"))
    assert (attention, convs, router, experts) == (
        counted["attention"], counted["convolutions"], counted["router"], counted["experts"])
    assert sum(per_leaf.values()) == counted["a_layer"]


def test_the_cca_projection_alone():
    """q, k and v of one layer over a sequence: both convolutions with zeros
    before the sequence, the means, the normalisation and the temperature,
    the rotation of half a head, the values' look-back."""
    raw, cfg, params = model(seed=1)
    w = one_layer(params)
    x = jax.random.normal(jax.random.key(2), (2, 19, cfg.hidden_size), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(19), (2, 19))
    q, k, v, tails = llama._cca_qkv(cfg, x, w, *llama._rope(cfg, pos))
    want = reference.cca_qkv(x, w, raw)
    assert q.shape == (2, 19, 4, 16) and k.shape == v.shape == (2, 19, 2, 16)
    assert tails.shape == (2, 19, cfg.cca_state_dim)
    for got, ref in zip((q, k, v), want):
        assert rel_l2(got, ref) < REL_L2
    # each head of q has norm sqrt(Dh); KV head 1 holds the token before's values
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(q, axis=-1)), 4.0, rtol=3e-3)  # the eps
    np.testing.assert_array_equal(np.asarray(v[:, 0, 1]), 0.0)
    np.testing.assert_allclose(
        np.asarray(v[:, 1:, 1]), np.asarray((x @ w["v_prev_proj"])[:, :-1]), rtol=1e-6, atol=1e-7)
    # token by token through the state, as a decode step runs it, is the same
    past, rows = None, []
    for t in range(19):
        qt, kt, vt, tail = llama._cca_qkv(
            cfg, x[:, t : t + 1], w, *llama._rope(cfg, pos[:, t : t + 1]), past)
        past = tail[:, 0]
        rows.append((qt, kt, vt))
    for i, full in enumerate((q, k, v)):
        stepped = jnp.concatenate([r[i] for r in rows], axis=1)
        np.testing.assert_allclose(np.asarray(stepped), np.asarray(full), rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(past), np.asarray(tails[:, -1]), rtol=2e-5, atol=2e-6)


def test_the_router_reads_the_layer_before_and_chooses_under_the_bias():
    raw, cfg, params = model(seed=5)
    m = jax.random.normal(jax.random.key(6), (2, 23, cfg.hidden_size), jnp.float32)
    carried = jax.random.normal(jax.random.key(7), (2, 23, 12), jnp.float32)
    w = one_layer(params, 1)
    want, r, own, margin = reference.routed_ffn(m, w, raw, carried)
    chosen = []
    features, handed = llama._router_features(cfg, m.reshape(-1, 32), w, carried)
    out, _, counts = llama._routed_ffn(cfg, m, w, None, features, chosen)
    assert rel_l2(out, want) < REL_L2 and rel_l2(handed.reshape(r.shape), r) < REL_L2
    np.testing.assert_array_equal(np.asarray(chosen[0]).reshape(2, 23), np.asarray(own))
    assert int(counts[0]) == 2 * 23 and float(margin.min()) > 1e-5
    # the layer before matters, the bias moves some choices and weighs nothing
    assert rel_l2(reference.routed_ffn(m, w, raw, None)[0], want) > 20 * REL_L2
    _, _, unbiased, _ = reference.routed_ffn(m, {**w, "router_bias": jnp.zeros(8)}, raw, carried)
    moved = np.asarray(unbiased != own)
    assert 0 < moved.sum() < moved.size
    followed = reference.routed_ffn(m, {**w, "router_bias": jnp.zeros(8)}, raw, carried, own)[0]
    np.testing.assert_allclose(np.asarray(followed), np.asarray(want), rtol=1e-5, atol=1e-7)
    # a linear router hands nothing on and reads the tokens themselves
    assert llama._router_features(cfg, m.reshape(-1, 32), {"router": 0}, None)[1] is None


@pytest.mark.parametrize("remat", [False, True])
def test_forward_logits_against_the_reference(remat):
    raw, cfg, params = model(seed=7)
    ids = tokens(8, (3, 37))
    got = forward(params, ids, cfg, compute_dtype=jnp.float32, remat=remat)
    want, own, margin = jax.jit(
        lambda p, i: reference.forward(p, i, raw, with_choices=True))(params, ids)
    assert rel_l2(got, want) < REL_L2
    assert own.shape == margin.shape == (3, 37, LAYERS) and float(margin.min()) > 0
    # following its own choices is the same walk; following others is another
    again = jax.jit(lambda p, i, f: reference.forward(p, i, raw, follow=f))(params, ids, own)
    np.testing.assert_allclose(np.asarray(again), np.asarray(want), rtol=1e-6, atol=1e-7)
    other = jax.jit(lambda p, i, f: reference.forward(p, i, raw, follow=f))(params, ids, (own + 1) % 8)
    assert rel_l2(other, want) > 20 * REL_L2
    # the rows asked for are those rows
    part = jax.jit(lambda p, i: reference.forward(p, i, raw, rows=(30, 5)))(params, ids)
    np.testing.assert_allclose(np.asarray(part), np.asarray(want)[:, 30:35], rtol=1e-6, atol=1e-7)


def test_train_step_loss_and_gradient_against_the_reference():
    """Through ``InnerTrainer.train_step`` in float32 on the CPU mesh: the loss
    (the configuration states no aux loss) and the gradient's norm."""
    raw, cfg, params = model(seed=9)
    tc = TrainerConfig(precision="fp32", remat=False, total_steps=10, warmup_steps=2)
    trainer = InnerTrainer(cfg, tc, build_mesh("NO_SHARD"))
    state = trainer.init_state(jax.random.key(0))
    state["params"] = jax.device_put(  # a copy: the step donates its state
        jax.tree.map(jnp.copy, params), jax.tree.map(lambda x: x.sharding, state["params"]))
    ids = tokens(10, (8, 32))
    _, m = trainer.train_step(state, trainer.shard_batch(ids, ids.copy(), accum=1))
    want_loss, want_norm = jax.jit(
        lambda p, i: reference.loss_and_grad_norm(p, i, i, raw)
    )(params, ids)
    np.testing.assert_allclose(float(m["loss"]), float(want_loss), rtol=1e-5)
    np.testing.assert_allclose(float(m["grad_norm"]), float(want_norm), rtol=1e-4)


def test_fsdp_sees_the_new_leaves():
    """FULL_SHARD over the 8-device CPU mesh: every leaf has a spec, the
    projections are sharded, the small leaves replicated, and a step runs."""
    from jax.sharding import PartitionSpec as P

    from opendiloco_tpu.parallel.sharding import param_specs

    _, cfg, _ = model(seed=9)
    trainer = InnerTrainer(cfg, TrainerConfig(precision="fp32", total_steps=10, warmup_steps=2),
                           build_mesh("FULL_SHARD"))
    specs = param_specs(cfg, trainer.plan)["layers"]
    for name in ("q_proj", "k_proj", "v_proj", "v_prev_proj", "o_proj", "router_down"):
        assert specs[name] != P(), name
    for name in ("cca_conv0_weight", "cca_conv1_weight", "cca_k_temp", "router_fc1",
                 "router_gamma", "attn_stream_scale", "ffn_branch_bias"):
        assert all(axis is None for axis in specs[name]), name  # replicated
    state = trainer.init_state(jax.random.key(0))
    ids = tokens(11, (8, 16))
    _, m = trainer.train_step(state, trainer.shard_batch(ids, ids.copy(), accum=1))
    assert np.isfinite(float(m["loss"]))


def engine_for(cfg, params, **kw):
    kw = {"num_slots": 4, "max_context": RING, "prefill_buckets": (8, 16),
          "compute_dtype": jnp.float32, "decode_kernel": "xla", **kw}
    return ServeEngine(cfg, params, **kw)


def serve(engine, prompts, steps, slots=None):
    """Prefill each prompt into a slot, then ``steps`` decode steps through
    ring and state -> per prompt (the token sequence that was fed, the logits
    rows of its last ``steps + 1`` positions)."""
    slots = list(slots or range(len(prompts)))
    toks, lens = np.zeros(engine.num_slots, np.int32), np.zeros(engine.num_slots, np.int32)
    seqs, rows = [], []
    for slot, prompt in zip(slots, prompts):
        tok, logits = engine.admit(slot, prompt)
        toks[slot], lens[slot] = tok, len(prompt)
        seqs.append(list(prompt) + [tok])
        rows.append([np.asarray(logits)])
    for step in range(steps):
        nxt, logits = engine.decode_step(toks.copy(), lens.copy())
        logits = np.asarray(logits)
        for i, slot in enumerate(slots):
            rows[i].append(logits[slot])
            toks[slot] = nxt[slot]
            lens[slot] += 1
            if step < steps - 1:
                seqs[i].append(int(nxt[slot]))
    return seqs, [np.stack(r) for r in rows]


def against_reference(raw, params, prompts, seqs, rows, steps) -> float:
    """Worst relative L2 over the prompts' compared rows."""
    ref = jax.jit(lambda p, i: reference.forward(p, i, raw))
    worst = 0.0
    for prompt, seq, got in zip(prompts, seqs, rows):
        want = np.asarray(ref(params, np.asarray([seq], np.int32)))[0]
        first = len(prompt) - 1
        worst = max(worst, rel_l2(got, want[first : first + steps + 1]))
    return worst


def runs_the_decode_kernel(engine) -> bool:
    vec = jnp.zeros((engine.num_slots,), jnp.int32)
    jaxpr = jax.make_jaxpr(engine._decode)(
        engine.params, vec, vec, engine.cache_k, engine.cache_v, *engine._cca)
    return "odtp_paged_decode_attn" in str(jaxpr)


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_engine_prefill_then_decode_at_every_bucket_edge(kernel, monkeypatch):
    """Prompts of 1, 7, 8 (a bucket's edge), 9 and 16 tokens, all but two
    shorter than their bucket: prefill pads each, hands K/V and the state at
    the prompt's true length over, then five decode steps through ring and
    state; and the engine's counters."""
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "8")
    raw, cfg, params = model(seed=12)
    steps = 5
    lengths = [1, 7, 8, 9, 16]
    worst, engine = 0.0, None
    for group in (lengths[:4], lengths[4:]):
        engine = engine_for(cfg, params, decode_kernel=kernel)
        prompts = [tokens(13 + n, n).tolist() for n in group]
        seqs, rows = serve(engine, prompts, steps)
        worst = max(worst, against_reference(raw, params, prompts, seqs, rows, steps))
    assert worst < REL_L2
    assert runs_the_decode_kernel(engine) == (kernel == "pallas")
    # the last engine served one prompt of 16 and five steps of one live slot
    assert engine.cache_k.shape == engine.cache_v.shape == (LAYERS, 4, 2, 16, RING)
    (state,) = engine._cca
    assert state.shape == (LAYERS, 4, cfg.cca_state_dim)
    resident = LAYERS * 4 * cfg.cca_state_dim * 4
    assert engine.cca_state_resident_bytes == resident
    assert engine.cca_tokens == 16 + steps
    assert engine.cca_state_bytes_moved == resident // 4 + steps * 2 * resident
    assert engine.moe_pairs == engine.moe_pairs_all == (16 + steps) * LAYERS  # a pair is a token
    assert engine.ssm_tokens == 0 and engine.latent_rows_read == 0


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_decode_across_the_rings_wrap(kernel, monkeypatch):
    """A slot decodes past its ring's 24 rows beside one that does not: the
    row written at ``lens % T`` replaces the oldest, attention slides over the
    last T tokens, and the state knows no ring. Both paths agree with each
    other to rounding and, until the wrap, with the reference."""
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "8")
    raw, cfg, params = model(seed=14)
    prompts, steps = [tokens(15, 16).tolist(), tokens(16, 3).tolist()], 14  # up to 30 > 24
    seqs, rows = serve(engine_for(cfg, params, decode_kernel=kernel), prompts, steps)
    before_wrap = RING - 16  # steps whose position is still inside the ring
    assert against_reference(
        raw, params, prompts[:1], seqs[:1], [rows[0][:before_wrap]], before_wrap - 1) < REL_L2
    assert against_reference(raw, params, prompts[1:], seqs[1:], rows[1:], steps) < REL_L2
    seqs_x, rows_x = serve(engine_for(cfg, params, decode_kernel="xla"), prompts, steps)
    assert seqs == seqs_x and rel_l2(rows[0], rows_x[0]) < REL_L2
    # past the wrap the full-sequence reference sees tokens the ring dropped
    assert against_reference(raw, params, prompts[:1], seqs[:1], rows[:1], steps) > REL_L2


def test_decode_equals_prefill_of_the_same_tokens_step_by_step():
    """After a prompt of n tokens and k decode steps the slot's state (both
    tails and the shifted value of every layer, which hold what the router's
    state and everything else before them made of the tokens) and its ring
    rows are what a prefill of the n + k tokens leaves, for every k."""
    _, cfg, params = model(seed=16)
    n, steps = 6, 7
    seq = tokens(17, n + steps)
    f32 = dict(compute_dtype=jnp.float32)
    logits, ks, vs, tails = prefill_forward(params, jnp.asarray(seq[None, :n]), jnp.int32(n), cfg, **f32)
    cache = init_kv_cache(cfg, 2, RING, jnp.float32)
    ck, cv = llama.cache_insert(cache["k"], cache["v"], ks, vs, jnp.int32(1))
    state = cca_state_insert(init_cca_state(cfg, 2, jnp.float32), tails, jnp.int32(1))
    for k in range(steps):
        toks = jnp.asarray([0, seq[n + k]], jnp.int32)
        lens = jnp.asarray([0, n + k], jnp.int32)
        step, ck, cv, state = decode_forward(params, toks, lens, ck, cv, cfg, cca_state=state, **f32)
        upto = n + k + 1
        want_logits, want_k, want_v, want_tails = prefill_forward(
            params, jnp.asarray(seq[None, :upto]), jnp.int32(upto), cfg, **f32)
        np.testing.assert_allclose(np.asarray(step[1]), np.asarray(want_logits[0]), rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(np.asarray(state[:, 1]), np.asarray(want_tails), rtol=2e-4, atol=2e-5)
        got_k = np.moveaxis(np.asarray(ck[:, 1, :, :, :upto]), -1, 1)  # [L, rows, Nkv, Dh]
        np.testing.assert_allclose(got_k, np.asarray(want_k), rtol=2e-4, atol=2e-5)
        got_v = np.moveaxis(np.asarray(cv[:, 1, :, :, :upto]), -1, 1)
        np.testing.assert_allclose(got_v, np.asarray(want_v), rtol=2e-4, atol=2e-5)


def test_padding_rows_change_nothing():
    """What a padded prefill hands over (the live rows, and the state at the
    prompt's true length) is what the unpadded prompt leaves, whatever the
    padding holds: a bucket's padding rows do not reach the state."""
    _, cfg, params = model(seed=18)
    n, bucket = 11, 16
    prompt = tokens(19, n)
    run = lambda ids, length: prefill_forward(
        params, jnp.asarray(ids[None]), jnp.int32(length), cfg, compute_dtype=jnp.float32)
    logits, ks, vs, tails = run(prompt, n)
    assert ks.shape == vs.shape == (LAYERS, n, 2, 16) and tails.shape == (LAYERS, cfg.cca_state_dim)
    for filler in (0, 77):
        padded = np.full(bucket, filler, np.int32)
        padded[:n] = prompt
        got_logits, got_k, got_v, got_tails = run(padded, n)
        np.testing.assert_allclose(np.asarray(got_logits), np.asarray(logits), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_k)[:, :n], np.asarray(ks), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_v)[:, :n], np.asarray(vs), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_tails), np.asarray(tails), rtol=1e-5, atol=1e-6)
    # the state at the bucket's end is another
    assert rel_l2(run(np.full(bucket, 77, np.int32), bucket)[3], tails) > 0.1


def test_slot_reuse_starts_clean():
    """A slot's second tenant decodes as if the slot had never been used: the
    first tenant's rows beyond the new prompt are stale and masked, and its
    state is replaced whole."""
    raw, cfg, params = model(seed=20)
    engine = engine_for(cfg, params)
    serve(engine, [tokens(21, 16).tolist()], 6, slots=[2])
    second = [tokens(22, 5).tolist()]
    seqs, rows = serve(engine, second, 6, slots=[2])
    assert against_reference(raw, params, second, seqs, rows, 6) < REL_L2


def test_batcher_serves_the_block():
    """Through ``ContinuousBatcher``: more requests than slots, so slots are
    reused while others decode; greedy tokens equal the training forward's."""
    _, cfg, params = model(seed=23)
    batcher = ContinuousBatcher(engine_for(cfg, params, num_slots=2)).start()
    try:
        prompts = [tokens(24 + i, n).tolist() for i, n in enumerate((5, 8, 9, 16, 3))]
        reqs = [batcher.submit(p, max_new_tokens=4) for p in prompts]
        for r in reqs:
            assert r.wait(120) and r.error is None, r.error
    finally:
        batcher.stop()
    for prompt, r in zip(prompts, reqs):
        seq = list(prompt)
        for _ in range(4):
            logits = forward(params, jnp.asarray([seq]), cfg, compute_dtype=jnp.float32, remat=False)
            seq.append(int(jnp.argmax(logits[0, -1])))
        assert r.tokens == seq[len(prompt):]


def test_the_engine_keeps_the_programs_choices_when_asked():
    """``keep_expert_choices``, set before the first call: each call's experts
    by token and layer stay on the device, and are the reference's."""
    raw, cfg, params = model(seed=25)
    engine = engine_for(cfg, params)
    engine.keep_expert_choices()
    prompt = tokens(26, 11).tolist()
    tok, _ = engine.admit(1, prompt)
    assert engine.expert_choices.shape == (LAYERS, 16, 1)  # the bucket's rows
    own = reference.forward(params, np.asarray([prompt + [tok]], np.int32), raw, with_choices=True)[1]
    np.testing.assert_array_equal(np.asarray(engine.expert_choices)[:, :11, 0].T, np.asarray(own)[0, :11])
    toks, lens = np.zeros(4, np.int32), np.zeros(4, np.int32)
    toks[1], lens[1] = tok, 11
    engine.decode_step(toks, lens)
    assert engine.expert_choices.shape == (LAYERS, 4, 1)
    np.testing.assert_array_equal(np.asarray(engine.expert_choices)[:, 1, 0], np.asarray(own)[0, 11])
    assert engine_for(cfg, params).expert_choices is None


REFUSED = "refused for a configuration with CCA"


def test_what_cannot_follow_the_state_says_so(tmp_path):
    from opendiloco_tpu.models import hf_io
    from opendiloco_tpu.parallel.pipeline import pipeline_hidden
    from opendiloco_tpu.serve.kvcache import HostKVTier

    _, cfg, params = model(seed=27)
    engine = engine_for(cfg, params)
    with pytest.raises(ValueError, match=f"prefix_cache is {REFUSED}"):
        ContinuousBatcher(engine, prefix_cache=True)
    with pytest.raises(ValueError, match=f"kv_tier is {REFUSED}"):
        ContinuousBatcher(engine, kv_tier=HostKVTier(host_slots=2))
    engine.admit(0, tokens(28, 12).tolist())
    with pytest.raises(ValueError, match=f"prefix reuse.*{REFUSED}"):
        engine.admit(1, tokens(28, 16).tolist(), prefix_src=0, prefix_len=8)
    with pytest.raises(ValueError, match=f"page-out is {REFUSED}"):
        engine.fetch_slot_pages(0, 12)
    with pytest.raises(ValueError, match=f"page-in is {REFUSED}"):
        engine.install_slot_pages(0, np.zeros((LAYERS, 16, 2, 16)), np.zeros((LAYERS, 16, 2, 16)))
    with pytest.raises(ValueError, match=f"continued prefill.*{REFUSED}"):
        llama.chunk_prefill_forward(params, jnp.zeros((1, 2), jnp.int32), 0, 2, 0, engine.cache_k, engine.cache_v, None, cfg)
    with pytest.raises(ValueError, match="pp pipeline is refused for a configuration whose router reads"):
        pipeline_hidden(params, jnp.zeros((2, 8, 32)), None, cfg, None, microbatches=2, attn_fn=None)
    with pytest.raises(ValueError, match="no CCA"):
        hf_io.save_params(params, cfg, str(tmp_path))
    # what works unchanged is not refused: the plan for these heads, a weight swap
    assert "decode_plan_heads" in engine.decode_plan_stats()
    engine.install_params(1, params)
    assert engine.weight_binds == 2


def _existing_models():
    from test_model import _dense_model, _latent_routed_model, _routed_qk_norm_model

    return [_dense_model, _routed_qk_norm_model, _latent_routed_model]


@pytest.mark.parametrize("which", [0, 1, 2, "granite"])
def test_a_derived_head_dim_and_a_whole_rotation_leave_existing_models_as_they_were(which):
    """``head_dim`` absent (derived) or stated as what it derives to, and
    ``partial_rotary_factor`` absent or 1.0: one configuration, the same
    parameters and the same logits, bit for bit."""
    if which == "granite":
        from test_granite_hybrid import published as granite

        cfg = LlamaConfig.from_dict(granite())
        params = init_params(jax.random.key(3), cfg)
    else:
        cfg, params = _existing_models()[which]()
    raw = cfg.to_dict()
    assert "head_dim" not in raw and raw["partial_rotary_factor"] == 1.0
    stated = LlamaConfig.from_dict(
        {**raw, "head_dim": cfg.hidden_size // cfg.num_attention_heads, "partial_rotary_factor": 1.0})
    del raw["partial_rotary_factor"]
    import dataclasses

    # (``to_dict`` writes the KV heads out where they were left to the query heads')
    assert LlamaConfig.from_dict(raw) == stated == dataclasses.replace(
        cfg, num_key_value_heads=cfg.kv_heads)
    assert stated.rotary_dim == stated.head_dim == cfg.hidden_size // cfg.num_attention_heads
    again = init_params(jax.random.key(3 if which == "granite" else (3, 4, 5)[which]), stated)
    if which in (0, "granite"):  # the others' makers scale their routers after the draw
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)), params, again)
    ids = tokens(31, (2, 12))
    one = forward(params, ids, cfg, compute_dtype=jnp.float32, remat=False)
    two = forward(params, ids, stated, compute_dtype=jnp.float32, remat=False)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(two))


FAULTS = ["no_conv0", "no_conv1_back", "no_mean", "no_temp", "own_values_only", "full_rotary",
          "no_carry", "bias_weighed", "no_residual_scaling", "stale_state", "bfloat16",
          "float8_e4m3fn"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_tolerance_catches_what_it_must(fault):
    """Each fault moves the compared rows (the last prompt position and five
    decode steps, as the cell's check compares) by far more than ``REL_L2``."""
    raw, cfg, params = model(seed=30)
    prompt, steps = tokens(31, 13).tolist(), 5
    ref = jax.jit(lambda p, i, **kw: reference.forward(p, i, raw, **kw),
                  static_argnames=("operands", "faults"))
    engine = engine_for(cfg, params)
    if fault == "stale_state":
        # the previous tenant's state where the new prompt's belongs: what a
        # prefill that skipped the state's insert would leave
        engine.admit(1, tokens(32, 16).tolist())
        old = jnp.copy(engine._cca[0])  # the next insert donates the engine's
        tok, logits = engine.admit(1, prompt)
        engine._cca = (old,)
        toks, lens = np.zeros(4, np.int32), np.zeros(4, np.int32)
        toks[1], lens[1] = tok, len(prompt)
        seq, rows = list(prompt) + [tok], []
        for step in range(steps):
            nxt, logits = engine.decode_step(toks.copy(), lens.copy())
            rows.append(np.asarray(logits)[1])
            toks[1], lens[1] = nxt[1], lens[1] + 1
            if step < steps - 1:
                seq.append(int(nxt[1]))
        want = np.asarray(ref(params, np.asarray([seq], np.int32)))[0]
        got, want = np.stack(rows), want[len(prompt) : len(prompt) + steps]
    else:
        seqs, rows = serve(engine, [prompt], steps, slots=[1])
        ids = np.asarray([seqs[0]], np.int32)
        first = len(prompt) - 1
        kw = ({"operands": getattr(jnp, fault)} if fault in ("bfloat16", "float8_e4m3fn")
              else {"faults": (fault,)})
        got = np.asarray(ref(params, ids, **kw))[0, first : first + steps + 1]
        want = np.asarray(ref(params, ids))[0, first : first + steps + 1]
        assert rel_l2(rows[0], want) < REL_L2  # the engine itself is inside
    assert rel_l2(got, want) > 20 * REL_L2, rel_l2(got, want)
