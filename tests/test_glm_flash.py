"""GLM-4.7-Flash's block (latent attention over a ring of one latent row a
token, rebuilt in training and prefill and absorbed in decode; a sigmoid
router that chooses under a bias it does not weigh by, beside a shared expert;
a leading dense layer; one chip's share of the experts) through every path of
the program, against the float32 reference written from its equations
(``benchmark/odbench/reference_glm_flash.py``: attention in the rebuilt form
only, every held expert on every token, nothing imported from the program).
Tiny sizes, seeded random weights, everything float32 on the CPU.

Tolerances. Program and reference both compute in float32 here and differ in
the order of accumulation only: the absorbed form multiplies ``q W_UK^T`` and
then the latent where the reference multiplies the latent by ``W_UK`` and then
q (and the same on the value side), grouped matmuls over sorted pairs run
against every expert on every token. That measured 2e-7 relative L2 on these
sizes; 1e-4 leaves nearly three orders of magnitude. Anything structural -- a
latent without its norm, the rotation on the wrong part, a bias that weighs,
softmax scores, a missing scale, values from another part of the row, a stale
row, operands below float32 -- gives 2e-3 and more (the last tests show it).
A flipped choice between the k-th and (k+1)-th expert needs two biased scores
within float32 rounding of each other; the seeds here are fixed and have none.
"""

import dataclasses
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
from opendiloco_tpu.models.ring_cache import init_kv_cache
from opendiloco_tpu.parallel.mesh import build_mesh
from opendiloco_tpu.serve import ContinuousBatcher, ServeEngine
from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from odbench import reference_glm_flash as reference  # noqa: E402

REL_L2 = 1e-4
RING = 24  # rows of a slot's ring in the engine tests: three kernel tiles of 8


def published(**over) -> dict:
    """The published ``config.json``'s keys at a tiny size: 16 experts of
    which this share holds 8, from the 8th on; one leading dense layer."""
    raw = {
        "model_type": "glm4_moe_lite", "hidden_size": 32, "intermediate_size": 48,
        "moe_intermediate_size": 16, "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
        "qk_nope_head_dim": 12, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "first_k_dense_replace": 1, "num_experts": 16, "n_routed_experts": 8,
        "first_local_expert": 8, "n_shared_experts": 1, "num_experts_per_tok": 3,
        "topk_method": "noaux_tc", "norm_topk_prob": True, "routed_scaling_factor": 1.8,
        "n_group": 1, "topk_group": 1, "num_nextn_predict_layers": 1, "rope_theta": 1e6,
        "rope_scaling": None, "vocab_size": 128, "max_position_embeddings": 256,
        "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    }
    raw.update(over)
    return raw


def model(seed: int = 0, **over):
    raw = published(**over)
    cfg = LlamaConfig.from_dict(raw)
    params = init_params(jax.random.key(seed), cfg)
    # norms away from 1, a router that spreads its scores and an FFN as large
    # as the residual, so that every leaf matters to the result
    keys = iter(jax.random.split(jax.random.key(seed + 100), 32))
    for stack in params["layers"].values():
        for name in ("input_norm", "post_attn_norm", "q_a_norm", "kv_a_norm"):
            stack[name] = 1.0 + 0.3 * jax.random.normal(next(keys), stack[name].shape)
        for name in ("gate_proj", "up_proj", "down_proj", "kv_b_proj", "q_b_proj"):
            stack[name] = stack[name] * 4.0
        if "router" in stack:
            stack["router"] = stack["router"] * 25.0
    return raw, cfg, params


def rel_l2(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.sum((got - want) ** 2) / np.sum(want**2)))


def tokens(seed: int, shape) -> np.ndarray:
    return np.random.default_rng(seed).integers(3, 128, shape).astype(np.int32)


def expert_layer(params, i=0) -> dict:
    return {name: leaf[i] for name, leaf in params["layers"]["attention"].items()}


def test_published_keys_mean_the_latent_block():
    cfg = LlamaConfig.from_dict(published())
    assert cfg.latent and not cfg.hybrid and cfg.layers_by_kind
    assert cfg.layer_kinds == ("dense", "attention", "attention", "attention")
    assert layer_runs(cfg) == [Run("dense", 0, 1, 0), Run("attention", 0, 3, 1)]
    assert (cfg.num_experts, cfg.held_experts, cfg.first_local_expert) == (16, 8, 8)
    assert (cfg.latent_row_dim, cfg.qk_head_dim, cfg.expert_width, cfg.shared_width) == (24, 20, 16, 16)
    assert cfg.router_aux_loss_coef == 0.0 and cfg.topk_method == "noaux_tc"
    # the published file itself: its one count of experts is the router's width
    whole = published(n_routed_experts=16)
    del whole["num_experts"], whole["first_local_expert"]
    cfg16 = LlamaConfig.from_dict(whole)
    assert (cfg16.num_experts, cfg16.num_local_experts, cfg16.held_experts) == (16, None, 16)
    assert LlamaConfig.from_dict(cfg16.to_dict()) == cfg16
    assert LlamaConfig.from_dict(cfg.to_dict()) == cfg
    shapes = llama.shapes(cfg)["layers"]
    assert set(shapes) == {"dense", "attention"}
    assert shapes["dense"]["gate_proj"].shape == (1, 32, 48) and "router" not in shapes["dense"]
    assert "shared_gate_proj" not in shapes["dense"]  # the dense layer has no shared expert
    assert shapes["attention"]["gate_proj"].shape == (3, 8, 32, 16)  # the held experts only
    assert shapes["attention"]["router"].shape == (3, 32, 16)  # the router whole
    assert shapes["attention"]["router_bias"].shape == (3, 16)
    assert shapes["attention"]["shared_up_proj"].shape == (3, 32, 16)
    for kind in shapes.values():
        assert kind["kv_a_proj"].shape[1:] == (32, 24) and kind["kv_b_proj"].shape[1:] == (16, 4 * 28)
        assert kind["q_b_proj"].shape[1:] == (24, 4 * 20) and kind["o_proj"].shape[1:] == (4 * 16, 32)
        assert "k_proj" not in kind and "v_proj" not in kind
    bias = init_params(jax.random.key(0), cfg)["layers"]["attention"]["router_bias"]
    assert 0.03 < float(jnp.std(bias)) < 0.3  # drawn, not zero
    # the benchmark's configuration at its published widths
    import json
    with open(os.path.join(BENCH, "configs", "glm-4.7-flash.json")) as f:
        real = json.load(f)
    big = LlamaConfig.from_dict(real)
    assert big.num_params() == real["parameters"]["as_run"] == 2_621_048_256
    assert (big.num_experts, big.held_experts, big.latent_row_dim) == (64, 8, 576)
    # a stack of like layers is still one run with its cache to itself
    assert layer_runs(LlamaConfig(num_hidden_layers=3)) == [Run("attention", 0, 3, 0)]


def test_the_latent_projection_alone():
    """q and the cached row of one layer: the q pair with its norm, the
    latent's norm, the rotation on the rotated parts only and over all their
    values, the shared key part."""
    raw, cfg, params = model(seed=1)
    w = expert_layer(params)
    x = jax.random.normal(jax.random.key(2), (2, 19, cfg.hidden_size), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(19), (2, 19))
    q, rows, _ = llama._latent_qkv(cfg, x, w, *llama._rope(cfg, pos))
    want_q, want_rows = reference.latent_rows(x, w, raw)
    assert q.shape == (2, 19, 4, 20) and rows.shape == (2, 19, 24)
    assert rel_l2(q, want_q) < REL_L2 and rel_l2(rows, want_rows) < REL_L2


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_absorbed_against_rebuilt_attention_on_the_same_rows(kernel, monkeypatch):
    """One layer, the same q and rows: the rebuilt form (k and v through
    ``kv_b_proj``, ``xla_attention``) and the absorbed form over a ring that
    holds the rows (XLA path and the kernel, interpreted) give each query
    position's output."""
    from opendiloco_tpu.ops.attention import latent_decode_step_attention, xla_attention
    from opendiloco_tpu.ops.decode_kernels import mla_decode_attention

    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "8")
    raw, cfg, params = model(seed=3)
    w = expert_layer(params, 1)
    n = 21
    x = jax.random.normal(jax.random.key(4), (1, n, cfg.hidden_size), jnp.float32)
    pos = jnp.arange(n)[None]
    q, rows, _ = llama._latent_qkv(cfg, x, w, *llama._rope(cfg, pos))
    rebuilt = xla_attention(q, *llama.latent_keys_values(cfg, rows, w["kv_b_proj"]), causal=True)
    # rows 0..n-2 in slot 2 of a ring, layer 1; the last row arrives with the step
    ring = init_kv_cache(cfg, 3, RING, jnp.float32)["k"]
    assert ring.shape == (4, 3, 1, 24, RING)
    ring = ring.at[1, 2, 0, :, : n - 1].set(rows[0, : n - 1].T)
    lens = jnp.array([0, 0, n - 1], jnp.int32)
    step = mla_decode_attention if kernel == "pallas" else latent_decode_step_attention
    q_last = jnp.zeros((3, 4, 20)).at[2].set(q[0, -1])
    new_rows = jnp.zeros((3, 24)).at[2].set(rows[0, -1])
    o_lat, ring2 = step(
        llama.latent_absorb(cfg, q_last, w["kv_b_proj"]), new_rows, ring, lens, 1,
        scale=cfg.qk_head_dim**-0.5, value_dim=cfg.kv_lora_rank,
    )
    out = llama.latent_expand(cfg, o_lat, w["kv_b_proj"])
    assert rel_l2(out[2], rebuilt[0, -1]) < REL_L2
    np.testing.assert_array_equal(np.asarray(ring2[1, 2, 0, :, n - 1]), np.asarray(rows[0, -1]))


def test_the_router_chooses_under_the_bias_and_weighs_without_it():
    raw, cfg, params = model(seed=5, n_routed_experts=16, first_local_expert=0)
    w = expert_layer(params)
    m = jax.random.normal(jax.random.key(6), (2, 23, cfg.hidden_size), jnp.float32)
    want = reference.router_weights(m, w, raw)  # [B, T, E], 0 where not chosen
    np.testing.assert_allclose(np.asarray(want.sum(-1)), 1.8, rtol=1e-5)  # sum to the scale
    assert int((want > 0).sum(-1).min()) == int((want > 0).sum(-1).max()) == 3
    # the choice changes with the bias for some token, and the weights hold no bias
    unbiased = reference.router_weights(m, {**w, "router_bias": jnp.zeros(16)}, raw)
    moved = np.asarray((want > 0) != (unbiased > 0)).any(-1)
    assert 0 < moved.sum() < moved.size
    score = jax.nn.sigmoid(m @ w["router"])
    ratio = np.asarray(jnp.where(want > 0, want / score, 0.0))  # 1.8 / the chosen scores' sum
    for tok in ratio.reshape(-1, 16):
        np.testing.assert_allclose(tok[tok > 0], tok[tok > 0][0], rtol=1e-5)
    # the program's routed FFN is that routing
    out, _, counts = llama._routed_ffn(cfg, m, w, None)
    assert rel_l2(out, reference.routed_part(m, w, raw)) < REL_L2
    assert int(counts[0]) == 2 * 23 * 3


@pytest.mark.parametrize("remat", [False, True])
def test_forward_logits_against_the_reference(remat):
    raw, cfg, params = model(seed=7)
    ids = tokens(8, (3, 37))
    got = forward(params, ids, cfg, compute_dtype=jnp.float32, remat=remat)
    want = jax.jit(lambda p, i: reference.forward(p, i, raw))(params, ids)
    assert rel_l2(got, want) < REL_L2


def test_train_step_loss_and_gradient_against_the_reference():
    """Through ``InnerTrainer.train_step`` in float32 on the CPU mesh, with
    ``attn_impl`` left to resolve (``xla`` for latent attention): the loss
    (the configuration states no aux loss) and the gradient's norm."""
    raw, cfg, params = model(seed=9)
    tc = TrainerConfig(precision="fp32", remat=False, total_steps=10, warmup_steps=2)
    trainer = InnerTrainer(cfg, tc, build_mesh("NO_SHARD"))
    assert trainer.tc.attn_impl == "xla"
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
    latent projections are sharded, the bias and the norms replicated, and a
    step runs."""
    from jax.sharding import PartitionSpec as P

    from opendiloco_tpu.parallel.sharding import param_specs

    _, cfg, _ = model(seed=9)
    trainer = InnerTrainer(cfg, TrainerConfig(precision="fp32", total_steps=10, warmup_steps=2),
                           build_mesh("FULL_SHARD"))
    specs = param_specs(cfg, trainer.plan)["layers"]
    for kind in ("dense", "attention"):
        for name in ("q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj", "o_proj"):
            assert specs[kind][name] != P(), (kind, name)
        assert specs[kind]["q_a_norm"] == P() and specs[kind]["kv_a_norm"] == P()
    assert specs["attention"]["router_bias"] == P()
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
    the latent ring -> per prompt (the token sequence that was fed, the
    logits rows of its last ``steps + 1`` positions)."""
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


def runs_the_latent_kernel(engine) -> bool:
    vec = jnp.zeros((engine.num_slots,), jnp.int32)
    jaxpr = jax.make_jaxpr(engine._decode)(engine.params, vec, vec, engine.cache_k, None)
    return "odtp_mla_decode_attn" in str(jaxpr)


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_engine_prefill_then_decode_at_every_bucket_edge(kernel, monkeypatch):
    """Prompts of 1, 7, 8 (a bucket's edge), 9 and 16 tokens: prefill in the
    rebuilt form pads each into its bucket and hands the latent rows over,
    then five decode steps in the absorbed form through the ring; and the
    engine's counters."""
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
    assert runs_the_latent_kernel(engine) == (kernel == "pallas")
    # which form the step takes, as ``GET /stats`` carries it: the kernel's tile
    # and the ring rows a slot's step hands back of it (the tile, under 128)
    plan = engine.decode_plan_stats()
    tile = 8 if kernel == "pallas" else 0
    assert plan["decode_plan_mla_block_t"] == plan["decode_plan_mla_rows_written_back"] == tile
    assert plan["decode_grid_steps"] == (4 * 4 * RING // 8 if tile else 0)
    assert plan["mla_ring_rows"] == RING and plan["decode_plan_heads"] == 0
    # the last engine served one prompt of 16 and five steps of one live slot
    assert engine.cache_v is None and engine.cache_k.shape == (4, 4, 1, 24, RING)
    row = 24 * 4  # bytes of a float32 row
    assert engine.latent_cache_resident_bytes == 4 * 4 * RING * row
    read = sum(16 + i + 1 for i in range(steps))  # rows [0, lens] of the live slot
    assert engine.latent_rows_read == 4 * read
    assert engine.latent_bytes_moved == 4 * row * (16 + read + steps)
    live = 16 + steps
    assert engine.moe_pairs_all == live * cfg.num_experts_per_tok * 3  # three expert layers
    assert 0 < engine.moe_pairs < engine.moe_pairs_all  # the held experts' share
    assert engine.ssm_tokens == 0 and engine.ssm_state_resident_bytes == 0


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_decode_across_the_rings_wrap(kernel, monkeypatch):
    """A slot decodes past its ring's 24 rows: the row written at ``lens % T``
    replaces the oldest, and attention slides over the last T tokens. Both
    paths agree with each other to rounding, and, until the wrap, with the
    reference."""
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "8")
    raw, cfg, params = model(seed=14)
    prompt, steps = [tokens(15, 16).tolist()], 14  # positions up to 30 > 24
    seqs, rows = serve(engine_for(cfg, params, decode_kernel=kernel), prompt, steps)
    before_wrap = RING - 16  # steps whose position is still inside the ring
    assert against_reference(raw, params, prompt, seqs, [rows[0][:before_wrap]], before_wrap - 1) < REL_L2
    seqs_x, rows_x = serve(engine_for(cfg, params, decode_kernel="xla"), prompt, steps)
    assert seqs == seqs_x and rel_l2(rows[0], rows_x[0]) < REL_L2
    # past the wrap the full-sequence reference sees tokens the ring dropped
    assert against_reference(raw, params, prompt, seqs, rows, steps) > REL_L2


def test_padding_rows_change_nothing():
    """What a padded prefill hands over for the live rows is what the
    unpadded prompt leaves, whatever the padding holds."""
    _, cfg, params = model(seed=16)
    n, bucket = 11, 16
    prompt = tokens(17, n)
    run = lambda ids, length: prefill_forward(
        params, jnp.asarray(ids[None]), jnp.int32(length), cfg, compute_dtype=jnp.float32)
    logits, rows, none = run(prompt, n)
    assert none is None and rows.shape == (4, n, 24)
    for filler in (0, 77):
        padded = np.full(bucket, filler, np.int32)
        padded[:n] = prompt
        got_logits, got_rows, _ = run(padded, n)
        np.testing.assert_allclose(np.asarray(got_logits), np.asarray(logits), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_rows)[:, :n], np.asarray(rows), rtol=1e-5, atol=1e-6)


def test_slot_reuse_starts_clean():
    """A slot's second tenant decodes as if the slot had never been used: the
    first tenant's rows beyond the new prompt are stale and masked."""
    raw, cfg, params = model(seed=18)
    engine = engine_for(cfg, params)
    serve(engine, [tokens(19, 16).tolist()], 6, slots=[2])
    second = [tokens(20, 5).tolist()]
    seqs, rows = serve(engine, second, 6, slots=[2])
    assert against_reference(raw, params, second, seqs, rows, 6) < REL_L2


def test_batcher_serves_the_latent_block():
    """Through ``ContinuousBatcher``: more requests than slots, so slots are
    reused while others decode; greedy tokens equal the training forward's."""
    _, cfg, params = model(seed=21)
    batcher = ContinuousBatcher(engine_for(cfg, params, num_slots=2)).start()
    try:
        prompts = [tokens(22 + i, n).tolist() for i, n in enumerate((5, 8, 9, 16, 3))]
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


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts that the eight shares compute (2 experts each), with
    the shared expert counted once, are what the uncut layer gives: in the
    program, and against the reference's whole layer."""
    raw, cfg, params = model(seed=23, n_routed_experts=16, first_local_expert=0)
    assert cfg.num_local_experts is None  # every expert held: the uncut layer
    w = expert_layer(params)
    m = jax.random.normal(jax.random.key(24), (2, 19, cfg.hidden_size), jnp.float32)
    whole, _, counts = llama._ffn(cfg, m, w)
    want = reference.routed_part(m, w, raw) + reference.shared_experts(m, w)
    assert rel_l2(whole, want) < REL_L2

    parts, pairs = [], 0
    for first in range(0, 16, 2):
        share = dataclasses.replace(cfg, num_local_experts=2, first_local_expert=first)
        held = {name: (leaf[first : first + 2] if name in ("gate_proj", "up_proj", "down_proj")
                       else leaf) for name, leaf in w.items()}
        out, _, c = llama._routed_ffn(share, m, held, None)
        parts.append(out)
        pairs += int(c[0])
        assert int(c[3]) == 2 * 19 * 3  # the pairs of all experts, on every share
        ref_share = reference.routed_part(
            m, held, {**raw, "n_routed_experts": 2, "first_local_expert": first})
        assert rel_l2(out, ref_share) < REL_L2
    assert pairs == int(counts[0]) == 2 * 19 * 3  # every pair is some share's
    total = sum(parts) + llama._swiglu(m, w, "shared_")
    assert rel_l2(total, whole) < REL_L2 and rel_l2(total, want) < REL_L2


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_the_five_forwards_agree_on_this_block(kernel, monkeypatch):
    """The forwards this block supports -- training, prefill, decode -- give
    one token's logits alike; the two that handle (k, v) rows (verify, draft)
    refuse it (``test_what_cannot_hold_a_latent_row_says_so``)."""
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "8")
    _, cfg, params = model(seed=25)
    ids = tokens(26, (1, 13))
    full = forward(params, ids, cfg, compute_dtype=jnp.float32, remat=False)[0]
    pre = prefill_forward(params, jnp.asarray(ids[:, :12]), jnp.int32(12), cfg,
                          compute_dtype=jnp.float32, return_moe_counts=True)
    logits, rows, none, counts = pre
    assert none is None and counts.shape == (4,)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(full[11]), rtol=2e-4, atol=2e-5)
    ring = init_kv_cache(cfg, 2, RING, jnp.float32)["k"]
    ring = llama.cache_insert(ring, None, rows, None, jnp.int32(1))[0]
    out = decode_forward(
        params, jnp.asarray([0, int(ids[0, 12])], jnp.int32), jnp.asarray([0, 12], jnp.int32),
        ring, None, cfg, compute_dtype=jnp.float32, decode_kernel=kernel, return_moe_counts=True)
    step_logits, ring2, none, counts = out
    assert none is None and ring2.shape == ring.shape
    np.testing.assert_allclose(np.asarray(step_logits[1]), np.asarray(full[12]), rtol=2e-4, atol=2e-5)
    assert int(counts[3]) == 1 * 3 * 3  # one live slot, 3 experts, 3 expert layers


REFUSED = "refused for a configuration with latent attention"


def test_what_cannot_hold_a_latent_row_says_so(tmp_path):
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
        engine.install_slot_pages(0, np.zeros((4, 16, 1, 24)), np.zeros((4, 16, 1, 24)))
    with pytest.raises(ValueError, match=f"continued prefill.*{REFUSED}"):
        llama.chunk_prefill_forward(params, jnp.zeros((1, 2), jnp.int32), 0, 2, 0, engine.cache_k, None, None, cfg)
    with pytest.raises(ValueError, match="pp pipeline is refused for a configuration with a leading dense"):
        pipeline_hidden(params, jnp.zeros((2, 8, 32)), None, cfg, None, microbatches=2, attn_fn=None)
    with pytest.raises(ValueError, match="no latent attention"):
        hf_io.save_params(params, cfg, str(tmp_path))
    for impl in ("pallas", "ring"):
        with pytest.raises(ValueError, match=f"attn_impl='{impl}' is {REFUSED}"):
            forward(params, tokens(29, (1, 8)), cfg, attn_impl=impl)
    with pytest.raises(ValueError, match="group-limited routing is not written"):
        LlamaConfig.from_dict(published(n_group=2))
    with pytest.raises(ValueError, match="needs q_lora_rank"):
        LlamaConfig.from_dict(published(q_lora_rank=0))
    with pytest.raises(ValueError, match="are not among the router's 16"):
        LlamaConfig.from_dict(published(first_local_expert=12))
    # what works unchanged is not refused: the plan's numbers, a weight swap
    assert "decode_plan_heads" in engine.decode_plan_stats()
    engine.install_params(1, params)
    assert engine.weight_binds == 2


FAULTS = ["no_kv_norm", "rope_on_nope", "bias_weighed", "softmax_scores", "no_scale",
          "values_from_tail", "stale_row", "bfloat16", "float8_e4m3fn"]


@pytest.mark.parametrize("fault", FAULTS)
def test_the_tolerance_catches_what_it_must(fault):
    """Each fault moves the compared rows (the last prompt position and five
    decode steps, as the cell's check compares) by far more than ``REL_L2``."""
    raw, cfg, params = model(seed=30)
    prompt, steps = tokens(31, 13).tolist(), 5
    ref = jax.jit(lambda p, i, **kw: reference.forward(p, i, raw, **kw),
                  static_argnames=("operands", "faults"))
    if fault == "stale_row":
        # the previous tenant's row where the new prompt's last row belongs:
        # what a reader sees whose mask is one row too wide
        engine = engine_for(cfg, params)
        engine.admit(1, tokens(32, 16).tolist())
        old = jnp.copy(engine.cache_k[:, 1, 0, :, len(prompt) - 1])
        tok, logits = engine.admit(1, prompt)
        engine.cache_k = engine.cache_k.at[:, 1, 0, :, len(prompt) - 1].set(old)
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
        engine = engine_for(cfg, params)
        seqs, rows = serve(engine, [prompt], steps, slots=[1])
        ids = np.asarray([seqs[0]], np.int32)
        first = len(prompt) - 1
        kw = ({"operands": getattr(jnp, fault)} if fault in ("bfloat16", "float8_e4m3fn")
              else {"faults": (fault,)})
        got = np.asarray(ref(params, ids, **kw))[0, first : first + steps + 1]
        want = np.asarray(ref(params, ids))[0, first : first + steps + 1]
        assert rel_l2(rows[0], want) < REL_L2  # the engine itself is inside
    assert rel_l2(got, want) > 20 * REL_L2, rel_l2(got, want)
