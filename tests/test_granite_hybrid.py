"""The granite-4.0-h hybrid (Mamba-2 mixers with a recurrent state beside the
ring cache, NoPE attention, routed experts beside a shared MLP, one chip's
share of the experts, the four multipliers) through every path of the
program, against the float32 reference written from its equations
(``benchmark/odbench/reference_granite_h.py``: the recurrence token by token,
every held expert on every token, nothing imported from the program). Tiny
sizes, seeded random weights, everything float32 on the CPU.

Tolerances. Program and reference both compute in float32 here and differ in
the order of accumulation only (the chunked scan against the token-by-token
recurrence, grouped matmuls over sorted pairs against every expert on every
token), which measured 1e-7 relative L2 on these sizes; 1e-4 leaves three
orders of magnitude. Anything structural -- a state from another tenant, a
state taken at the bucket's end, a missing ``D`` or ``z`` gate, operands
below float32 -- gives 1e-3 and more (the last tests show it). A flipped
choice between the k-th and (k+1)-th expert needs two router logits within
float32 rounding of each other; the seeds here are fixed and have none.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opendiloco_tpu.models import llama, mamba
from opendiloco_tpu.models.llama import (
    LlamaConfig, Run, forward, init_params, layer_runs, prefill_forward,
)
from opendiloco_tpu.parallel.mesh import build_mesh
from opendiloco_tpu.serve import ContinuousBatcher, ServeEngine
from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from odbench import reference_granite_h as reference  # noqa: E402

REL_L2 = 1e-4
PERIOD = ["mamba", "mamba", "attention", "mamba", "mamba", "mamba", "attention", "mamba"]


def published(**over) -> dict:
    """The published ``config.json``'s keys at a tiny size: the pattern longer
    than the depth (a file cut in depth keeps it whole), 16 experts of which
    this share holds 8, from the 8th on."""
    raw = {
        "model_type": "granitemoehybrid", "hidden_size": 32, "intermediate_size": 16,
        "shared_intermediate_size": 24, "num_hidden_layers": 5, "layer_types": list(PERIOD),
        "num_attention_heads": 4, "num_key_value_heads": 2, "attention_multiplier": 0.125,
        "embedding_multiplier": 12, "residual_multiplier": 0.22, "logits_scaling": 16,
        "position_embedding_type": "nope", "mamba_n_heads": 8, "mamba_d_head": 8,
        "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_n_groups": 1, "mamba_chunk_size": 8,
        "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
        "num_experts": 16, "num_local_experts": 8, "first_local_expert": 8,
        "num_experts_per_tok": 3, "vocab_size": 128, "max_position_embeddings": 256,
        "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
    }
    raw.update(over)
    return raw


def model(seed: int = 0, **over):
    raw = published(**over)
    cfg = LlamaConfig.from_dict(raw)
    params = init_params(jax.random.key(seed), cfg)
    # norms and D away from 1, a router that spreads its logits and an FFN as
    # large as the residual, so that every leaf matters to the result
    keys = iter(jax.random.split(jax.random.key(seed + 100), 32))
    for stack in params["layers"].values():
        for name in ("input_norm", "post_attn_norm", "mixer_norm", "D"):
            if name in stack:
                stack[name] = 1.0 + 0.3 * jax.random.normal(next(keys), stack[name].shape)
        stack["router"] = stack["router"] * 25.0
        for name in ("gate_proj", "up_proj", "down_proj"):
            stack[name] = stack[name] * 4.0
    return raw, cfg, params


def rel_l2(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.sum((got - want) ** 2) / np.sum(want**2)))


def tokens(seed: int, shape) -> np.ndarray:
    return np.random.default_rng(seed).integers(3, 128, shape).astype(np.int32)


def test_published_keys_mean_the_hybrid():
    cfg = LlamaConfig.from_dict(published())
    assert cfg.hybrid and cfg.layer_types == tuple(PERIOD[:5])  # the pattern's leading part
    # each run with its first layer's index in its mixer's state (PR 32): here its start
    assert layer_runs(cfg) == [
        Run("mamba", 0, 2, 0), Run("attention", 0, 1, 0), Run("mamba", 2, 2, 2)]
    assert (cfg.num_mamba_layers, cfg.num_attention_layers) == (4, 1)
    assert cfg.norm_topk_prob and cfg.router_aux_loss_coef == 0.0
    assert (cfg.num_experts, cfg.held_experts, cfg.first_local_expert) == (16, 8, 8)
    # the published file itself: its one count of experts is the router's width
    whole = published(num_local_experts=16)
    del whole["num_experts"], whole["first_local_expert"]
    cfg = LlamaConfig.from_dict(whole)
    assert (cfg.num_experts, cfg.num_local_experts, cfg.held_experts) == (16, None, 16)
    assert LlamaConfig.from_dict(cfg.to_dict()) == cfg
    # a stack of attention layers has one run and no recurrent state
    dense = LlamaConfig(num_hidden_layers=3)
    assert not dense.hybrid and layer_runs(dense) == [Run("attention", 0, 3, 0)]
    shapes = llama.shapes(LlamaConfig.from_dict(published()))["layers"]
    assert shapes["mamba"]["in_proj"].shape == (4, 32, 2 * 64 + 2 * 16 + 8)
    assert shapes["mamba"]["gate_proj"].shape == (4, 8, 32, 16)  # the held experts only
    assert shapes["attention"]["router"].shape == (1, 32, 16)  # the router whole


@pytest.mark.parametrize("length", [1, 255, 256, 257, 700])
def test_mixer_chunked_against_the_sequential_recurrence(length):
    """One mixer at the published chunk of 256: the chunked form, the
    program's own one-step form token by token, and the reference's scan give
    one output, one final state and one conv tail."""
    raw, cfg, params = model(seed=1, mamba_chunk_size=256)
    w = {name: leaf[1] for name, leaf in params["layers"]["mamba"].items()}
    x = jax.random.normal(jax.random.key(length), (2, length, cfg.hidden_size), jnp.float32)
    out, state, tail = jax.jit(lambda x: mamba.ssm_chunked(cfg, x, w))(x)
    want, want_state = jax.jit(lambda x: reference.mamba_mixer(x, w, raw))(x)
    assert rel_l2(out, want) < REL_L2 and rel_l2(state, want_state) < REL_L2

    def step(carry, x_t):
        y, s, t = mamba.ssm_step(cfg, x_t, w, *carry)
        return (s, t), y

    zero = (jnp.zeros_like(state), jnp.zeros_like(tail))
    (state_1, tail_1), out_1 = jax.jit(lambda x: jax.lax.scan(step, zero, jnp.moveaxis(x, 1, 0)))(x)
    assert rel_l2(jnp.moveaxis(out_1, 0, 1), want) < REL_L2
    assert rel_l2(state_1, want_state) < REL_L2
    np.testing.assert_allclose(np.asarray(tail_1), np.asarray(tail), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("remat", [False, True])
def test_forward_logits_against_the_reference(remat):
    raw, cfg, params = model(seed=2)
    ids = tokens(3, (3, 37))
    got = forward(params, ids, cfg, compute_dtype=jnp.float32, remat=remat)
    want = jax.jit(lambda p, i: reference.forward(p, i, raw))(params, ids)
    assert rel_l2(got, want) < REL_L2


def test_train_step_loss_and_gradient_against_the_reference():
    """Through ``InnerTrainer.train_step`` in float32 on the CPU mesh: the
    loss (the configuration states no aux loss) and the gradient's norm."""
    raw, cfg, params = model(seed=4)
    tc = TrainerConfig(precision="fp32", remat=False, attn_impl="xla",
                       total_steps=10, warmup_steps=2)
    trainer = InnerTrainer(cfg, tc, build_mesh("NO_SHARD"))
    state = trainer.init_state(jax.random.key(0))
    state["params"] = jax.device_put(  # a copy: the step donates its state
        jax.tree.map(jnp.copy, params), jax.tree.map(lambda x: x.sharding, state["params"]))
    ids = tokens(5, (8, 32))
    _, m = trainer.train_step(state, trainer.shard_batch(ids, ids.copy(), accum=1))
    want_loss, want_norm = jax.jit(
        lambda p, i: reference.loss_and_grad_norm(p, i, i, raw)
    )(params, ids)
    np.testing.assert_allclose(float(m["loss"]), float(want_loss), rtol=1e-5)
    np.testing.assert_allclose(float(m["grad_norm"]), float(want_norm), rtol=1e-4)


def test_fsdp_sees_the_new_leaves():
    """FULL_SHARD over the 8-device CPU mesh: every leaf has a spec, the
    mixer's matrices are sharded, and a step runs."""
    from jax.sharding import PartitionSpec as P

    from opendiloco_tpu.parallel.sharding import param_specs

    _, cfg, _ = model(seed=4)
    trainer = InnerTrainer(cfg, TrainerConfig(precision="fp32", attn_impl="xla", total_steps=10,
                                              warmup_steps=2), build_mesh("FULL_SHARD"))
    specs = param_specs(cfg, trainer.plan)["layers"]["mamba"]
    assert specs["in_proj"] != P() and specs["out_proj"] != P() and specs["A_log"] == P()
    state = trainer.init_state(jax.random.key(0))
    ids = tokens(6, (8, 16))
    _, m = trainer.train_step(state, trainer.shard_batch(ids, ids.copy(), accum=1))
    assert np.isfinite(float(m["loss"]))


def engine_for(cfg, params, **kw):
    kw = {"num_slots": 4, "max_context": 128, "prefill_buckets": (16, 32),
          "compute_dtype": jnp.float32, "decode_kernel": "xla", **kw}
    return ServeEngine(cfg, params, **kw)


def serve(engine, prompts, steps, slots=None):
    """Prefill each prompt into a slot, then ``steps`` decode steps through
    state and cache -> per prompt (the token sequence that was fed, the
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
        nxt, logits = engine.decode_step(toks, lens)
        logits = np.asarray(logits)
        for i, slot in enumerate(slots):
            rows[i].append(logits[slot])
            toks[slot] = nxt[slot]
            lens[slot] += 1
            if step < steps - 1:
                seqs[i].append(int(nxt[slot]))
    return seqs, [np.stack(r) for r in rows]


def against_reference(raw, params, prompts, seqs, rows, steps) -> float:
    ref = jax.jit(lambda p, i: reference.forward(p, i, raw))
    worst = 0.0
    for prompt, seq, got in zip(prompts, seqs, rows):
        want = np.asarray(ref(params, np.asarray([seq], np.int32)))[0]
        first = len(prompt) - 1
        worst = max(worst, rel_l2(got, want[first : first + steps + 1]))
    return worst


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_engine_prefill_then_decode_at_every_bucket_edge(kernel):
    """Prompts of 1, 15, 16 (a bucket's edge), 17 and 32 tokens: prefill pads
    each into its bucket, the state is handed over at the true length, then
    five decode steps through state and cache; and the engine's counters."""
    raw, cfg, params = model(seed=6)
    steps = 5
    lengths = [1, 15, 16, 17, 32]
    worst, engine = 0.0, None
    for group in (lengths[:4], lengths[4:]):
        engine = engine_for(cfg, params, decode_kernel=kernel)
        prompts = [tokens(7 + n, n).tolist() for n in group]
        seqs, rows = serve(engine, prompts, steps)
        worst = max(worst, against_reference(raw, params, prompts, seqs, rows, steps))
    assert worst < REL_L2
    # the last engine served one prompt of 32 and five steps of one live slot
    assert engine.ssm_tokens == 32 + steps
    state_bytes = cfg.num_mamba_layers * (8 * 8 * 16 * 4 + 3 * (64 + 32) * 4)
    assert engine.ssm_state_resident_bytes == engine.num_slots * state_bytes
    assert engine.ssm_state_bytes_moved == state_bytes + steps * 2 * engine.ssm_state_resident_bytes
    live = 32 + steps
    assert engine.moe_pairs_all == live * cfg.num_experts_per_tok * cfg.num_hidden_layers
    assert 0 < engine.moe_pairs < engine.moe_pairs_all  # the held experts' share
    assert engine.cache_k.shape[0] == 1  # the ring holds the attention layer alone


def test_the_state_is_the_true_lengths_under_padding():
    """What a padded prefill hands over is what the unpadded prompt leaves,
    whatever the padding holds."""
    _, cfg, params = model(seed=8)
    n, bucket = 11, 32
    prompt = tokens(9, n)
    run = lambda ids, length: prefill_forward(
        params, jnp.asarray(ids[None]), jnp.int32(length), cfg, compute_dtype=jnp.float32)
    bare = run(prompt, n)
    for filler in (0, 77):
        padded = np.full(bucket, filler, np.int32)
        padded[:n] = prompt
        got = run(padded, n)
        for have, want in zip(got, bare):  # logits, k, v, states, tails
            np.testing.assert_allclose(
                np.asarray(have)[..., :n, :, :] if have.ndim == 4 and have.shape[1] == bucket
                else np.asarray(have),
                np.asarray(want), rtol=1e-5, atol=1e-6)


def test_slot_reuse_starts_clean():
    """A slot's second tenant decodes as if the slot had never been used."""
    raw, cfg, params = model(seed=10)
    engine = engine_for(cfg, params)
    serve(engine, [tokens(11, 30).tolist()], 6, slots=[2])
    second = [tokens(12, 9).tolist()]
    seqs, rows = serve(engine, second, 6, slots=[2])
    assert against_reference(raw, params, second, seqs, rows, 6) < REL_L2


def test_batcher_serves_the_hybrid():
    """Through ``ContinuousBatcher``: more requests than slots, so slots are
    reused while others decode; greedy tokens equal the training forward's."""
    _, cfg, params = model(seed=13)
    batcher = ContinuousBatcher(engine_for(cfg, params, num_slots=2)).start()
    try:
        prompts = [tokens(20 + i, n).tolist() for i, n in enumerate((5, 16, 17, 30, 9))]
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
    """The routed parts that the shares compute (4 shares of 4 experts), with
    the shared MLP counted once, are what the uncut layer gives: in the
    program, and against the reference's whole layer."""
    raw, cfg, params = model(seed=14, num_local_experts=16, first_local_expert=0)
    assert cfg.num_local_experts is None  # every expert held: the uncut layer
    w = {name: leaf[0] for name, leaf in params["layers"]["mamba"].items()}
    m = jax.random.normal(jax.random.key(15), (2, 19, cfg.hidden_size), jnp.float32)
    whole, _, counts = llama._ffn(cfg, m, w)
    want = reference.routed_part(m, w, raw) + reference.shared_mlp(m, w)
    assert rel_l2(whole, want) < REL_L2

    parts, pairs = [], 0
    for first in range(0, 16, 4):
        share = dataclasses.replace(cfg, num_local_experts=4, first_local_expert=first)
        held = {name: (leaf[first : first + 4] if name in ("gate_proj", "up_proj", "down_proj")
                       else leaf) for name, leaf in w.items()}
        out, _, c = llama._routed_ffn(share, m, held, None)
        parts.append(out)
        pairs += int(c[0])
        assert int(c[3]) == 2 * 19 * 3  # the pairs of all experts, on every share
        ref_share = reference.routed_part(
            m, held, {**raw, "num_local_experts": 4, "first_local_expert": first})
        assert rel_l2(out, ref_share) < REL_L2
    assert pairs == int(counts[0]) == 2 * 19 * 3  # every pair is some share's
    total = sum(parts) + llama._swiglu(m, w, "shared_")
    assert rel_l2(total, whole) < REL_L2 and rel_l2(total, want) < REL_L2


REFUSED = "refused for a configuration with Mamba-2 layers"


def test_what_cannot_hold_a_recurrent_state_says_so(tmp_path):
    from opendiloco_tpu.models import hf_io
    from opendiloco_tpu.parallel.pipeline import pipeline_hidden
    from opendiloco_tpu.serve.kvcache import HostKVTier

    _, cfg, params = model(seed=16)
    engine = engine_for(cfg, params)
    with pytest.raises(ValueError, match=f"prefix_cache is {REFUSED}"):
        ContinuousBatcher(engine, prefix_cache=True)
    with pytest.raises(ValueError, match=f"kv_tier is {REFUSED}"):
        ContinuousBatcher(engine, kv_tier=HostKVTier(host_slots=2))
    engine.admit(0, tokens(17, 12).tolist())
    with pytest.raises(ValueError, match=f"prefix reuse.*{REFUSED}"):
        engine.admit(1, tokens(17, 20).tolist(), prefix_src=0, prefix_len=8)
    with pytest.raises(ValueError, match=f"page-out is {REFUSED}"):
        engine.fetch_slot_pages(0, 12)
    with pytest.raises(ValueError, match=f"page-in is {REFUSED}"):
        engine.install_slot_pages(0, np.zeros((1, 16, 2, 8)), np.zeros((1, 16, 2, 8)))
    with pytest.raises(ValueError, match=f"continued prefill.*{REFUSED}"):
        llama.chunk_prefill_forward(params, jnp.zeros((1, 2), jnp.int32), 0, 2, 0, engine.cache_k,
                                    engine.cache_v, None, cfg)
    with pytest.raises(ValueError, match=f"pp pipeline is {REFUSED}"):
        pipeline_hidden(params, jnp.zeros((2, 8, 32)), None, cfg, None, microbatches=2,
                        attn_fn=None)
    with pytest.raises(ValueError, match="no Mamba-2 mixer"):
        hf_io.save_params(params, cfg, str(tmp_path))
    with pytest.raises(ValueError, match="mamba_n_groups 1"):
        LlamaConfig.from_dict(published(mamba_n_groups=2))
    with pytest.raises(ValueError, match="are not among the router's 16"):
        LlamaConfig.from_dict(published(first_local_expert=12))


@pytest.mark.parametrize(
    "fault", ["stale_state", "state_at_bucket_end", "no_D", "no_z_gate", "bfloat16", "float8_e4m3fn"])
def test_the_tolerance_catches_what_it_must(fault):
    """Each fault moves the compared rows (the last prompt position and five
    decode steps, as the cell's check compares) by far more than ``REL_L2``."""
    raw, cfg, params = model(seed=18)
    prompt, steps = tokens(19, 21).tolist(), 5
    engine = engine_for(cfg, params)
    if fault in ("stale_state", "state_at_bucket_end"):
        if fault == "stale_state":  # the previous tenant's state under the new prompt
            engine.admit(1, tokens(20, 30).tolist())
            stale = tuple(jnp.copy(x) for x in engine._ssm)
        else:  # what the mixers hold after the bucket's 32 rows, padding included
            ids = np.zeros((1, 32), np.int32)
            ids[0, : len(prompt)] = prompt
            _, _, _, states, tails = prefill_forward(
                params, jnp.asarray(ids), jnp.int32(32), cfg, compute_dtype=jnp.float32)
        tok, logits = engine.admit(1, prompt)
        if fault == "stale_state":
            engine._ssm = stale
        else:
            engine._ssm = engine._state_insert(*engine._ssm, states, tails, jnp.int32(1))
        toks, lens = np.zeros(4, np.int32), np.zeros(4, np.int32)
        toks[1], lens[1] = tok, len(prompt)
        seq, rows = list(prompt) + [tok], [np.asarray(logits)]
        for step in range(steps):
            nxt, logits = engine.decode_step(toks, lens)
            rows.append(np.asarray(logits)[1])
            toks[1], lens[1] = nxt[1], lens[1] + 1
            if step < steps - 1:
                seq.append(int(nxt[1]))
        got = against_reference(raw, params, [prompt], [seq], [np.stack(rows)], steps)
    else:
        seqs, rows = serve(engine, [prompt], steps)
        kw = {"faults": (fault,)} if fault.startswith("no_") else {"operands": getattr(jnp, fault)}
        ref = jax.jit(lambda p, i: reference.forward(p, i, raw, **kw))
        want = np.asarray(ref(params, np.asarray([seqs[0]], np.int32)))[0]
        got = rel_l2(rows[0], want[len(prompt) - 1 : len(prompt) + steps])
    assert got > 10 * REL_L2
