"""The OLMoE block (dropless top-k routed FFN, QK-norm) through every path of
the program, against the float32 reference written from its equations
(``benchmark/odbench/reference_olmoe.py``: dense over the experts, nothing
imported from the program). Tiny OLMoE: 8 experts, 2 and 8 per token,
seeded random weights, everything float32 on the CPU.

Tolerances. Program and reference both compute in float32 here and differ
in the order of accumulation only (grouped matmuls over sorted pairs against
every expert on every token), which measured 1e-6 relative L2 on these
sizes; 1e-4 leaves two orders of magnitude, and anything structural -- a
missing expert, renormalised weights, QK-norm left out or applied per head,
a different expert at the k-th place -- gives 1e-2 and more (the last test
shows it). A flipped choice between the k-th and (k+1)-th expert needs two
router probabilities within float32 rounding of each other; the seeds here
are fixed and have none.
"""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opendiloco_tpu.models import llama
from opendiloco_tpu.models.llama import LlamaConfig, chunk_prefill_forward, forward, init_params
from opendiloco_tpu.parallel.mesh import build_mesh
from opendiloco_tpu.serve import ServeEngine
from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from odbench import reference_olmoe  # noqa: E402

REL_L2 = 1e-4


def published(top_k: int) -> dict:
    """The published ``config.json``'s keys at a tiny size."""
    return {
        "model_type": "olmoe", "hidden_size": 64, "intermediate_size": 32,
        "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 4,
        "num_experts": 8, "num_experts_per_tok": top_k, "norm_topk_prob": False,
        "vocab_size": 256, "max_position_embeddings": 128, "rms_norm_eps": 1e-5,
        "rope_theta": 10000, "tie_word_embeddings": False, "router_aux_loss_coef": 0.01,
    }


def model(top_k: int, seed: int = 0):
    raw = published(top_k)
    cfg = LlamaConfig.from_dict(raw)
    params = init_params(jax.random.key(seed), cfg)
    # norms away from 1 and a router that spreads its probabilities, so that
    # every weight and every place of the top-k matters to the result
    keys = iter(jax.random.split(jax.random.key(seed + 100), 8))
    for name in ("input_norm", "post_attn_norm", "q_norm", "k_norm"):
        shape = params["layers"][name].shape
        params["layers"][name] = 1.0 + 0.3 * jax.random.normal(next(keys), shape)
    params["layers"]["router"] = params["layers"]["router"] * 25.0
    for name in ("gate_proj", "up_proj", "down_proj"):  # an FFN as large as the residual
        params["layers"][name] = params["layers"][name] * 4.0
    raw["router_z_loss_coef"] = cfg.router_z_loss_coef
    return raw, cfg, params


def rel_l2(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.sqrt(np.sum((got - want) ** 2) / np.sum(want**2)))


def tokens(seed: int, shape) -> np.ndarray:
    return np.random.default_rng(seed).integers(3, 256, shape).astype(np.int32)


def test_published_keys_mean_olmoe():
    cfg = LlamaConfig.from_dict(published(8))
    assert cfg.qk_norm and cfg.num_experts == 8 and cfg.num_experts_per_tok == 8
    assert cfg.router_aux_loss_coef == 0.01 and cfg.router_z_loss_coef == 0.001
    shapes = llama.shapes(cfg)["layers"]
    assert shapes["q_norm"].shape == (2, 64) and shapes["gate_proj"].shape == (2, 8, 64, 32)
    # a llama config.json still means what it meant
    dense = LlamaConfig.from_dict({**published(8), "model_type": "llama", "num_experts": 0})
    assert not dense.qk_norm and "q_norm" not in llama.shapes(dense)["layers"]
    # and the configuration survives its own dictionary
    assert LlamaConfig.from_dict(cfg.to_dict()) == cfg


@pytest.mark.parametrize("top_k", [2, 8])
def test_forward_logits_against_the_reference(top_k):
    raw, cfg, params = model(top_k)
    ids = tokens(1, (2, 40))
    got = forward(params, ids, cfg, compute_dtype=jnp.float32, remat=False)
    want = jax.jit(lambda p, i: reference_olmoe.forward(p, i, raw))(params, ids)
    assert rel_l2(got, want) < REL_L2


@pytest.mark.parametrize("top_k", [2, 8])
def test_train_step_loss_aux_and_gradient_against_the_reference(top_k):
    """Through ``InnerTrainer.train_step`` in float32: the loss with both aux
    terms under their coefficients, and the gradient's norm; then the aux
    terms alone, as ``forward`` hands them to the trainer."""
    raw, cfg, params = model(top_k, seed=2)
    tc = TrainerConfig(precision="fp32", remat=False, attn_impl="xla",
                       total_steps=10, warmup_steps=2)
    trainer = InnerTrainer(cfg, tc, build_mesh("NO_SHARD"))
    state = trainer.init_state(jax.random.key(0))
    state["params"] = jax.device_put(  # a copy: the step donates its state
        jax.tree.map(jnp.copy, params), jax.tree.map(lambda x: x.sharding, state["params"]))
    ids = tokens(3, (8, 32))
    _, m = trainer.train_step(state, trainer.shard_batch(ids, ids.copy(), accum=1))
    want_loss, want_norm = jax.jit(
        lambda p, i: reference_olmoe.loss_and_grad_norm(p, i, i, raw)
    )(params, ids)
    np.testing.assert_allclose(float(m["loss"]), float(want_loss), rtol=1e-5)
    np.testing.assert_allclose(float(m["grad_norm"]), float(want_norm), rtol=1e-4)

    _, balance, z = jax.jit(lambda p, i: reference_olmoe.loss_terms(p, i, i, raw))(params, ids)
    _, aux = forward(params, ids, cfg, compute_dtype=jnp.float32, remat=False, return_moe_aux=True)
    np.testing.assert_allclose(float(aux), 0.01 * float(balance) + 0.001 * float(z), rtol=1e-5)
    # the two terms apart: each coefficient alone
    for changes, want in (({"router_z_loss_coef": 0.0}, 0.01 * float(balance)),
                          ({"router_aux_loss_coef": 0.0}, 0.001 * float(z))):
        _, one = forward(params, ids, dataclasses.replace(cfg, **changes),
                         compute_dtype=jnp.float32, remat=False, return_moe_aux=True)
        np.testing.assert_allclose(float(one), want, rtol=1e-5)
    assert float(balance) >= top_k - 1e-4  # E * sum f P is k at perfect balance, more otherwise


def serve(cfg, params, prompts, steps):
    """Prefill each prompt into a slot, then ``steps`` decode steps through
    the cache -> per prompt (the token sequence that was fed, the logits rows
    of its last ``steps + 1`` positions)."""
    engine = ServeEngine(cfg, params, num_slots=4, max_context=64, prefill_buckets=(16, 32),
                         compute_dtype=jnp.float32, decode_kernel="xla")
    toks, lens = np.zeros(4, np.int32), np.zeros(4, np.int32)
    seqs, rows = [], []
    for slot, prompt in enumerate(prompts):
        tok, logits = engine.admit(slot, prompt)
        toks[slot], lens[slot] = tok, len(prompt)
        seqs.append(list(prompt) + [tok])
        rows.append([np.asarray(logits)])
    for step in range(steps):
        nxt, logits = engine.decode_step(toks, lens)
        logits = np.asarray(logits)
        for slot in range(len(prompts)):
            rows[slot].append(logits[slot])
            toks[slot] = nxt[slot]
            lens[slot] += 1
            if step < steps - 1:
                seqs[slot].append(int(nxt[slot]))
    return engine, seqs, [np.stack(r) for r in rows]


@pytest.mark.parametrize("top_k", [2, 8])
def test_engine_prefill_then_decode_against_the_reference(top_k):
    raw, cfg, params = model(top_k, seed=4)
    prompts = [tokens(5, 21).tolist(), tokens(6, 9).tolist()]
    steps = 5
    engine, seqs, rows = serve(cfg, params, prompts, steps)
    ref = jax.jit(lambda p, i: reference_olmoe.forward(p, i, raw))
    for prompt, seq, got in zip(prompts, seqs, rows):
        want = np.asarray(ref(params, np.asarray([seq], np.int32)))[0]
        first = len(prompt) - 1
        assert rel_l2(got, want[first : first + steps + 1]) < REL_L2
    # the counters: every live token routed to k experts in each of 2 layers
    live = sum(len(p) for p in prompts) + steps * len(prompts)
    assert engine.moe_pairs == live * top_k * cfg.num_hidden_layers
    calls = len(prompts) + steps
    assert 0 < engine.moe_experts_hit <= calls * cfg.num_hidden_layers * cfg.num_experts
    assert engine.moe_pairs / cfg.num_experts <= engine.moe_max_pairs <= engine.moe_pairs


@pytest.mark.parametrize("top_k", [2, 8])
def test_continue_prefill_logits_against_forward(top_k):
    """A suffix run over a cached prefix gives the rows of the full
    forward, through the engine's own prefix-reuse admission too: its first
    token is the forward's greedy one at the prompt's end."""
    _, cfg, params = model(top_k, seed=7)
    prompt, tail = tokens(8, 12), tokens(9, (1, 4))
    engine = ServeEngine(cfg, params, num_slots=2, max_context=32, prefill_buckets=(16,),
                         compute_dtype=jnp.float32, decode_kernel="xla")
    engine.admit(0, prompt.tolist())
    ids = np.concatenate([prompt[None], tail], axis=1)
    want = forward(params, ids, cfg, compute_dtype=jnp.float32, remat=False)
    for count in range(1, tail.shape[1] + 1):  # the row of each of the tail's positions
        got, *_ = chunk_prefill_forward(
            engine.params, jnp.asarray(tail), len(prompt), count, 0, engine.cache_k, engine.cache_v,
            None, cfg, compute_dtype=jnp.float32)
        assert rel_l2(got[0], want[0, len(prompt) + count - 1]) < REL_L2
    tok, row = engine.admit(1, ids[0].tolist(), prefix_src=0, prefix_len=len(prompt))
    assert rel_l2(row, want[0, -1]) < REL_L2 and tok == int(np.argmax(want[0, -1]))


def test_top_1_is_the_old_top_1_gate():
    """k = 1: the argmax expert under its own probability, nothing dropped."""
    _, cfg, params = model(1, seed=10)
    layer = jax.tree.map(lambda x: x[0], params["layers"])
    x = jax.random.normal(jax.random.key(11), (3, 17, 64))
    got, _, counts = llama._routed_ffn(cfg, x, layer, None)
    probs = jax.nn.softmax(x @ layer["router"], axis=-1)
    best = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, best[..., None], axis=-1)
    every = jnp.einsum(
        "btef,efd->bted",
        jax.nn.silu(jnp.einsum("btd,edf->btef", x, layer["gate_proj"]))
        * jnp.einsum("btd,edf->btef", x, layer["up_proj"]),
        layer["down_proj"],
    )
    want = gate * jnp.take_along_axis(every, best[..., None, None], axis=2)[:, :, 0]
    assert rel_l2(got, want) < 1e-5
    assert int(counts[0]) == 3 * 17 and int(counts[2]) == int(jnp.max(jnp.bincount(best.ravel())))


@pytest.mark.parametrize("top_k", [2, 8])
def test_a_router_that_ties_routes_the_same_in_forward_and_in_decode(top_k):
    """A router of zeros ties every expert on every token: ``top_k`` then
    takes the lowest indices, in the full forward and through the cache
    alike, so the two agree; the experts differ, so they would not if one
    path had broken the tie another way."""
    _, cfg, params = model(top_k, seed=12)
    params["layers"]["router"] = jnp.zeros_like(params["layers"]["router"])
    prompts = [tokens(13, 14).tolist()]
    _, seqs, rows = serve(cfg, params, prompts, 4)
    want = forward(params, np.asarray(seqs, np.int32), cfg, compute_dtype=jnp.float32, remat=False)
    assert rel_l2(rows[0], np.asarray(want)[0, 13:18]) < REL_L2
    if top_k < cfg.num_experts:  # the last experts were never chosen
        other = jax.tree.map(lambda x: x, params)
        other["layers"]["down_proj"] = other["layers"]["down_proj"].at[:, top_k:].set(0.0)
        same = forward(other, np.asarray(seqs, np.int32), cfg, compute_dtype=jnp.float32, remat=False)
        np.testing.assert_array_equal(np.asarray(same), np.asarray(want))


@pytest.mark.parametrize("fault", ["renormalised", "no_qk_norm", "expert_missing", "one_expert_more"])
def test_the_tolerance_catches_what_it_must(fault):
    """Each structural fault moves the logits by far more than ``REL_L2``."""
    raw, cfg, params = model(2, seed=14)
    ids = tokens(15, (2, 40))
    want = jax.jit(lambda p, i: reference_olmoe.forward(p, i, raw))(params, ids)
    bad_cfg, bad = cfg, jax.tree.map(lambda x: x, params)
    if fault == "renormalised":
        bad_cfg = dataclasses.replace(cfg, norm_topk_prob=True)
    elif fault == "no_qk_norm":
        bad["layers"]["q_norm"] = jnp.ones_like(bad["layers"]["q_norm"])
    elif fault == "expert_missing":
        bad["layers"]["down_proj"] = bad["layers"]["down_proj"].at[:, 3].set(0.0)
    else:  # the (k+1)-th expert computed too
        bad_cfg = dataclasses.replace(cfg, num_experts_per_tok=3)
    got = forward(bad, ids, bad_cfg, compute_dtype=jnp.float32, remat=False)
    assert rel_l2(got, want) > 100 * REL_L2
