"""Mixture-of-Experts (dropless top-k routing) + expert parallelism over
the ep axis.

The reference's zoo is dense-only (SURVEY §2.4: no EP); one oracle for the
routed FFN is the dense model: a single-expert MoE IS the dense network
(router softmax over one logit = 1.0). The float32 reference of the OLMoE
block is the other (tests/test_olmoe.py)."""

import jax
import jax.numpy as jnp
import numpy as np

from opendiloco_tpu.models.llama import (
    LlamaConfig,
    forward,
    init_params,
)
from opendiloco_tpu.parallel.mesh import build_mesh
from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig


def _cfg(num_experts=0, layers=2):
    return LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=layers, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=64,
        num_experts=num_experts,
    )


def test_single_expert_equals_dense():
    """E=1: the MoE forward is exactly the dense forward with the same
    weights."""
    dense_cfg = _cfg(0)
    moe_cfg = _cfg(1)
    dense = init_params(jax.random.key(0), dense_cfg)
    moe = init_params(jax.random.key(0), moe_cfg)
    # graft the dense FFN weights into the single expert
    for k in ("gate_proj", "up_proj", "down_proj"):
        moe["layers"][k] = dense["layers"][k][:, None]
    for k in ("input_norm", "post_attn_norm", "q_proj", "k_proj", "v_proj", "o_proj"):
        moe["layers"][k] = dense["layers"][k]
    moe["embed_tokens"] = dense["embed_tokens"]
    moe["final_norm"] = dense["final_norm"]
    moe["lm_head"] = dense["lm_head"]

    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (2, 32)), jnp.int32
    )
    ref = forward(dense, ids, dense_cfg, compute_dtype=jnp.float32, remat=False)
    got, aux = forward(
        moe, ids, moe_cfg, compute_dtype=jnp.float32, remat=False,
        return_moe_aux=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5)
    # load balance E * f * P = 1 under its coefficient; no z-loss by default
    np.testing.assert_allclose(float(aux), moe_cfg.router_aux_loss_coef, atol=1e-7)


def test_moe_trains_on_ep_mesh():
    """E=4 experts sharded over ep=4: training steps run, the loss is
    finite and decreases, and the expert leaves actually carry the ep axis."""
    cfg = _cfg(4)
    plan = build_mesh("NO_SHARD", ep_size=4)
    from opendiloco_tpu.parallel.sharding import param_specs

    specs = param_specs(cfg, plan)
    assert specs["layers"]["gate_proj"][1] == "ep"
    assert specs["layers"]["down_proj"][1] == "ep"

    tc = TrainerConfig(
        lr=3e-3, warmup_steps=2, total_steps=50, precision="fp32", remat=False
    )
    trainer = InnerTrainer(cfg, tc, plan)
    state = trainer.init_state(jax.random.key(1))
    rng = np.random.default_rng(0)
    losses = []
    for step in range(6):
        starts = rng.integers(0, 256, (8, 1))
        ids = ((starts + np.arange(32)) % 256).astype(np.int32)
        state, m = trainer.train_step(
            state, trainer.shard_batch(ids, ids.copy(), accum=1)
        )
        losses.append(float(m["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0]  # learns the sequential structure


def test_moe_pp_loss_matches_sequential():
    """MoE composes with pipeline parallelism: the router aux rides the
    pipeline's per-stage accumulators (parallel/pipeline.py). With
    microbatches=1 the total loss (xent + aux) is exactly the unpipelined
    value; with M>1 EVERY router batch statistic becomes microbatch-local
    (standard GPipe semantics), so the aux matches the mean of
    per-microbatch unpipelined forwards rather than the joint-batch one."""
    cfg = _cfg(4)
    ids = np.random.default_rng(2).integers(
        0, cfg.vocab_size, (8, 32), dtype=np.int32
    )

    def one_loss(pp, mb, ep=1):
        plan = build_mesh("NO_SHARD", pp_size=pp, ep_size=ep)
        tc = TrainerConfig(
            precision="fp32", remat=False, total_steps=10, warmup_steps=2,
            attn_impl="xla", pp_microbatches=mb,
        )
        trainer = InnerTrainer(cfg, tc, plan)
        state = trainer.init_state(jax.random.key(11))
        batch = trainer.shard_batch(ids, ids.copy(), accum=1)
        _, m = trainer.train_step(state, batch)
        return float(m["loss"])

    ref = one_loss(pp=1, mb=1)
    # microbatches=1: per-batch router statistics identical -> exact
    np.testing.assert_allclose(one_loss(pp=2, mb=1), ref, atol=2e-5)

    # microbatched pp x ep: each microbatch's router statistics are its
    # own, so the oracle is the mean over per-microbatch UNPIPELINED
    # forwards. Building both terms from halves also pins the aux
    # normalization (/L/M, not /L)
    from opendiloco_tpu.models.llama import causal_lm_loss

    tc = TrainerConfig(
        precision="fp32", remat=False, total_steps=10, warmup_steps=2,
        attn_impl="xla",
    )
    trainer = InnerTrainer(cfg, tc, build_mesh("NO_SHARD"))
    params = jax.device_get(trainer.init_state(jax.random.key(11))["params"])
    jids = jnp.asarray(ids)
    xents, auxs = [], []
    for mb_ids in (jids[:4], jids[4:]):
        logits, aux = forward(
            params, mb_ids, cfg, compute_dtype=jnp.float32, remat=False,
            return_moe_aux=True,
        )
        xents.append(float(causal_lm_loss(logits, mb_ids)))
        auxs.append(float(aux))
    ref2 = float(np.mean(xents)) + float(np.mean(auxs))
    np.testing.assert_allclose(one_loss(pp=2, mb=2, ep=2), ref2, atol=1e-4)


def test_moe_fused_loss_matches_standard():
    """fused lm-head+xent composes with MoE: the router aux loss rides
    return_hidden (models/llama.py:forward) and is added after the fused
    xent, so the total loss (and one train step) must match the standard
    path to numerical tolerance."""
    cfg = _cfg(4)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 32), dtype=np.int32
    )

    def one_step(fused):
        tc = TrainerConfig(
            precision="fp32", remat=False, total_steps=10, warmup_steps=2,
            attn_impl="xla", fused_loss=fused,
        )
        trainer = InnerTrainer(cfg, tc, build_mesh("NO_SHARD"))
        state = trainer.init_state(jax.random.key(5))
        batch = trainer.shard_batch(ids, ids.copy(), accum=1)
        state, m = trainer.train_step(state, batch)
        return float(m["loss"]), jax.device_get(state["params"])

    loss_std, p_std = one_step(False)
    loss_fused, p_fused = one_step(True)
    assert abs(loss_std - loss_fused) < 1e-4, (loss_std, loss_fused)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5),
        p_std,
        p_fused,
    )
