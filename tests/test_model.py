"""Model unit tests: shapes, causality, HF interop, torch parity oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opendiloco_tpu.models import hf_io
from opendiloco_tpu.models.llama import (
    LlamaConfig,
    causal_lm_loss,
    forward,
    init_params,
    shapes,
)


def test_config_registry_loads():
    for name in ["2m", "14m", "60m", "150m", "1b"]:
        cfg = hf_io.load_config(name)
        assert cfg.hidden_size > 0
    cfg = hf_io.load_config("configs/config_150m.json")
    assert cfg.hidden_size == 1024 and cfg.num_hidden_layers == 12
    cfg1b = hf_io.load_config("1b")
    assert cfg1b.kv_heads == 4 and cfg1b.num_attention_heads == 32


def test_init_params_shapes(tiny_cfg):
    params = init_params(jax.random.key(0), tiny_cfg)
    want = jax.tree.map(lambda s: s.shape, shapes(tiny_cfg))
    got = jax.tree.map(lambda x: x.shape, params)
    assert got == want
    # norms init to ones
    assert np.allclose(params["final_norm"], 1.0)


def test_forward_shape_and_dtype(tiny_cfg):
    params = init_params(jax.random.key(0), tiny_cfg)
    ids = jnp.arange(2 * 16, dtype=jnp.int32).reshape(2, 16) % tiny_cfg.vocab_size
    logits = forward(params, ids, tiny_cfg)
    assert logits.shape == (2, 16, tiny_cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_causality(tiny_cfg):
    """Changing a suffix token must not change prefix logits."""
    params = init_params(jax.random.key(1), tiny_cfg)
    ids = jax.random.randint(jax.random.key(2), (1, 32), 0, tiny_cfg.vocab_size)
    logits_a = forward(params, ids, tiny_cfg, compute_dtype=jnp.float32)
    ids_b = ids.at[0, 20].set((ids[0, 20] + 7) % tiny_cfg.vocab_size)
    logits_b = forward(params, ids_b, tiny_cfg, compute_dtype=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(logits_a[0, :20]), np.asarray(logits_b[0, :20]), atol=1e-5
    )
    assert not np.allclose(np.asarray(logits_a[0, 20:]), np.asarray(logits_b[0, 20:]))


def test_loss_masking():
    logits = jnp.zeros((1, 4, 8))
    labels = jnp.array([[1, 2, -100, 3]])
    loss = causal_lm_loss(logits, labels)
    # uniform logits -> loss == log(8) regardless of masking correctness;
    # use a biased logit at the masked position to detect leakage
    biased = logits.at[0, 1, :].set(jnp.arange(8.0) * 100)
    loss_biased = causal_lm_loss(biased, labels)  # position 1 predicts label[2]=-100
    np.testing.assert_allclose(float(loss), float(loss_biased), atol=1e-5)


def test_hf_roundtrip(tmp_path, tiny_cfg):
    params = init_params(jax.random.key(3), tiny_cfg)
    hf_io.save_params(params, tiny_cfg, str(tmp_path / "m"))
    cfg2 = hf_io.load_config(str(tmp_path / "m"))
    assert cfg2.hidden_size == tiny_cfg.hidden_size
    params2 = hf_io.load_params(str(tmp_path / "m"))
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
        params,
        params2,
    )


@pytest.mark.slow
def test_torch_parity(tmp_path, tiny_cfg):
    """Oracle: our forward matches HF transformers LlamaForCausalLM on the
    same safetensors weights (float32, tiny model)."""
    torch = pytest.importorskip("torch")
    from transformers import AutoModelForCausalLM

    params = init_params(jax.random.key(4), tiny_cfg)
    model_dir = str(tmp_path / "parity")
    hf_io.save_params(params, tiny_cfg, model_dir)

    hf_model = AutoModelForCausalLM.from_pretrained(model_dir)
    hf_model.eval()

    ids = np.random.default_rng(0).integers(0, tiny_cfg.vocab_size, (2, 24))
    with torch.no_grad():
        ref = hf_model(torch.tensor(ids)).logits.numpy()
    ours = np.asarray(
        forward(params, jnp.asarray(ids, jnp.int32), tiny_cfg, compute_dtype=jnp.float32)
    )
    # f32 trig/accumulation-order noise amplifies through the residual
    # stream; verified elementwise at ~1e-5 per-layer (see git history)
    np.testing.assert_allclose(ours, ref, atol=5e-3, rtol=5e-2)


@pytest.mark.parametrize("remat", [False, True, "none", "full", "dots", "dots_all"])
def test_remat_policies_forward_and_grad_parity(tiny_cfg, remat):
    """Every remat policy is pure memory/schedule choice: forward logits and
    parameter gradients must match the no-remat baseline exactly (fp32)."""
    params = init_params(jax.random.key(0), tiny_cfg)
    ids = jnp.arange(2 * 16, dtype=jnp.int32).reshape(2, 16) % tiny_cfg.vocab_size

    def loss(p, r):
        return causal_lm_loss(
            forward(p, ids, tiny_cfg, compute_dtype=jnp.float32, remat=r), ids
        )

    base = jax.grad(lambda p: loss(p, False))(params)
    got = jax.grad(lambda p: loss(p, remat))(params)
    assert float(loss(params, remat)) == pytest.approx(float(loss(params, False)))
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(got)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def _kernel_calls(jaxpr, name: str) -> int:
    """Pallas calls named ``name`` in a jaxpr, whatever they are nested in."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and name in str(eqn.params.get("name")):
            n += 1
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else [v]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _kernel_calls(sub, name)
    return n


@pytest.fixture
def bare_checkpoint(monkeypatch):
    """``remat=True`` as the parent of ISSUE 41 had it: a checkpoint that
    keeps the layer's input and nothing else."""
    from opendiloco_tpu.models import llama

    def arm():
        monkeypatch.setattr(
            llama, "_maybe_remat", lambda block, remat: jax.checkpoint(block)
        )

    return arm


def test_full_remat_keeps_the_flash_kernels_output_and_runs_it_once(
    tiny_cfg, monkeypatch, bare_checkpoint
):
    """Under ``remat=True`` the gradient holds ``odtp_flash_fwd`` once a
    layer's body (the backward has the tagged ``attn_out`` / ``attn_lse``
    at hand), twice under a bare ``jax.checkpoint``; and what it computes is
    what no rematerialisation computes, bit for bit."""
    import jax.experimental.pallas as pl
    from opendiloco_tpu.ops import flash_attention as fa

    orig = pl.pallas_call
    monkeypatch.setattr(
        fa.pl, "pallas_call", lambda *a, **kw: orig(*a, **{**kw, "interpret": True})
    )
    params = init_params(jax.random.key(0), tiny_cfg)
    ids = jnp.arange(2 * 128, dtype=jnp.int32).reshape(2, 128) % tiny_cfg.vocab_size

    def grad(remat):
        return jax.grad(lambda p: causal_lm_loss(forward(
            p, ids, tiny_cfg, compute_dtype=jnp.float32, attn_impl="pallas", remat=remat
        ), ids))

    calls = lambda remat, name: _kernel_calls(jax.make_jaxpr(grad(remat))(params).jaxpr, name)
    assert calls(False, "odtp_flash_fwd") == 1
    assert [calls(True, k) for k in ("odtp_flash_fwd", "odtp_flash_dq", "odtp_flash_dkv")] == [1, 1, 1]
    kept, all_kept = jax.jit(grad(True))(params), jax.jit(grad(False))(params)
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(all_kept)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    bare_checkpoint()
    assert calls(True, "odtp_flash_fwd") == 2
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(jax.jit(grad(True))(params))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_full_remat_keeps_nothing_where_nothing_is_tagged(tiny_cfg, bare_checkpoint):
    """XLA's attention names no value: under ``remat=True`` the gradient's
    lowered program is the bare checkpoint's, letter for letter."""
    params = init_params(jax.random.key(0), tiny_cfg)
    ids = jnp.arange(2 * 16, dtype=jnp.int32).reshape(2, 16) % tiny_cfg.vocab_size

    def lowered():
        return jax.jit(jax.grad(lambda p: causal_lm_loss(forward(
            p, ids, tiny_cfg, compute_dtype=jnp.float32, attn_impl="xla", remat=True
        ), ids))).lower(params).as_text()

    ours = lowered()
    assert "optimization_barrier" in ours  # a checkpoint's mark: something is recomputed
    bare_checkpoint()
    assert ours == lowered()


def test_remat_rejects_unknown_policy(tiny_cfg):
    params = init_params(jax.random.key(0), tiny_cfg)
    ids = jnp.zeros((1, 16), jnp.int32)
    with pytest.raises(ValueError, match="remat"):
        forward(params, ids, tiny_cfg, remat="bogus")


def _dense_model():
    cfg = LlamaConfig(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=128, tie_word_embeddings=True,
    )
    return cfg, init_params(jax.random.key(3), cfg)


def _routed_qk_norm_model():
    cfg = LlamaConfig.from_dict({
        "model_type": "olmoe", "vocab_size": 256, "hidden_size": 64,
        "intermediate_size": 32, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_experts": 8, "num_experts_per_tok": 2, "max_position_embeddings": 128,
    })
    assert cfg.qk_norm
    params = init_params(jax.random.key(4), cfg)
    # a router that spreads its probabilities: no top-k choice near a tie
    params["layers"]["router"] = params["layers"]["router"] * 25.0
    return cfg, params


def _hybrid_model():
    cfg = LlamaConfig.from_dict({
        "model_type": "granitemoehybrid", "vocab_size": 256, "hidden_size": 32,
        "intermediate_size": 16, "shared_intermediate_size": 24, "num_hidden_layers": 4,
        "layer_types": ["mamba", "mamba", "attention", "mamba"], "num_attention_heads": 4,
        "num_key_value_heads": 2, "attention_multiplier": 0.125, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 16, "position_embedding_type": "nope",
        "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_state": 16, "mamba_d_conv": 4,
        "mamba_n_groups": 1, "mamba_chunk_size": 8, "mamba_expand": 2, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "num_experts": 8, "num_experts_per_tok": 2,
        "max_position_embeddings": 128, "tie_word_embeddings": True,
    })
    assert cfg.hybrid and cfg.num_mamba_layers == 3
    params = init_params(jax.random.key(8), cfg)
    for stack in params["layers"].values():  # no top-k choice near a tie
        stack["router"] = stack["router"] * 25.0
    return cfg, params


def _latent_routed_model():
    cfg = LlamaConfig.from_dict({
        "model_type": "glm4_moe_lite", "vocab_size": 256, "hidden_size": 64,
        "intermediate_size": 96, "moe_intermediate_size": 32, "num_hidden_layers": 3,
        "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
        "qk_nope_head_dim": 12, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "first_k_dense_replace": 1, "n_routed_experts": 8, "n_shared_experts": 1,
        "num_experts_per_tok": 2, "topk_method": "noaux_tc", "norm_topk_prob": True,
        "routed_scaling_factor": 1.8, "rope_theta": 1e6, "max_position_embeddings": 128,
    })
    assert cfg.latent and cfg.leading_dense == 1
    params = init_params(jax.random.key(5), cfg)
    stack = params["layers"]["attention"]
    stack["router"] = stack["router"] * 25.0  # no top-k choice near a tie
    return cfg, params


def _zaya_model():
    cfg = LlamaConfig.from_dict({
        "model_type": "zaya", "vocab_size": 256, "hidden_size": 64, "head_dim": 8,
        "num_attention_heads": 4, "num_key_value_heads": 2, "num_hidden_layers": 3,
        "layer_types": ["hybrid"] * 3, "cca_time0": 2, "cca_time1": 2,
        "partial_rotary_factor": 0.5, "router_hidden_size": 16, "num_experts": 8,
        "num_experts_per_tok": 1, "moe_intermediate_size": 32, "tie_word_embeddings": True,
        "rope_parameters": {"hybrid": {"rope_theta": 5e6}}, "max_position_embeddings": 128,
    })
    assert cfg.cca and cfg.head_dim * cfg.num_attention_heads != cfg.hidden_size
    return cfg, init_params(jax.random.key(6), cfg)


def _eva_model():
    cfg = LlamaConfig.from_dict({
        "model_type": "evabyte", "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 4, "num_hidden_layers": 3,
        "attention_class": "eva", "chunk_size": 2, "window_size": 8, "num_pred_heads": 2,
        "norm_add_unit_offset": True, "fp32_skip_add": True, "fp32_logits": True,
        "rope_theta": 1e5, "init_std": 0.05, "max_position_embeddings": 128,
    })
    assert cfg.eva and cfg.eva_chunks_per_window == 4
    return cfg, init_params(jax.random.key(7), cfg)


def _two_latent_kinds_model():
    cfg = LlamaConfig.from_dict({
        "model_type": "dots3_note", "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_hidden_layers": 4, "num_attention_heads": 4,
        "layer_types": ["full_attention", "full_attention", "sliding_attention", "sliding_attention"],
        "first_k_dense_replace": 1, "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 12,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "index_n_heads": 2, "index_head_dim": 16,
        "index_topk": 6, "q_chunk_size": 4, "sliding_window_size": 3, "swa_num_attention_heads": 2,
        "swa_q_lora_rank": 16, "swa_kv_lora_rank": 24, "swa_qk_nope_head_dim": 8,
        "swa_qk_rope_head_dim": 8, "swa_v_head_dim": 8, "swa_rope_theta": 5e4,
        "attention_gate_type": "headwise", "swa_attention_gate_type": "headwise",
        "apply_mla_qkv_lora_rescale": True, "n_routed_experts": 8, "n_shared_experts": 1,
        "num_experts_per_tok": 2, "topk_method": "noaux_tc", "norm_topk_prob": True,
        "rope_theta": 8e7, "max_position_embeddings": 128,
    })
    assert cfg.sliding and cfg.layer_kinds == ("dense", "attention", "sliding", "sliding")
    params = init_params(jax.random.key(9), cfg)
    for kind in ("attention", "sliding"):  # no top-k choice near a tie
        params["layers"][kind]["router"] = params["layers"][kind]["router"] * 25.0
    return cfg, params


def _two_gqa_kinds_model():
    cfg = LlamaConfig.from_dict({
        "model_type": "laguna", "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32, "num_hidden_layers": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 3,
        "layer_types": ["full_attention", "sliding_attention", "full_attention", "sliding_attention"],
        "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"], "mlp_only_layers": [0],
        "num_attention_heads_per_layer": [4, 6, 4, 6], "gating": "per-head",
        "rope_parameters": {
            "full_attention": {"rope_theta": 5e5, "rope_type": "yarn", "factor": 8, "beta_fast": 4,
                               "original_max_position_embeddings": 16, "beta_slow": 1,
                               "attention_factor": 1.2, "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 1e4, "partial_rotary_factor": 1},
        },
        "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
        "moe_routed_scaling_factor": 2.5, "q_chunk_size": 4, "max_position_embeddings": 128,
    })
    assert cfg.sliding and not cfg.latent
    assert cfg.layer_kinds == ("dense", "sliding", "attention", "sliding")
    params = init_params(jax.random.key(10), cfg)
    for kind in ("attention", "sliding"):  # no top-k choice near a tie
        params["layers"][kind]["router"] = params["layers"][kind]["router"] * 25.0
    return cfg, params


def _lightning_and_blocks_model():
    cfg = LlamaConfig.from_dict({
        "model_type": "minicpm_sala", "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
        "num_hidden_layers": 3, "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "mixer_types": ["minicpm4", "lightning-attn", "minicpm4", "lightning-attn"], "qk_norm": True,
        "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 16, "attn_use_output_gate": True,
        "max_position_embeddings": 128, "norm_init_std": 0.1,
        "sparse_config": {"kernel_size": 4, "kernel_stride": 2, "block_size": 4, "topk": 3,
                          "init_blocks": 1, "window_size": 4, "dense_len": 8},
    })
    assert cfg.traits == ("linear", "blocks") and cfg.layer_kinds == ("attention", "lightning", "attention")
    return cfg, init_params(jax.random.key(11), cfg)


def _kda_and_gqa_model():
    cfg = LlamaConfig.from_dict({
        "model_type": "solar_open2", "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_hidden_layers": 4, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "gqa_layers": [1, 5], "use_gqa_gate": True,
        "kda_allow_neg_eigval": True, "n_routed_experts": 8, "n_shared_experts": 1,
        "num_experts_per_tok": 2, "norm_topk_prob": True, "max_position_embeddings": 128,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4,
                               "num_kv_heads": None},
        "norm_init_std": 0.1,
    })
    assert cfg.traits == ("kda",) and cfg.layer_kinds == ("kda", "attention", "kda", "kda")
    params = init_params(jax.random.key(12), cfg)
    for kind in ("attention", "kda"):  # no top-k choice near a tie
        params["layers"][kind]["router"] = params["layers"][kind]["router"] * 25.0
    return cfg, params


@pytest.mark.parametrize(
    "model",
    [_dense_model, _routed_qk_norm_model, _hybrid_model, _latent_routed_model, _zaya_model,
     _eva_model, _two_latent_kinds_model, _two_gqa_kinds_model, _lightning_and_blocks_model,
     _kda_and_gqa_model],
)
def test_the_four_forwards_agree(model):
    """One block under four drivers: in float32 the training forward, the
    prefill, the decode step and the continued prefill give one another's
    logits and greedy tokens. A change to one driver's layer that the others
    do not get fails here. The latent block runs under the three that support
    it (training and prefill rebuild k and v, the decode step absorbs them:
    two formulas of one attention); the run over a slot's rows handles (k, v) rows
    and refuses it. The CCA block runs under the same three (its projections
    read the token before: a shift over the sequence in training and prefill,
    a per-slot state in decode, written by the prefill at the prompt's true
    length); the continued prefill has no such state to start from and
    refuses it. The EVA block runs under the same three (a window of rows and
    the pooled chunks before it: over the whole sequence in training and
    prefill, over a slot's two rings in decode, the prompt of 9 ending in the
    second window of 8); the continued prefill takes the ring for the context
    and refuses it. The hybrid stack runs under the same three (its Mamba-2
    layers in chunks over the sequence in training and prefill, a step over
    the slot's recurrent state in decode, which the prefill leaves at the
    prompt's true length); the continued prefill has rows to start from and
    no state, and refuses it. Of a head of two vocabularies the first samples."""
    from opendiloco_tpu.models.llama import (
        cache_insert, chunk_prefill_forward, decode_forward, init_kv_cache, prefill_forward,
    )

    cfg, params = model()
    f32 = dict(compute_dtype=jnp.float32)
    full = lambda ids: forward(params, jnp.asarray([ids], jnp.int32), cfg, remat=False, **f32)[0]
    close = lambda got, want: np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    prompt = np.random.default_rng(5).integers(3, 256, 9).tolist()
    P, K = len(prompt), 3

    # prefill (padded to a bucket of 16) = the forward's last prompt row
    padded = jnp.asarray([prompt + [0] * (16 - P)], jnp.int32)
    logits, ks, vs, *left = prefill_forward(params, padded, jnp.int32(P), cfg, **f32)
    close(logits[0], full(prompt)[P - 1])
    tok = int(jnp.argmax(logits[0, : cfg.vocab_size]))
    assert (vs is None) == (cfg.latent and not cfg.sliding)  # the latent rows alone are kept
    if cfg.linear:
        # lightning layers beside a selection by blocks (PR 61): the prompt goes in
        # as chunks, each entering with the state the chunk before left (the prompt
        # of 9 is past ``dense_len`` 8: the selection runs), and the decode steps
        # through both rings and the state give the forward's next rows
        from opendiloco_tpu.models.ring_cache import init_lightning_state, init_pooled_cache

        cache = init_kv_cache(cfg, 2, 32, jnp.float32)
        rings = (cache["k"], cache["v"], init_pooled_cache(cfg, 2, 32, jnp.float32),
                 init_lightning_state(cfg, 2))
        assert left[0].shape == (1, 4, 16, 16) and rings[2].shape[-1] == 16
        for plen in range(0, P, 4):
            count = min(4, P - plen)
            ids = jnp.asarray([(prompt[plen : plen + count] + [0] * 4)[:4]], jnp.int32)
            chunked, ck, cv, _, pc, ls = chunk_prefill_forward(
                params, ids, plen, count, 1, *rings[:2], None, cfg, pooled_cache=rings[2],
                lightning_state=rings[3], total=P, **f32)
            rings = (ck, cv, pc, ls)
        close(chunked[0], logits[0])
        close(rings[3][:, 1], left[0])
        seq, steps = prompt + [tok], []
        for _ in range(4):
            tokens, lens = jnp.asarray([0, seq[-1]], jnp.int32), jnp.asarray([0, len(seq) - 1], jnp.int32)
            step, *rings = decode_forward(
                params, tokens, lens, *rings[:2], cfg, pooled_cache=rings[2], lightning_state=rings[3], **f32)
            steps.append(step[1])
            seq.append(int(jnp.argmax(step[1])))
        close(jnp.stack(steps), full(seq[:-1])[P:])
        return
    if cfg.kda:
        # kda layers beside gated grouped-query ones (PR 64): the prompt goes in as
        # chunks, each entering with the state and the convolution's tail the chunk
        # before left, and the decode steps through the ring, the states and the
        # tails give the forward's next rows
        from opendiloco_tpu.models.ring_cache import init_kda_state

        cache, held = init_kv_cache(cfg, 2, 32, jnp.float32), init_kda_state(cfg, 2, jnp.float32)
        rings = (cache["k"], cache["v"], held["state"], held["tail"])
        assert left[0].shape == (3, 4, 16, 16) and left[1].shape == (3, 3, 192)
        for plen in range(0, P, 4):
            count = min(4, P - plen)
            ids = jnp.asarray([(prompt[plen : plen + count] + [0] * 4)[:4]], jnp.int32)
            chunked, ck, cv, _, ks_, kt_ = chunk_prefill_forward(
                params, ids, plen, count, 1, *rings[:2], None, cfg, kda_state=rings[2],
                kda_tail=rings[3], **f32)
            rings = (ck, cv, ks_, kt_)
        close(chunked[0], logits[0])
        close(rings[2][:, 1], left[0])
        close(rings[3][:, :, 1], left[1])
        seq, steps = prompt + [tok], []
        for _ in range(4):
            tokens, lens = jnp.asarray([0, seq[-1]], jnp.int32), jnp.asarray([0, len(seq) - 1], jnp.int32)
            step, *rings = decode_forward(
                params, tokens, lens, *rings[:2], cfg, kda_state=rings[2], kda_tail=rings[3], **f32)
            steps.append(step[1])
            seq.append(int(jnp.argmax(step[1])))
        close(jnp.stack(steps), full(seq[:-1])[P:])
        return
    if cfg.sliding and not cfg.latent:
        # two kinds of grouped-query layer (PR 56): the rows come by kind, each a
        # (k, v) pair; the sliding layers' rings wrap, so the prompt goes in as
        # chunks over both pairs of rings and the decode steps over them (the
        # sliding layers' under the window) give the forward's next rows, past
        # the point where the ring of 8 rows wraps
        cache = init_kv_cache(cfg, 2, 32, jnp.float32)
        ck, cv = cache["k"], cache["v"]
        assert ks.k.shape == (2, 16, 2, 16) and vs.v.shape == (2, 16, 2, 16) and cv.shape[-1] == 8
        for plen in range(0, P, 4):
            count = min(4, P - plen)
            ids = jnp.asarray([(prompt[plen : plen + count] + [0] * 4)[:4]], jnp.int32)
            chunked, ck, cv, _ = chunk_prefill_forward(params, ids, plen, count, 1, ck, cv, None, cfg, **f32)
        close(chunked[0], logits[0])
        seq, steps = prompt + [tok], []
        for _ in range(4):
            tokens, lens = jnp.asarray([0, seq[-1]], jnp.int32), jnp.asarray([0, len(seq) - 1], jnp.int32)
            step, ck, cv = decode_forward(params, tokens, lens, ck, cv, cfg, **f32)
            steps.append(step[1])
            seq.append(int(jnp.argmax(step[1])))
        close(jnp.stack(steps), full(seq[:-1])[P:])
        return
    if cfg.sliding:
        # two kinds of latent layer (the stack of two geometries, PR 54): the
        # sliding layers' rows stand in the values' place, and their ring wraps,
        # so no whole prompt is inserted: the prompt goes in as chunks (the
        # continued prefill over latent rows, which this block is the first to
        # run), and the decode step over the three rings gives the forward's next row
        from opendiloco_tpu.models.ring_cache import init_index_cache

        cache = init_kv_cache(cfg, 2, 32, jnp.float32)
        ck, cv, ci = cache["k"], cache["v"], init_index_cache(cfg, 2, 32, jnp.float32)
        assert cv.shape[-1] == 8  # a chunk of 4 beside the window's rows before it, in whole chunks
        for plen in range(0, P, 4):
            count = min(4, P - plen)
            ids = jnp.asarray([(prompt[plen : plen + count] + [0] * 4)[:4]], jnp.int32)
            chunked, ck, cv, ci = chunk_prefill_forward(params, ids, plen, count, 1, ck, cv, ci, cfg, **f32)
        close(chunked[0], logits[0])
        tokens, lens = jnp.asarray([0, tok], jnp.int32), jnp.asarray([0, P], jnp.int32)
        step, *_ = decode_forward(params, tokens, lens, ck, cv, cfg, index_cache=ci, **f32)
        close(step[1], full(prompt + [tok])[P])
        return

    # slot 1 of two holds the prompt; one decode step = a continued prefill
    # over a tail of one = the forward's next row
    cache = init_kv_cache(cfg, 2, 32, jnp.float32)
    state = {}
    if cfg.eva:  # the last window's rows, the pooled rows and the pooling under way
        from opendiloco_tpu.models.ring_cache import eva_insert, init_eva_state

        eva = init_eva_state(cfg, 2, 32, jnp.float32)
        ck, cv, *state["eva_state"] = eva_insert(
            cache["k"], cache["v"], eva["pool_k"], eva["pool_v"], eva["stats"], ks, vs, *left,
            jnp.int32(1))
    else:
        ck, cv = cache_insert(cache["k"], cache["v"], ks, vs, jnp.int32(1))
    tokens, lens = jnp.asarray([0, tok], jnp.int32), jnp.asarray([0, P], jnp.int32)
    want = full(prompt + [tok])[P]
    if cfg.cca:  # what the prompt's last token left, into slot 1 of the state
        from opendiloco_tpu.models.ring_cache import cca_state_insert, init_cca_state

        state["cca_state"] = cca_state_insert(
            init_cca_state(cfg, 2, jnp.float32), left[0], jnp.int32(1))
    if cfg.hybrid:  # the recurrent states and conv tails the prompt left
        from opendiloco_tpu.models.ring_cache import init_ssm_state, state_insert

        held = init_ssm_state(cfg, 2, jnp.float32)
        state["ssm_state"], state["conv_state"] = state_insert(
            held["ssm"], held["conv"], *left, jnp.int32(1))
    step, *_ = decode_forward(params, tokens, lens, ck, cv, cfg, **state, **f32)
    close(step[1], want)
    if cfg.latent or cfg.cca or cfg.eva or cfg.hybrid:
        what = "latent" if cfg.latent else "CCA" if cfg.cca else "EVA" if cfg.eva else "Mamba-2"
        with pytest.raises(ValueError, match=f"refused for a configuration with {what}"):
            chunk_prefill_forward(params, tokens[1:, None], P, 1, 1, ck, cv, None, cfg, **f32)
        return
    continued, *_ = chunk_prefill_forward(params, tokens[1:, None], P, 1, 1, ck, cv, None, cfg, **f32)
    close(continued[0], want)

    # over a tail of K tokens (in a bucket of K + 2) it gives the forward's row
    # at each of their positions, and leaves the tail's rows where decode steps would
    seq = prompt + [tok]
    for _ in range(K - 1):
        seq.append(int(jnp.argmax(full(seq)[-1])))
    tail = jnp.asarray([seq[P:] + [0, 0]], jnp.int32)
    for count in range(1, K + 1):
        continued, sk, sv, _ = chunk_prefill_forward(
            params, tail, P, count, 1, ck, cv, None, cfg, **f32)
        close(continued[0], full(seq)[P + count - 1])
    step, dk, dv = decode_forward(params, tokens, lens, ck, cv, cfg, **f32)
    np.testing.assert_allclose(sk[:, 1, ..., P], dk[:, 1, ..., P], rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(sk[:, 1, ..., P + K:], ck[:, 1, ..., P + K:])  # no padding row lands
    np.testing.assert_array_equal(sk[:, 0], ck[:, 0])  # nor anything in another slot
