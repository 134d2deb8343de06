"""The serving weights are bound once (ISSUE 31): ``ServeEngine`` rounds the
float32 masters to its compute dtype as they come through ``_bind`` and holds
nothing else, where every prefill and decode program used to start by casting
the whole float32 tree.

It is the same arithmetic, so the test is equality of bits, not a tolerance:
the engine's own programs, given the tree the engine holds, against the same
programs given the float32 tree (the parent's path: ``_serving_boundary``
casts inside the program). Masters are seeded random float32, which no bf16
holds exactly; each kind of block the cells' programs have is a case.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_evabyte
import test_glm_flash
import test_zaya
from opendiloco_tpu import obs
from opendiloco_tpu.diloco.compression import get_codec
from opendiloco_tpu.models.llama import LlamaConfig, init_params
from opendiloco_tpu.serve import ServeEngine

DENSE = {
    "model_type": "llama", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
    "vocab_size": 256, "max_position_embeddings": 128, "tie_word_embeddings": True,
}
ROUTED_QK_NORM = {
    "model_type": "olmoe", "hidden_size": 64, "intermediate_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": False,
    "vocab_size": 256, "max_position_embeddings": 128, "rms_norm_eps": 1e-5,
    "rope_theta": 10000, "tie_word_embeddings": False,
}
HYBRID = {
    "model_type": "granitemoehybrid", "hidden_size": 32, "intermediate_size": 16,
    "shared_intermediate_size": 24, "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "attention_multiplier": 0.125,
    "embedding_multiplier": 12, "residual_multiplier": 0.22, "logits_scaling": 16,
    "position_embedding_type": "nope", "mamba_n_heads": 8, "mamba_d_head": 8,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_n_groups": 1, "mamba_chunk_size": 8,
    "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "num_experts": 16, "num_local_experts": 8, "first_local_expert": 8,
    "num_experts_per_tok": 3, "vocab_size": 128, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": True,
}
# the last three as their own suites publish them at a tiny size. EVA's two
# rings: a window of 16 rows and 12 pooled rows, in tiles of 4
CASES = {
    "dense": DENSE,
    "routed-qk-norm": ROUTED_QK_NORM,
    "hybrid": HYBRID,
    "latent": test_glm_flash.published(),
    "cca": test_zaya.published(),
    "eva": test_evabyte.published(),
}
SLOTS, BUCKET = 2, 16


def _masters(cfg, seed):
    """Seeded float32 masters, every leaf off its initial value (a norm's
    ones are exact in bf16) so that no leaf survives the rounding unchanged."""
    params = init_params(jax.random.key(seed), cfg)
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed + 1000), len(leaves))
    leaves = [x + 0.01 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)]
    for x in leaves:
        assert x.dtype == jnp.float32
        assert bool(jnp.any(x.astype(jnp.bfloat16).astype(jnp.float32) != x))
    return jax.tree.unflatten(treedef, leaves)


def _same_bits(got, want):
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(
            np.asarray(g).view(np.uint8), np.asarray(w).view(np.uint8)
        )


def _programs_agree(engine, masters, seed):
    """One prefill and two decode steps, the engine's jitted programs on the
    engine's tree against the same programs on the float32 masters (what the
    parent's engine handed its programs): logits, the rows for the cache, the
    per-slot state and the routed FFN's counts."""
    rng = np.random.default_rng(seed)
    n = 11
    ids = np.zeros((1, BUCKET), np.int32)
    ids[0, :n] = rng.integers(1, engine.cfg.vocab_size, n)
    args = (jnp.asarray(ids), jnp.int32(n))
    _same_bits(engine._prefill(engine.params, *args), engine._prefill(masters, *args))

    tok, _ = engine.admit(0, ids[0, :n].tolist())
    tokens, lens = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    tokens[0], lens[0] = tok, n
    for _ in range(2):
        # ``_decode`` donates the caches and the state: each side gets its own
        state = lambda: [None if x is None else jnp.array(x) for x in (
            engine.cache_k, engine.cache_v, *engine._ssm, *engine._cca, *engine._eva)]
        step = (jnp.asarray(tokens), jnp.asarray(lens))
        want = engine._decode(masters, *step, *state())
        got = engine._decode(engine.params, *step, *state())
        _same_bits(got, want)
        nxt, logits = engine.decode_step(tokens, lens)
        np.testing.assert_array_equal(np.asarray(logits), np.asarray(want[1]))
        tokens[0], lens[0] = nxt[0], lens[0] + 1


def _resident_bytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("case", list(CASES))
def test_bound_weights_give_the_bits_of_the_per_call_cast(case, monkeypatch):
    cfg = LlamaConfig.from_dict(CASES[case])
    # the kernel over a 24-row ring in tiles of 8; EVA's over both its rings
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "4" if cfg.eva else "8")
    masters = _masters(cfg, seed=1)
    engine = ServeEngine(
        cfg, masters, num_slots=SLOTS, max_context=48 if cfg.eva else 24,
        prefill_buckets=(BUCKET,), compute_dtype=jnp.bfloat16, decode_kernel="pallas",
    )
    # one tree, in the compute dtype, and the masters are still the caller's
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(engine.params))
    assert all(x.dtype == jnp.float32 for x in jax.tree.leaves(masters))
    assert engine.weight_binds == 1 and engine.swap_count == 0
    assert engine.weights_resident_bytes == _resident_bytes(engine.params)
    assert engine.weights_resident_bytes == _resident_bytes(masters) // 2
    _programs_agree(engine, masters, seed=2)

    second = _masters(cfg, seed=3)
    engine.install_params(1, second)
    assert (engine.weight_binds, engine.swap_count, engine.weights_epoch) == (2, 1, 1)
    _programs_agree(engine, second, seed=4)

    # the wire's float32 leaves arrive on the host and are cast on their way in
    third = _masters(cfg, seed=5)
    codec = get_codec("none")
    blobs = [
        (*codec.encode(np.asarray(x, np.float32).reshape(-1)), tuple(x.shape))
        for x in jax.tree.leaves(third)
    ]
    engine.install_wire(2, blobs, "none")
    assert (engine.weight_binds, engine.swap_count, engine.weights_epoch) == (3, 2, 2)
    _programs_agree(engine, third, seed=6)


def test_a_float32_engine_holds_one_float32_copy(tiny_cfg, monkeypatch):
    """``compute_dtype=float32`` (most of the tests): the tree is float32 as
    before, once, in buffers of the engine's own; the binding is counted and
    its size published."""
    masters = _masters(tiny_cfg, seed=7)
    monkeypatch.setenv("ODTP_OBS", "test")
    obs.reset()
    tracer = obs.tracer()
    try:
        engine = ServeEngine(
            tiny_cfg, masters, num_slots=SLOTS, max_context=24, prefill_buckets=(BUCKET,),
            compute_dtype=jnp.float32,
        )
        held = jax.tree.leaves(engine.params)
        assert all(x.dtype == jnp.float32 for x in held)
        assert engine.weights_resident_bytes == _resident_bytes(masters)
        for h, m in zip(held, jax.tree.leaves(masters)):
            np.testing.assert_array_equal(np.asarray(h), np.asarray(m))
            assert h.unsafe_buffer_pointer() != m.unsafe_buffer_pointer()
        engine.install_params(1, masters)
        assert engine.weight_binds == 2
        assert tracer.counters()[("serve_weight_binds", ())] == 2
        assert tracer.gauges()[("serve_weights_resident_bytes", ())] == _resident_bytes(masters)
    finally:
        obs.reset()
