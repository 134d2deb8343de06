"""The serving programs of seven configurations the benchmark measures lower
to the text they lowered to before dots3-note-prev's layers came (PR 54) and
before Laguna-S-2.1's (PR 56, which added ``serve-olmoe-fewshot``'s and
``serve-dots3-notes``'s configurations, recorded from its parent ``b964f0b``): the
decode step, a whole-prompt prefill and the continued prefill (a chunk, or the
suffix behind a prefix) of ``serve-360m-batch``'s, ``serve-glm-flash-agent``'s,
``serve-keye-videoqa``'s, ``serve-olmoe-fewshot``'s and ``serve-dots3-notes``'s
configurations, lowered for the TPU at the cells'
shapes with the decode kernels in (shapes alone: nothing is compiled or run).
A model PR that adds work to a shared program hands those cells a reason to
move; this holds the programs' text to a digest recorded from the parent
commit (``python tests/test_program_text.py`` prints a tree's digests: run it
with the parent's checkout first on ``PYTHONPATH`` to record).

Since PR 55 a whole-prompt prefill's causal attention takes the flash forward
kernel where ``decode_kernels.prefill_form`` says so. ``prefill`` here is the
512-row program in the XLA form (``decode_kernel`` "xla"), which stays the
parent's to the letter; ``prefill/<bucket>`` are the cell's own buckets that
keep the XLA form under the kernels (the batch cell's 32 and 128, under the
floor of 512 rows; the agent cell's 768, 45 MB of scores), lowered as the
engine lowers them, and they are the parent's too (recorded from ``c8ccf9c``, PR 55's parent).

PR 60 changed the latent decode kernel (``odtp_mla_decode_attn``: what a slot's
step writes back), which only ``glm``'s and ``dots3``'s ``decode`` hold: those two
digests are PR 60's own tree's, every other one is as it was.

PR 61 (MiniCPM-SALA: lightning layers' states and a selection by blocks in the
shared forwards, engine and scheduler) added ``serve-granite-h-docqa``'s and
``serve-laguna-repoedit``'s configurations, recorded from its parent ``c311252``.

PR 62 gave ``chunk_prefill_forward`` the kernel ``odtp_chunk_attn`` where the XLA
form's tile of scores would pass 96 MB (``decode_kernels.chunk_form``): of these
cells Laguna's full layers alone. ``chunk`` is lowered as the engine lowers it on
the chip (``decode_kernel`` "pallas") wherever the form stays the XLA one there,
Keye's and dots3's among them, and for Laguna as it lowers off the chip: every
digest is the parent's.

PR 63 gave the same function the kernel ``odtp_latent_chunk_attn`` for a latent
layer's chunk where the XLA form's tile of scores would reach the same line
(``decode_kernels.latent_chunk_form``): of these cells dots3's full layers alone
(128 heads: 134 MB; its sliding layers and Keye keep the XLA form). dots3's
``chunk`` is lowered as it lowers off the chip, as Laguna's is, and no digest was
re-recorded: every one is as PR 62 left it.

PR 64 (Solar-Open2: kda layers' states and tails in the shared forwards, engine
and scheduler) added ``serve-sala-longdoc``'s configuration, whose decode step
hands a state over as the kda layers' does and whose chunks enter with one,
recorded from its parent ``4984b84``; its ``chunk`` (the engine's
``state_chunk_program``) is lowered as it lowers off the chip, as Laguna's is.

The loop is held the same way: ``ContinuousBatcher``'s iteration for a
configuration without sliding layers calls no function of the engine that the
parent's did not."""

import dataclasses
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = {
    "360m": ("smollm2-360m", "serve-360m-batch"),
    "glm": ("glm-4.7-flash", "serve-glm-flash-agent"),
    "keye": ("keye-vl-2.0-30b-a3b", "serve-keye-videoqa"),
    "olmoe": ("olmoe-1b-7b", "serve-olmoe-fewshot"),
    "dots3": ("dots3-note-prev", "serve-dots3-notes"),
    "granite": ("granite-4.0-h-small", "serve-granite-h-docqa"),
    "laguna": ("laguna-s-2.1", "serve-laguna-repoedit"),
    "sala": ("minicpm-sala", "serve-sala-longdoc"),
}
# recorded from commit f83e3d2 (PR 52's tree, PR 54's parent)
PARENT = {
    "360m": {"decode": "60bc61211abd1b80", "prefill": "c0dc8cf8a5e066b8",
             "chunk": "a3efda827d689e95", "prefill/32": "09f6602a6c323cac",
             "prefill/128": "173e5ce5277ff946"},
    # ``decode``: PR 60's own (the latent decode kernel hands back the 128-row
    # block that holds the step's row; the parent's read 2d6902c25c19c003)
    "glm": {"decode": "6a11d9027cd2cc97", "prefill": "111348d7d79011c6",
            "prefill/768": "0d22cf03759c402a"},
    "keye": {"decode": "c115bdfaa18a765f", "prefill": "fccbb3f9f6585ff8",
             "chunk": "1a2463f7d2aefe97"},
    # recorded from commit b964f0b (PR 55's tree, PR 56's parent)
    "olmoe": {"decode": "80be10d23e498851", "prefill": "c01c547432729285",
              "chunk": "2be097d15cf0759f"},
    # ``decode``: PR 60's own, as ``glm``'s (the parent's read 4b291eeac5a6b303)
    "dots3": {"decode": "8b9e121900f07068", "prefill": "643fffdf8cd98c94",
              "chunk": "1496f43173c333a6"},
    # recorded from commit c311252 (PR 60's tree, PR 61's parent): the Mamba-2
    # hybrid, whose decode step hands a state over as the lightning layers' does,
    # and the stack of two grouped-query kinds, whose chunks are the engine's
    "granite": {"decode": "139da673d719abfa", "prefill": "dff0fa32301a6561",
                "prefill/512": "dff0fa32301a6561"},
    "laguna": {"decode": "1ca47f13e8229877", "prefill": "4bab75441ed0072b",
               "chunk": "b7704d4b7c53f06e"},
    # recorded from commit 4984b84 (PR 63's tree, PR 64's parent): the stack whose
    # state rides from chunk to chunk and to the step as the kda layers' does
    "sala": {"decode": "b4f4c18d3646ea97", "prefill": "0aceaeb71cc78b05",
             "chunk": "bb2d459ff0653fb9"},
}
# the engine's methods that the batcher's loop (and a submit) called at that
# commit while it served two requests of a dense, a latent and an indexed
# configuration at a tiny size, each past the other in the queue
_LOOP = (
    "_bucket_of", "_count_cca", "_count_eva", "_count_latent", "_count_phases", "_count_ssm",
    "_enqueue_step", "_finish_step", "_read", "_refuse_positions", "_split_counts",
    "admit_enqueue", "maybe_swap", "needs_chunks", "prompt_fits", "staleness", "step_ahead",
)
_CHUNKS = ("_close_chunk", "_count_dsa", "admit_begin", "admit_chunk")
PARENT_CALLS = {
    "360m": _LOOP, "glm": _LOOP, "olmoe": _LOOP,
    "keye": (*_LOOP, *_CHUNKS), "dots3": (*_LOOP, *_CHUNKS),
    # recorded from commit c311252 (PR 61's parent)
    "granite": _LOOP,
    "laguna": tuple(sorted({*_LOOP, *_CHUNKS, "_count_kinds"} - {"_bucket_of", "_count_latent", "admit_enqueue"})),
    # recorded from commit 4984b84 (PR 64's parent)
    "sala": tuple(sorted({*_LOOP, *_CHUNKS, "_count_sala"}
                         - {"_bucket_of", "_count_latent", "_split_counts", "admit_enqueue"})),
}


def _cell(config, workload):
    from opendiloco_tpu.models.llama import LlamaConfig

    bench = os.path.join(ROOT, "benchmark")
    with open(os.path.join(bench, "configs", f"{config}.json")) as f:
        cfg = LlamaConfig.from_dict(json.load(f))
    with open(os.path.join(bench, "workloads", f"{workload}.json")) as f:
        return cfg, json.load(f)["engine"]


def digests(name: str) -> dict:
    """{program: sha256 of its StableHLO text, lowered for the TPU} of one
    cell's configuration at the cell's slots and context."""
    from opendiloco_tpu.models import llama, ring_cache
    from opendiloco_tpu.ops.decode_kernels import prefill_form
    from opendiloco_tpu.serve.engine import chunk_program, serving_programs, state_chunk_program

    jax.config.update("jax_traceback_in_locations_limit", 0)
    cfg, opts = _cell(*CELLS[name])
    bf = jnp.bfloat16
    sds = jax.ShapeDtypeStruct
    params = jax.tree.map(lambda x: sds(x.shape, bf), llama.shapes(cfg))
    slots, rows = opts["num_slots"], opts["max_context"]
    if (cfg.sliding or cfg.linear) and not cfg.q_chunk_size:  # the engine's chunk, as the engine lays it
        cfg = dataclasses.replace(cfg, q_chunk_size=opts["prefill_chunk"])
    cache = jax.eval_shape(lambda: ring_cache.init_kv_cache(cfg, slots, rows, bf))
    rings = [cache["k"], cache["v"]]
    if cfg.linear:
        rings += [jax.eval_shape(lambda: ring_cache.init_pooled_cache(cfg, slots, rows, bf)),
                  jax.eval_shape(lambda: ring_cache.init_lightning_state(cfg, slots))]
    if cfg.hybrid:
        state = jax.eval_shape(lambda: ring_cache.init_ssm_state(cfg, slots, bf))
        rings += [state["ssm"], state["conv"]]
    if cfg.sparse:
        rings.append(jax.eval_shape(lambda: ring_cache.init_index_cache(cfg, slots, rows, bf)))
    vec, scalar = sds((slots,), jnp.int32), sds((), jnp.int32)
    prefill, decode, _, n = serving_programs(cfg, compute_dtype=bf, decode_kernel="pallas")
    lower = lambda fn, *args, **kw: jax.jit(fn, **kw).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()
    texts = {
        "decode": lower(decode, params, vec, vec, vec, *rings,
                        donate_argnums=tuple(range(4, 4 + n))),
        "prefill": lower(
            serving_programs(cfg, compute_dtype=bf, decode_kernel="xla")[0],
            params, sds((1, 512), jnp.int32), scalar),
    }
    heads = llama.causal_prefill_heads(cfg)
    for bucket in opts["prefill_buckets"] if heads else ():
        if prefill_form(bucket, *heads, "pallas") == "xla":
            texts[f"prefill/{bucket}"] = lower(
                prefill, params, sds((1, bucket), jnp.int32), scalar)
    # the continued prefill as the engine lowers it on the chip where its
    # attention keeps the tiled XLA form there (PR 62: ``chunk_form``'s bytes
    # rule; PR 63: ``latent_chunk_form``'s, the same line over latent rows), else
    # (Laguna's full layers, dots3's) as it lowers off the chip: the parent's
    # text either way
    chunk = cfg.q_chunk_size or 128
    if cfg.latent:
        xla_there = llama.latent_chunk_attn_form(cfg, chunk, rows, "pallas") == "absorbed-xla"
    else:
        xla_there = llama.chunk_attn_form(cfg, chunk, rows, "pallas") == "tiled-xla"
    assert xla_there == (name not in ("laguna", "dots3", "sala"))
    kernel = "pallas" if xla_there else "xla"
    if cfg.linear:
        texts["chunk"] = lower(
            state_chunk_program(cfg, compute_dtype=bf, decode_kernel=kernel), params,
            sds((1, cfg.q_chunk_size), jnp.int32),
            scalar, scalar, scalar, scalar, sds((), jnp.bool_), vec, *rings,
            donate_argnums=(7, 8, 9, 10, 11),
        )
    elif cfg.sparse or cfg.sliding:
        texts["chunk"] = lower(
            chunk_program(cfg, compute_dtype=bf, decode_kernel=kernel), params,
            sds((1, cfg.q_chunk_size), jnp.int32),
            scalar, scalar, scalar, sds((), jnp.bool_), vec, *rings,
            *([] if cfg.sparse else [None]), donate_argnums=(6, 7, 8, 9),
        )
    elif not (cfg.latent or cfg.hybrid):  # the suffix behind a reused prefix
        texts["chunk"] = lower(
            lambda p, tail, plen, count, slot, ck, cv: llama.chunk_prefill_forward(
                p, tail, plen, count, slot, ck, cv, None, cfg, compute_dtype=bf,
                decode_kernel=kernel),
            params, sds((1, chunk), jnp.int32), scalar, scalar, scalar, *rings,
            donate_argnums=(5, 6),
        )
    return {k: hashlib.sha256(v.encode()).hexdigest()[:16] for k, v in texts.items()}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_programs_lower_to_the_parents_text(name):
    assert digests(name) == PARENT[name]


TINY = {
    "360m": dict(hidden_size=32, intermediate_size=64, num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, vocab_size=64),
    "glm": dict(hidden_size=32, intermediate_size=64, num_hidden_layers=2, num_attention_heads=4,
                vocab_size=64, q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
                qk_rope_head_dim=4, v_head_dim=8),
    "keye": dict(hidden_size=32, intermediate_size=64, num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, vocab_size=64, index_n_heads=2, index_head_dim=8,
                 index_topk=6, q_chunk_size=8),
    "olmoe": dict(model_type="olmoe", hidden_size=32, intermediate_size=32, num_hidden_layers=2,
                  num_attention_heads=4, vocab_size=64, num_experts=8, num_experts_per_tok=2),
    "dots3": dict(
        model_type="dots3_note", hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        num_hidden_layers=3, num_attention_heads=4, vocab_size=64,
        layer_types=["full_attention", "full_attention", "sliding_attention"],
        first_k_dense_replace=1, q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, index_n_heads=2, index_head_dim=8, index_topk=6,
        q_chunk_size=8, sliding_window_size=3, swa_num_attention_heads=2, swa_q_lora_rank=16,
        swa_kv_lora_rank=8, swa_qk_nope_head_dim=8, swa_qk_rope_head_dim=4, swa_v_head_dim=8,
        n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=2, topk_method="noaux_tc",
    ),
    "granite": dict(
        model_type="granitemoehybrid", hidden_size=32, intermediate_size=16, vocab_size=64,
        shared_intermediate_size=24, num_hidden_layers=3, num_attention_heads=4,
        num_key_value_heads=2, layer_types=["mamba", "attention", "mamba"],
        position_embedding_type="nope", mamba_n_heads=8, mamba_d_head=8, mamba_d_state=16,
        mamba_d_conv=4, mamba_n_groups=1, mamba_chunk_size=8, mamba_expand=2, mamba_conv_bias=True,
        mamba_proj_bias=False, num_experts=8, num_experts_per_tok=2,
    ),
    "laguna": dict(
        model_type="laguna", hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
        shared_expert_intermediate_size=16, vocab_size=64, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8, sliding_window=3,
        layer_types=["full_attention", "sliding_attention", "full_attention"],
        mlp_layer_types=["dense", "sparse", "sparse"], mlp_only_layers=[0],
        num_attention_heads_per_layer=[4, 6, 4], gating="per-head",
        rope_parameters={
            "full_attention": {"rope_theta": 5e5, "rope_type": "default", "partial_rotary_factor": 0.5},
            "sliding_attention": {"rope_type": "default", "rope_theta": 1e4, "partial_rotary_factor": 1},
        },
        num_experts=8, num_experts_per_tok=2, norm_topk_prob=True,
    ),
    "sala": dict(
        model_type="minicpm_sala", hidden_size=32, intermediate_size=64, vocab_size=64,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        mixer_types=["minicpm4", "lightning-attn", "minicpm4"], qk_norm=True, scale_emb=12,
        scale_depth=1.4, dim_model_base=16, attn_use_output_gate=True,
        sparse_config=dict(kernel_size=4, kernel_stride=2, block_size=4, topk=3, init_blocks=1,
                           window_size=4, dense_len=8),
    ),
}


def loop_calls(name: str) -> list:
    """The names of the engine's methods called while a batcher serves two
    requests (the second longer than every bucket where the configuration
    admits in chunks)."""
    import numpy as np

    from opendiloco_tpu.models.llama import LlamaConfig, init_params
    from opendiloco_tpu.serve import ContinuousBatcher, ServeEngine

    cfg = LlamaConfig.from_dict(TINY[name])
    engine = ServeEngine(
        cfg, init_params(jax.random.key(0), cfg), num_slots=2, max_context=32,
        prefill_buckets=(16,), compute_dtype=jnp.float32,
        **({"prefill_chunk": 8} if (cfg.sliding or cfg.linear) and not cfg.q_chunk_size else {}),
    )
    called = set()
    for attr in dir(ServeEngine):
        fn = getattr(ServeEngine, attr)
        if attr.startswith("__") or not callable(fn) or isinstance(fn, type):
            continue

        def wrapped(*a, _fn=getattr(engine, attr), _name=attr, **kw):
            called.add(_name)
            return _fn(*a, **kw)

        setattr(engine, attr, wrapped)
    batcher = ContinuousBatcher(engine).start()
    rng = np.random.default_rng(0)
    lens = (9, 20 if cfg.sparse else 12)
    reqs = [batcher.submit(rng.integers(3, 64, n).tolist(), max_new_tokens=4) for n in lens]
    for r in reqs:
        assert r.wait(120) and r.error is None, r.error
    batcher.stop()
    return sorted(called)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_the_loop_calls_nothing_new(name):
    assert set(loop_calls(name)) <= set(PARENT_CALLS[name])


if __name__ == "__main__":
    print(json.dumps({name: digests(name) for name in sorted(CELLS)}, indent=1))
    print(json.dumps({name: loop_calls(name) for name in sorted(CELLS)}))
