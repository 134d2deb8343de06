"""The serve cells' programs, compiled whole for a described v5e
(``described_chip.py``) at published widths.

First the decode step and the insert of the dense cells (seconds each): what
they must not hold is a copy of the ring cache (ISSUE 29), and only the
compiled program says whether they do. Then each serve cell's decode step and
prefill with the bf16 tree the engine holds (``described_chip.engine_program``):
each (config, workload, program, bucket) is lowered and compiled once a
session, under ``for_the_chip``, and every test below that reads it reads that
one: which is why GLM-4.7-Flash's and ZAYA1's own tests of their prefill and
decode step live here and not with the other families
(``test_chip_compile_families.py``).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from described_chip import (
    BF16, COMPILES, HBM_BYTES, MOVES_NOTHING, RESULT, cache_shaped_results, engine_program,
    kernel_windows, leaf_shaped_casts, olmoe_cell, on_chip, program_bytes, ring_copies, serve_cell,
    top_level,
)

from opendiloco_tpu.models.ring_cache import cache_shape
from opendiloco_tpu.ops import decode_kernels

pytest_plugins = ("described_chip",)
pytestmark = pytest.mark.usefixtures("for_the_chip")


# ---------------------------------------------------------------------------
# the decode step and the insert of both serving cells: nothing of the ring
# cache's size, or of one layer's pages, is produced on the way (ISSUE 29)
# ---------------------------------------------------------------------------


def _batch_cell():
    """SmolLM2-360M's widths (15/5 heads of 64) at 8 of 32 layers, on the batch
    cell's engine: 256 slots of 256 rows, buckets 32 and 128."""
    return serve_cell("smollm2-360m", "serve-360m-batch", num_hidden_layers=8)


CELLS = {"smollm2-360m": _batch_cell, "olmoe-1b-7b": olmoe_cell}


def _serving_shapes(chip, cell):
    from opendiloco_tpu.models.llama import shapes

    cfg, engine = CELLS[cell]()
    slots, rows = engine["num_slots"], engine["max_context"]
    cache = jax.ShapeDtypeStruct(
        cache_shape(cfg.num_hidden_layers, slots, rows, cfg.kv_heads, cfg.head_dim),
        BF16, sharding=chip,
    )
    return cfg, engine, on_chip(chip, shapes(cfg)), cache


@pytest.mark.parametrize("cell", list(CELLS))
def test_decode_step_moves_no_cache(chip, cell):
    """The engine's ``_decode`` (kernel ``pallas``, caches donated) at the
    cell's slots and rows: the decode kernel is in it; its temporaries stay under the bf16
    copy of the weights plus one layer's pages; and no copy, transpose,
    scatter, slice, update or fresh buffer in it has the shape of the cache
    or of one layer's pages. The batch cell's plan holds several slots a grid
    step (ISSUE 50): its caches come back in ``pl.ANY``, written by the
    kernel's own copies, and alias their inputs all the same."""
    from opendiloco_tpu.models.llama import decode_forward

    cfg, engine, params, cache = _serving_shapes(chip, cell)
    plan = decode_kernels.decode_plan(
        cfg.kv_heads, cfg.head_dim, engine["max_context"], 2, num_slots=engine["num_slots"]
    )
    assert (plan.slots > 1) == (cell == "smollm2-360m")
    vec = jax.ShapeDtypeStruct((engine["num_slots"],), jnp.int32, sharding=chip)
    moe = bool(cfg.num_experts)
    compiled = (
        jax.jit(
            lambda p, tok, lens, ck, cv: decode_forward(
                p, tok, lens, ck, cv, cfg, decode_kernel="pallas", return_moe_counts=moe),
            donate_argnums=(3, 4),
        ).lower(params, vec, vec, cache, cache).compile()
    )
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "odtp_paged_decode_attn" in text and "tpu_custom_call" in text
    weights_bf16 = 2 * sum(x.size for x in jax.tree.leaves(params))
    layer_pages_bytes = 2 * 2 * cache.size // cache.shape[0]  # K and V, bf16
    assert mem.temp_size_in_bytes < weights_bf16 + layer_pages_bytes
    assert mem.alias_size_in_bytes >= 2 * 2 * cache.size  # both caches, in place
    assert not cache_shaped_results(text, cache.shape)


@pytest.mark.parametrize("cell", list(CELLS))
def test_insert_does_not_relay_the_cache(chip, cell):
    """``_insert`` at each of the cell's prefill buckets: a prompt's rows land
    in the cache's lane dimension, and the program still only updates the
    donated buffers (nothing page-sized but the update itself)."""
    from opendiloco_tpu.models.ring_cache import cache_insert

    cfg, engine, _, cache = _serving_shapes(chip, cell)
    layer_pages_bytes = 2 * 2 * cache.size // cache.shape[0]
    for bucket in engine["prefill_buckets"]:
        rows = jax.ShapeDtypeStruct(
            (cfg.num_hidden_layers, bucket, cfg.kv_heads, cfg.head_dim), BF16, sharding=chip
        )
        slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        compiled = (
            jax.jit(cache_insert, donate_argnums=(0, 1))
            .lower(cache, cache, rows, rows, slot).compile()
        )
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= 2 * 2 * cache.size
        assert mem.temp_size_in_bytes < layer_pages_bytes, (bucket, mem.temp_size_in_bytes)
        moved = [
            line for line in cache_shaped_results(compiled.as_text(), cache.shape)
            if "dynamic-update-slice" not in line
        ]
        assert not moved, (bucket, moved)


def test_the_engines_own_programs_move_no_cache_either(chip):
    """What the engine jits at the batch cell's shapes (``serving_programs``,
    ISSUE 38): the decode step that takes a fresh slot's token from the
    first-token vector, and the admission's insert that writes it there
    beside the prompt's rows. The select and the scalar write cost no copy:
    both caches alias, nothing cache- or page-shaped is moved, and the vector
    goes back in place."""
    from opendiloco_tpu.serve.engine import serving_programs

    cfg, engine, params, cache = _serving_shapes(chip, "smollm2-360m")
    _, decode, admit_insert, carried = serving_programs(
        cfg, compute_dtype=BF16, decode_kernel="pallas"
    )
    assert carried == 2
    vec = jax.ShapeDtypeStruct((engine["num_slots"],), jnp.int32, sharding=chip)
    compiled = (
        jax.jit(decode, donate_argnums=(4, 5))
        .lower(params, vec, vec, vec, cache, cache).compile()
    )
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "odtp_paged_decode_attn" in text and "tpu_custom_call" in text
    assert mem.alias_size_in_bytes >= 2 * 2 * cache.size  # both caches, in place
    assert not cache_shaped_results(text, cache.shape)
    layer_pages_bytes = 2 * 2 * cache.size // cache.shape[0]
    slot = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    tok = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=chip)
    for bucket in engine["prefill_buckets"]:
        rows = jax.ShapeDtypeStruct(
            (cfg.num_hidden_layers, bucket, cfg.kv_heads, cfg.head_dim), BF16, sharding=chip
        )
        compiled = (
            jax.jit(admit_insert, donate_argnums=(0, 1, 2))
            .lower(cache, cache, vec, rows, rows, tok, slot).compile()
        )
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= 2 * 2 * cache.size + 4 * vec.size
        assert mem.temp_size_in_bytes < layer_pages_bytes, (bucket, mem.temp_size_in_bytes)
        moved = [
            line for line in cache_shaped_results(compiled.as_text(), cache.shape)
            if "dynamic-update-slice" not in line
        ]
        assert not moved, (bucket, moved)


def test_the_decode_step_takes_the_token_in_flight_without_a_copy(chip):
    """The decode program as the engine jits it since ISSUE 48, at the batch
    cell's shapes: ``tokens`` selects between the host's token, the slot's
    entry of ``first`` and its entry of ``prev``, the step before's output.
    Both caches still alias, nothing cache- or page-shaped is moved, and the
    two vectors are read where they lie: no copy of a slots-long int32 but the
    prefetch of each argument into fast memory."""
    from opendiloco_tpu.serve.engine import serving_programs

    cfg, engine, params, cache = _serving_shapes(chip, "smollm2-360m")
    _, decode, _, carried = serving_programs(cfg, compute_dtype=BF16, decode_kernel="pallas")
    vec = jax.ShapeDtypeStruct((engine["num_slots"],), jnp.int32, sharding=chip)
    compiled = (
        jax.jit(decode, donate_argnums=tuple(range(4, 4 + carried)))
        .lower(params, vec, vec, vec, cache, cache, prev=vec).compile()
    )
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "odtp_paged_decode_attn" in text and "tpu_custom_call" in text
    assert mem.alias_size_in_bytes >= 2 * 2 * cache.size  # both caches, in place
    assert not cache_shaped_results(text, cache.shape)
    entry = text[text.index("ENTRY "):]
    slots_long = [
        line.strip()[:160] for line in entry.splitlines()
        if (m := RESULT.match(line)) and m.group(2) == "s32"
        and m.group(3) == str(engine["num_slots"])
    ]
    assert sum("parameter(" in line for line in slots_long) == 4  # tokens, lens, first, prev
    # each is prefetched into fast memory (``S(1)``) as ``tokens`` and ``first``
    # were, and nothing else of that length is copied anywhere
    moved = [line for line in slots_long if "copy" in line]
    assert len(moved) == 4 and all("S(1)} copy-done(" in line for line in moved), moved


# ---------------------------------------------------------------------------
# the serving weights bound once (ISSUE 31): given the tree as the engine holds
# it, the three cells' decode program and largest prefill cast no weight leaf
# ---------------------------------------------------------------------------

SERVE_CELLS = {
    "serve-360m-batch": "smollm2-360m",
    "serve-olmoe-fewshot": "olmoe-1b-7b",
    "serve-granite-h-docqa": "granite-4.0-h-small",
    "serve-zaya1-reason": "zaya1-8b",
}
ROUTED_CELLS = {
    "serve-olmoe-fewshot": "olmoe-1b-7b",
    "serve-granite-h-docqa": "granite-4.0-h-small",
    "serve-glm-flash-agent": "glm-4.7-flash",
    "serve-zaya1-reason": "zaya1-8b",
}


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("workload", list(SERVE_CELLS))
def test_serving_programs_cast_no_weights(chip, workload, program):
    """Each serve cell whole (``engine_program``): no cast of a weight leaf
    is left, the temporaries are a fraction of the weights (the per-call bf16
    copy was all of them: 0.73 / 3.63 / 3.83 GB in the three decode programs
    at PR 30), and a decode step still updates caches and state where they
    are."""
    compiled, cfg, params, carried = engine_program(
        chip, SERVE_CELLS[workload], workload, program
    )
    leaves = jax.tree.leaves(params)
    assert not leaf_shaped_casts(compiled.as_text(), {tuple(x.shape) for x in leaves})
    mem = compiled.memory_analysis()
    weights = sum(x.size * x.dtype.itemsize for x in leaves)
    assert mem.argument_size_in_bytes >= weights + carried
    # compiled here: decode 0.10 / 0.001 / 0.003 GB, prefill 0.10 / 1.25 / 0.62 GB
    # (batch / OLMoE / granite; ZAYA1, PR 37: see its own tests below), a
    # prefill's being its attention scores. Until
    # ISSUE 33 the OLMoE decode step's 0.27 GB and granite's 0.06 were one
    # layer's ``gate_proj``, cut out of its stack for the grouped matmul
    # (``test_serving_programs_copy_no_experts``)
    assert mem.temp_size_in_bytes < weights / (2 if program == "prefill" else 4)
    assert mem.alias_size_in_bytes >= carried
    assert program_bytes(compiled) < HBM_BYTES


# ---------------------------------------------------------------------------
# a whole-prompt prefill attends through the flash forward kernel (ISSUE 55):
# where ``decode_kernels.prefill_form`` says "flash" the program holds the
# kernel and no array over the bucket's positions twice (the scores of every
# head, [heads, P, P] in float32, were 0.86 GB of temporaries at OLMoE's 2,560
# and 0.31 GB at GLM's 1,792); the batch cell's buckets, under the floor, keep
# the XLA form
# ---------------------------------------------------------------------------


def _spans_twice(text: str, rows: int) -> list[str]:
    """The array shapes of a compiled text with ``rows`` in two dimensions."""
    shapes = set(re.findall(r"\b\w+\[([\d,]+)\]", text))
    return sorted(s for s in shapes if s.split(",").count(str(rows)) >= 2)


@pytest.mark.parametrize("workload,config,bucket", [
    ("serve-olmoe-fewshot", "olmoe-1b-7b", 3072),
    ("serve-glm-flash-agent", "glm-4.7-flash", 1792),
])
def test_a_whole_prompt_prefill_holds_the_flash_kernel_and_no_scores(
    chip, workload, config, bucket
):
    compiled, cfg, _, _ = engine_program(chip, config, workload, "prefill", bucket)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "odtp_flash_fwd" in text and "tpu_custom_call" in text
    assert not _spans_twice(text, bucket), _spans_twice(text, bucket)
    # compiled here: 0.115 GB (OLMoE, 0.86 at the parent's 2,560), 0.087 (GLM, 0.31)
    assert mem.temp_size_in_bytes < 0.15e9, mem.temp_size_in_bytes


@pytest.mark.parametrize("bucket", [32, 128])
def test_the_batch_cells_prefill_keeps_the_xla_form(chip, bucket):
    compiled, cfg, _, _ = engine_program(
        chip, "smollm2-360m", "serve-360m-batch", "prefill", bucket
    )
    text = compiled.as_text()
    assert "odtp_flash_fwd" not in text and "tpu_custom_call" not in text
    assert _spans_twice(text, bucket)  # the scores, written out: 15 heads x 128 x 128


# ---------------------------------------------------------------------------
# a routed layer's experts read where they lie (ISSUE 33): the three routed
# cells' decode step and largest prefill write out no layer's expert matrices,
# and their grouped matmuls take the whole stack
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("workload", list(ROUTED_CELLS))
def test_serving_programs_copy_no_experts(chip, workload, program):
    """``lax.ragged_dot`` is a custom call on the TPU, and a custom call's
    operand is a buffer of its own: handed a layer's experts as a slice of
    their stack, XLA wrote the slice out (``%dynamic-slice_bitcast_fusion``,
    three a layer a call, 0.8 GB in an OLMoE layer). Handed the stack and the
    layer's index (``llama.InStack``) each grouped matmul's weight operand is
    the stack itself, read as ``L * Eh`` groups; nothing that runs as an
    operation of its own yields an array of one layer's experts, in either
    orientation, but what moves nothing; and a decode step's temporaries are
    under one expert matrix of a layer."""
    from opendiloco_tpu.models.llama import EXPERT_LEAVES

    compiled, cfg, params, _ = engine_program(
        chip, ROUTED_CELLS[workload], workload, program
    )
    stacks = params["layers"] if cfg.layers_by_kind else {"attention": params["layers"]}
    experts = [  # every routed kind's [L, Eh, in, out] stacks
        stack[name].shape for stack in stacks.values() if "router" in stack
        for name in EXPERT_LEAVES
    ]
    assert experts and all(shape[1] == cfg.held_experts for shape in experts)
    text = compiled.as_text()
    instructions, _ = top_level(text)

    of_a_layer = {tuple(sorted(shape[1:])) for shape in experts}
    written = [
        line for opcode, _, shape, _, line in instructions
        if tuple(sorted(d for d in shape if d != 1)) in of_a_layer
        and not any(k in line for k in MOVES_NOTHING)
    ]
    assert not written, written

    results = {m.group(1): tuple(int(d) for d in m.group(3).split(",") if d)
               for m in map(RESULT.match, text.splitlines()) if m}
    stacks_as_groups = {(shape[0] * shape[1], *shape[2:]) for shape in experts}
    calls = re.findall(
        r"^\s*%ragged-dot-none[.\d]* = \S+ custom-call\((.*?)\), custom_call_target", text, re.M
    )
    assert len(calls) >= 3  # gate, up and down of a routed run's scan
    for operands in calls:  # the weights come last
        weight = operands.split(", ")[-1].split("*/")[-1]
        assert results[weight] in stacks_as_groups, (operands, results[weight])

    if program == "decode":
        matrix = min(2 * shape[1] * shape[2] * shape[3] for shape in experts)
        assert compiled.memory_analysis().temp_size_in_bytes < matrix


# ---------------------------------------------------------------------------
# the GLM-4.7-Flash cell (ISSUE 32; the latent decode kernel alone is
# ``test_chip_compile_kernels.py``'s ``test_mla_decode_attention``): the
# cell's largest prefill and its decode step whole, published widths, 24
# layers, with the bf16 tree the engine holds. They fit the chip beside what
# the engine keeps; the decode step reads the one latent ring where it lies,
# copies nothing of a layer's size and casts no weight
# ---------------------------------------------------------------------------


def _glm_cell(chip):
    """-> (configuration, the latent ring as a shape on the described chip)."""
    cfg, engine = serve_cell("glm-4.7-flash", "serve-glm-flash-agent")
    ring = jax.ShapeDtypeStruct(
        cache_shape(cfg.num_hidden_layers, engine["num_slots"], engine["max_context"],
                    1, cfg.latent_row_dim),
        BF16, sharding=chip,
    )
    return cfg, ring


def test_glm_prefill_program_at_the_largest_bucket(chip):
    """Bucket 1,792 in the rebuilt form: the grouped matmuls over the 8 held
    experts are in it, it casts no weight, its temporaries (since PR 55 without
    the scores of 20 heads over 1,792 x 1,792: the flash forward kernel) stay
    under half the weights, and it fits beside the resident ring."""
    cfg, ring = _glm_cell(chip)
    assert (cfg.leading_dense, cfg.held_experts, cfg.num_experts, cfg.latent_row_dim) == (1, 8, 64, 576)
    compiled, _, params, _ = engine_program(
        chip, "glm-4.7-flash", "serve-glm-flash-agent", "prefill"
    )
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "%ragged-dot" in text
    leaves = jax.tree.leaves(params)
    assert not leaf_shaped_casts(text, {tuple(x.shape) for x in leaves})
    weights = sum(x.size * x.dtype.itemsize for x in leaves)
    assert mem.temp_size_in_bytes < weights / 2
    assert program_bytes(compiled) + 2 * ring.size < HBM_BYTES


def test_glm_decode_step_reads_the_latent_ring_in_place(chip):
    """64 slots (or what the cell's file says): the latent kernel over the
    one ring, the grouped matmuls, no cast of a weight, the ring aliased to
    the output, and no copy, transpose, scatter, slice, update or fresh
    buffer of the ring's shape or of one layer's pages. Each call of the
    kernel reads ``576 x 512`` tiles and hands back the ``576 x 128`` block
    that holds the step's row, not the tile (PR 60)."""
    _, ring = _glm_cell(chip)
    compiled, _, params, carried = engine_program(
        chip, "glm-4.7-flash", "serve-glm-flash-agent", "decode"
    )
    assert carried == 2 * ring.size
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "odtp_mla_decode_attn" in text and "%ragged-dot" in text
    assert "odtp_paged_decode_attn" not in text
    calls = kernel_windows(text, "odtp_mla_decode_attn")
    assert calls and all(
        blocks[-3:] == [(1, 1, 1, 576, 512), (1, 20, 512), (1, 1, 1, 576, 128)] for blocks in calls
    )
    assert not ring_copies(text, ring.shape)
    leaves = jax.tree.leaves(params)
    assert not leaf_shaped_casts(text, {tuple(x.shape) for x in leaves})
    weights = sum(x.size * x.dtype.itemsize for x in leaves)
    assert mem.temp_size_in_bytes < weights / 4
    assert mem.alias_size_in_bytes >= 2 * ring.size
    assert program_bytes(compiled) < HBM_BYTES
    assert not cache_shaped_results(text, ring.shape)


# ---------------------------------------------------------------------------
# the ZAYA1-8B cell (ISSUE 37): its largest prefill and its decode step whole,
# published widths, 10 layers, with the bf16 tree the engine holds (the kernel
# alone at (2 KV heads, 128, ring 1,536) is ``test_paged_decode_attention``'s
# case; that neither program casts a weight or writes out a layer's experts
# are ``test_serving_programs_cast_no_weights`` / ``_copy_no_experts``' cases).
# The decode step moves no cache and carries CCA's per-slot state in place
# ---------------------------------------------------------------------------


def _zaya_cell(chip):
    """-> (configuration, engine options, a ring and the state as shapes)."""
    cfg, engine = serve_cell("zaya1-8b", "serve-zaya1-reason")
    slots, rows = engine["num_slots"], engine["max_context"]
    ring = jax.ShapeDtypeStruct(
        cache_shape(cfg.num_hidden_layers, slots, rows, cfg.kv_heads, cfg.head_dim),
        BF16, sharding=chip,
    )
    state = jax.ShapeDtypeStruct(
        (cfg.num_hidden_layers, slots, cfg.cca_state_dim), BF16, sharding=chip)
    return cfg, engine, ring, state


def test_zaya_prefill_program_at_the_largest_bucket(chip):
    """Bucket 1,024: the grouped matmuls over all 16 experts are in it, its
    temporaries (the scores of 8 heads over 1,024 x 1,024 and the head's
    logits aside) stay under a quarter of the weights, and it fits beside the
    resident rings and state."""
    cfg, engine, ring, state = _zaya_cell(chip)
    assert (cfg.head_dim, cfg.rotary_dim, cfg.kv_heads, cfg.held_experts) == (128, 64, 2, 16)
    assert cfg.cca_state_dim == 2688 and ring.shape[-3:] == (2, 128, engine["max_context"])
    compiled, _, params, _ = engine_program(chip, "zaya1-8b", "serve-zaya1-reason", "prefill")
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "%ragged-dot" in text
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == 5_225_940_328
    assert mem.temp_size_in_bytes < weights / 4
    resident = 2 * 2 * ring.size + 2 * state.size
    assert program_bytes(compiled) + resident < HBM_BYTES


def test_zaya_decode_step_carries_ring_and_state_in_place(chip):
    """128 slots (or what the cell's file says): the decode kernel under the
    plan for (2, 128, 1,536), the grouped matmuls, both rings and the state
    aliased to the outputs, no copy, transpose, scatter, slice, update or
    fresh buffer of a ring's shape or of one layer's pages, and of the
    state's shape nothing that runs as an operation of its own but the
    in-place update of a layer's rows. The tied table: what the step says of
    its re-order is recorded, not asserted away (ISSUE 37: removing it is a
    ``perf_opt`` of its own)."""
    cfg, engine, ring, state = _zaya_cell(chip)
    compiled, _, params, carried = engine_program(chip, "zaya1-8b", "serve-zaya1-reason", "decode")
    assert carried == 2 * 2 * ring.size + 2 * state.size
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "odtp_paged_decode_attn" in text and "%ragged-dot" in text
    plan = decode_kernels.decode_plan(2, 128, engine["max_context"], 2, interpret=False)
    assert plan.heads == 2 and engine["max_context"] % plan.block_t == 0
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert mem.temp_size_in_bytes < weights / 4
    assert mem.alias_size_in_bytes >= carried
    assert program_bytes(compiled) < HBM_BYTES
    assert not cache_shaped_results(text, ring.shape)
    # the state: written where it lies (a dynamic-update-slice, alone or as a
    # fusion's root), never copied, transposed or allocated anew
    instructions, roots = top_level(text)
    of_the_state = [
        (opcode, roots.get(called), line) for opcode, _, shape, called, line in instructions
        if shape == state.shape and not any(k in line for k in MOVES_NOTHING)
    ]
    assert all(
        "dynamic-update-slice" in (opcode, root) for opcode, root, _ in of_the_state
    ), of_the_state
    # the tied table [262272, 2048]: a copy of its size in this program is the
    # re-order PERF.md section 5 records for the batch cell (the head's matmul
    # reads the table in the other order of dimensions than the token gather)
    table = (cfg.vocab_size, cfg.hidden_size)
    reordered = [
        line for opcode, _, shape, _, line in instructions
        if opcode in ("copy", "transpose", "fusion") and tuple(sorted(shape)) == tuple(sorted(table))
    ]
    print(f"tied table re-ordered in the decode step: {len(reordered)} operation(s): {reordered}")
    assert len(reordered) <= 1


# ---------------------------------------------------------------------------
# the memo under all of the above (PR 57): last in the file, so that it sees
# what every reader before it asked for
# ---------------------------------------------------------------------------


def test_a_program_is_compiled_once_and_only_for_the_bucket_asked(chip):
    """``engine_program`` keeps to its key: two buckets of one cell are two
    programs, each over its own bucket's extent; asked again for either, or
    for the largest under no name, it hands back the same object and compiles
    nothing; and no key of this session was built twice."""
    cell = ("smollm2-360m", "serve-360m-batch")
    small, large = (engine_program(chip, *cell, "prefill", bucket)[0] for bucket in (32, 128))
    assert small is not large
    ids = re.compile(r"s32\[1,(\d+)\]")
    assert set(ids.findall(small.as_text())) == {"32"} and set(ids.findall(large.as_text())) == {"128"}
    before = dict(COMPILES)
    assert engine_program(chip, *cell, "prefill", 32)[0] is small
    assert engine_program(chip, *cell, "prefill", 128)[0] is large
    assert engine_program(chip, *cell, "prefill")[0] is large  # the largest, by either name
    assert COMPILES == before and set(COMPILES.values()) == {1}
    print(f"{len(COMPILES)} programs, each compiled once this session")
    assert {("_engine_program", *cell, "prefill", bucket) for bucket in (32, 128)} <= set(COMPILES)
