"""The architecture families' own programs, compiled whole for a described
v5e (``described_chip.py``) at published widths and the depth each cell runs:
OLMoE, granite-4.0-h, EvaByte, Keye-VL-2.0, dots3-note-prev, Laguna-S-2.1.
(GLM-4.7-Flash's and ZAYA1's read ``engine_program`` and live with its other
readers in ``test_chip_compile_serve_cells.py``.) A ``model_config`` PR's
whole-program compiles come here, each program through
``described_chip.once_a_session`` where more than one test reads it.
"""

import jax
import jax.numpy as jnp
import pytest
from described_chip import (
    BF16, HBM_BYTES, RESULT, bound, cache_shaped_results, f32_blocks_over, kernel_windows,
    leaf_shaped_casts, olmoe_cell, on_chip, once_a_session, program_bytes, ring_copies, serve_cell,
)

from opendiloco_tpu.models.ring_cache import cache_shape
from opendiloco_tpu.ops import decode_kernels

pytest_plugins = ("described_chip",)
pytestmark = pytest.mark.usefixtures("for_the_chip")


# ---------------------------------------------------------------------------
# the OLMoE serving cell's programs, at the published widths and the depth
# the cell runs (benchmark/configs/olmoe-1b-7b.json and its cell's file)
# ---------------------------------------------------------------------------


def test_olmoe_prefill_program_at_the_largest_bucket(chip):
    from opendiloco_tpu.models.llama import prefill_forward, shapes

    cfg, engine = olmoe_cell()
    bucket = max(engine["prefill_buckets"])
    compiled = (
        jax.jit(lambda p, ids, n: prefill_forward(
            p, ids, n, cfg, return_moe_counts=True))
        .lower(
            on_chip(chip, shapes(cfg)),
            jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=chip),
        ).compile()
    )
    cache = engine["num_slots"] * engine["max_context"] * 2 * 2 * (
        cfg.num_hidden_layers * cfg.kv_heads * cfg.head_dim
    )
    # the prefill runs beside the resident cache (the cell's ``sizing``)
    assert program_bytes(compiled) + cache < HBM_BYTES


def test_olmoe_decode_program_at_16_slots(chip):
    """16 slots, 16 KV heads of 128, the cell's rows a slot: the Pallas
    decode kernel and the grouped matmuls in one program that fits."""
    from opendiloco_tpu.models.llama import decode_forward, shapes

    cfg, engine = olmoe_cell()
    slots, rows = engine["num_slots"], engine["max_context"]
    cache = jax.ShapeDtypeStruct(
        cache_shape(cfg.num_hidden_layers, slots, rows, cfg.kv_heads, cfg.head_dim),
        BF16, sharding=chip,
    )
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
    compiled = (
        jax.jit(
            lambda p, tok, lens, ck, cv: decode_forward(
                p, tok, lens, ck, cv, cfg, decode_kernel="pallas", return_moe_counts=True),
            donate_argnums=(3, 4),
        ).lower(on_chip(chip, shapes(cfg)), vec, vec, cache, cache).compile()
    )
    text = compiled.as_text()
    assert "odtp_paged_decode_attn" in text and "%ragged-dot" in text
    assert program_bytes(compiled) < HBM_BYTES


# ---------------------------------------------------------------------------
# the granite-4.0-h cell (ISSUE 30): the prefill at its largest bucket and the
# decode step at its slots, published widths, ten layers; they fit the chip
# beside what the engine holds, and the recurrent state is updated where it is
# ---------------------------------------------------------------------------


def _granite_cell(chip):
    """-> (configuration, engine options, parameters, one cache array, the
    recurrent state, the conv tails), as shapes on the described chip."""
    from opendiloco_tpu.models import mamba
    from opendiloco_tpu.models.llama import shapes

    cfg, engine = serve_cell("granite-4.0-h-small", "serve-granite-h-docqa")
    slots, rows = engine["num_slots"], engine["max_context"]
    cache = jax.ShapeDtypeStruct(
        cache_shape(cfg.num_attention_layers, slots, rows, cfg.kv_heads, cfg.head_dim),
        BF16, sharding=chip,
    )
    ssm, conv = mamba.state_shapes(cfg, slots)
    return (
        cfg, engine, on_chip(chip, shapes(cfg)), cache,
        jax.ShapeDtypeStruct(ssm, jnp.float32, sharding=chip),
        jax.ShapeDtypeStruct(conv, BF16, sharding=chip),
    )


def test_granite_prefill_program_at_the_largest_bucket(chip):
    from opendiloco_tpu.models.llama import prefill_forward

    cfg, engine, params, cache, ssm, conv = _granite_cell(chip)
    assert (cfg.num_mamba_layers, cfg.num_attention_layers, cfg.held_experts) == (9, 1, 9)
    bucket = max(engine["prefill_buckets"])
    compiled = (
        jax.jit(lambda p, ids, n: prefill_forward(
            p, ids, n, cfg, return_moe_counts=True))
        .lower(
            params,
            jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=chip),
        ).compile()
    )
    assert "%ragged-dot" in compiled.as_text()
    # the prefill runs beside the resident ring, state and tails
    resident = 2 * 2 * cache.size + 4 * ssm.size + 2 * conv.size
    assert program_bytes(compiled) + resident < HBM_BYTES


def test_granite_decode_step_updates_the_state_in_place(chip):
    """32 slots: the Pallas decode kernel over the one attention layer's ring,
    the grouped matmuls over the 9 held experts, and the two runs of Mamba-2
    layers carrying 1.2 GB of recurrent state that is aliased to the output
    and never copied: the temporaries stay under the bf16 copy of the weights
    plus one layer's state, and no ``copy`` has the state's shape."""
    from opendiloco_tpu.models.llama import decode_forward

    cfg, engine, params, cache, ssm, conv = _granite_cell(chip)
    vec = jax.ShapeDtypeStruct((engine["num_slots"],), jnp.int32, sharding=chip)
    compiled = (
        jax.jit(
            lambda p, tok, lens, ck, cv, s, c: decode_forward(
                p, tok, lens, ck, cv, cfg, decode_kernel="pallas", return_moe_counts=True,
                ssm_state=s, conv_state=c),
            donate_argnums=(3, 4, 5, 6),
        ).lower(params, vec, vec, cache, cache, ssm, conv).compile()
    )
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "odtp_paged_decode_attn" in text and "%ragged-dot" in text
    assert program_bytes(compiled) < HBM_BYTES
    weights_bf16 = 2 * sum(x.size for x in jax.tree.leaves(params))
    layer_state = 4 * ssm.size // ssm.shape[0]
    assert mem.temp_size_in_bytes < weights_bf16 + layer_state
    assert mem.alias_size_in_bytes >= 2 * 2 * cache.size + 4 * ssm.size + 2 * conv.size
    state_dims = ",".join(str(d) for d in ssm.shape)
    layer_dims = ",".join(str(d) for d in ssm.shape[1:])
    copies = [
        line.strip()[:160] for line in text.splitlines()
        if " copy(" in line and (f"f32[{state_dims}]" in line or f"f32[{layer_dims}]" in line
                                 or f"f32[1,{layer_dims}]" in line)
    ]
    assert not copies, copies


# ---------------------------------------------------------------------------
# the EvaByte cell (ISSUE 40): 8 of 32 layers at published widths, 24 slots
# of a 2,048-row window ring beside a 384-row pooled ring, one bucket of
# 4,096. The decode step runs the decode kernel over both rings and moves
# neither; the prefill holds no score block wider than a window and the
# pooled rows before it
# ---------------------------------------------------------------------------


def _eva_cell(chip):
    """-> (configuration, engine options, the carried state as shapes: the
    window's ring twice, the pooled ring twice, the pooling's stats)."""
    from opendiloco_tpu.models.ring_cache import eva_pooled_rows

    cfg, engine = serve_cell("evabyte-6.5b", "serve-evabyte-complete")
    L, slots = cfg.num_hidden_layers, engine["num_slots"]
    ring = jax.ShapeDtypeStruct(
        cache_shape(L, slots, cfg.window_size, cfg.kv_heads, cfg.head_dim), BF16, sharding=chip)
    pooled = jax.ShapeDtypeStruct(
        cache_shape(L, slots, eva_pooled_rows(cfg, engine["max_context"]), cfg.kv_heads, cfg.head_dim),
        BF16, sharding=chip)
    stats = jax.ShapeDtypeStruct(
        (L, slots, cfg.kv_heads, 2 * cfg.head_dim + 2), jnp.float32, sharding=chip)
    return cfg, engine, (ring, ring, pooled, pooled, stats)


def test_eva_decode_step_moves_neither_ring(chip):
    """The engine's own decode program (``serving_programs``) at 24 slots: the
    decode kernel over the window's ring and its pooled form over the pooled
    ring are both in it; both rings and the stats alias the outputs; no copy,
    transpose, scatter, slice, update or fresh buffer has the shape of either
    ring or of one layer's pages; no weight is cast; arguments and temporaries
    fit the chip."""
    from opendiloco_tpu.serve.engine import serving_programs

    cfg, engine, carried = _eva_cell(chip)
    assert (cfg.kv_heads, cfg.head_dim, cfg.window_size, cfg.eva_chunks_per_window) == (32, 128, 2048, 128)
    assert carried[2].shape[-1] == 384
    params = bound(chip, cfg)
    _, decode, _, n = serving_programs(cfg, compute_dtype=BF16, decode_kernel="pallas")
    assert n == 5
    vec = jax.ShapeDtypeStruct((engine["num_slots"],), jnp.int32, sharding=chip)
    compiled = (
        jax.jit(decode, donate_argnums=tuple(range(4, 9)))
        .lower(params, vec, vec, vec, *carried).compile()
    )
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "odtp_paged_decode_attn" in text and "odtp_eva_pooled_attn" in text
    held = sum(x.size * x.dtype.itemsize for x in carried)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == 3_261_865_984 and held == 7_656_751_104
    print(f"eva decode: arguments {mem.argument_size_in_bytes} temporaries {mem.temp_size_in_bytes} "
          f"aliased {mem.alias_size_in_bytes} program {program_bytes(compiled):.0f}")
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < weights / 4
    assert program_bytes(compiled) < HBM_BYTES
    assert not cache_shaped_results(text, carried[0].shape)
    assert not cache_shaped_results(text, carried[2].shape)
    assert not leaf_shaped_casts(text, {tuple(x.shape) for x in jax.tree.leaves(params)})


@pytest.mark.parametrize("form", ["flash", "xla"])
def test_eva_prefill_holds_no_score_block_wider_than_a_window(chip, form, monkeypatch):
    """The 4,096 prefill: no array in it spans the bucket's positions twice
    over the heads (a [4096, 4096] score block a head). As the chip runs it
    (``eva_prefill_form``: "flash" at these shapes, whatever the decode
    kernel) each window's own rows go through the flash kernel and only the
    pooled rows before it are scored in XLA (128 columns); in the XLA form (a
    window no tile divides; forced here) the widest score block is a window's
    2,048 queries against 2,048 + 128 columns. It fits beside the resident
    rings, and its insert writes both rings in place."""
    from opendiloco_tpu.serve.engine import serving_programs

    cfg, engine, carried = _eva_cell(chip)
    assert decode_kernels.eva_prefill_form(cfg.window_size, cfg.head_dim) == "flash"
    if form == "xla":
        monkeypatch.setattr(decode_kernels, "eva_prefill_form", lambda *a, **kw: "xla")
    params = bound(chip, cfg)
    prefill, _, admit_insert, _ = serving_programs(cfg, compute_dtype=BF16, decode_kernel="xla")
    bucket = max(engine["prefill_buckets"])
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    compiled = (
        jax.jit(prefill)
        .lower(params, jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=chip), scalar)
        .compile()
    )
    text, mem = compiled.as_text(), compiled.memory_analysis()
    shapes_in = [
        tuple(int(d) for d in m.group(3).split(",") if d)
        for m in map(RESULT.match, text.splitlines()) if m
    ]
    # a head's scores: an array over the 32 heads with two dimensions of
    # positions (a window's 2,048 or more); none spans the bucket twice over
    scores = [s for s in shapes_in if cfg.num_attention_heads in s and sum(d >= 2048 for d in s) >= 2]
    assert all(max(s) <= cfg.window_size + cfg.eva_chunks_per_window for s in scores), scores
    assert ("odtp_flash_fwd" in text) == (form == "flash") == (not scores)
    held = sum(x.size * x.dtype.itemsize for x in carried)
    print(f"eva prefill ({form}): arguments {mem.argument_size_in_bytes} temporaries {mem.temp_size_in_bytes} "
          f"program {program_bytes(compiled):.0f}")
    assert program_bytes(compiled) + held < HBM_BYTES
    L, Nkv, Dh = cfg.num_hidden_layers, cfg.kv_heads, cfg.head_dim
    rows = jax.ShapeDtypeStruct((L, cfg.window_size, Nkv, Dh), BF16, sharding=chip)
    pooled = jax.ShapeDtypeStruct((L, bucket // cfg.chunk_size, Nkv, Dh), BF16, sharding=chip)
    chunk = jax.ShapeDtypeStruct((L, Nkv, 2 * Dh + 2), jnp.float32, sharding=chip)
    vec = jax.ShapeDtypeStruct((engine["num_slots"],), jnp.int32, sharding=chip)
    tok = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=chip)
    compiled = (
        jax.jit(admit_insert, donate_argnums=tuple(range(6)))
        .lower(carried[0], carried[1], vec, *carried[2:], rows, rows, pooled, pooled, chunk,
               tok, scalar).compile()
    )
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held
    layer_pages_bytes = 2 * 2 * carried[0].size // L
    assert mem.temp_size_in_bytes < layer_pages_bytes, mem.temp_size_in_bytes
    moved = [
        line for shape in (carried[0].shape, carried[2].shape)
        for line in cache_shaped_results(compiled.as_text(), shape)
        if "dynamic-update-slice" not in line
    ]
    assert not moved, moved


# ---------------------------------------------------------------------------
# Keye-VL-2.0 (PR 49): learned sparse attention. The decode step at 12 slots and
# the chunk program behind 15,872 rows (``plen`` is traced: one program for
# every chunk), published widths, 16 layers.
# ---------------------------------------------------------------------------


def _keye_cell(chip):
    """-> (configuration, engine options, the three rings as shapes: K and V
    rows minor-most as every configuration's, the index ring beside them)."""
    cfg, engine = serve_cell("keye-vl-2.0-30b-a3b", "serve-keye-videoqa")
    L, slots, rows = cfg.num_hidden_layers, engine["num_slots"], engine["max_context"]
    kv = jax.ShapeDtypeStruct(
        cache_shape(L, slots, rows, cfg.kv_heads, cfg.head_dim), BF16, sharding=chip)
    index = jax.ShapeDtypeStruct((L, slots, cfg.index_head_dim, rows), BF16, sharding=chip)
    return cfg, engine, (kv, kv, index)


def test_keye_decode_step_moves_no_ring(chip):
    """The engine's own decode program at 12 slots of 16,896 rows: the decode
    kernel under its selection operand and the index ring's column write are in
    it; the three rings alias the outputs; nothing has the shape of the K and V
    rings or of a layer's pages of them, nothing copies the index ring (a
    layer's 26 MB of index keys may be cut out for the scoring); no weight is
    cast; no float32 block over 256 MB; temporaries are a few megabytes."""
    from opendiloco_tpu.serve.engine import serving_programs

    cfg, engine, rings = _keye_cell(chip)
    assert (cfg.kv_heads, cfg.head_dim, cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        4, 128, 16, 64, 2048)
    params = bound(chip, cfg)
    _, decode, _, n = serving_programs(cfg, compute_dtype=BF16, decode_kernel="pallas")
    assert n == 3
    vec = jax.ShapeDtypeStruct((engine["num_slots"],), jnp.int32, sharding=chip)
    compiled = (
        jax.jit(decode, donate_argnums=(4, 5, 6)).lower(params, vec, vec, vec, *rings).compile()
    )
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "odtp_paged_decode_attn" in text and "odtp_index_ring_write" in text
    held = sum(x.size * x.dtype.itemsize for x in rings)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == 3_256_369_152 and held == 7_059_013_632
    print(f"keye decode: arguments {mem.argument_size_in_bytes} temporaries {mem.temp_size_in_bytes} "
          f"aliased {mem.alias_size_in_bytes} program {program_bytes(compiled):.0f}")
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 64e6
    assert program_bytes(compiled) < HBM_BYTES
    assert not cache_shaped_results(text, rings[0].shape)
    assert not ring_copies(text, rings[2].shape)
    assert not leaf_shaped_casts(text, {tuple(x.shape) for x in jax.tree.leaves(params)})
    assert not f32_blocks_over(text, 256e6)


def test_keye_chunk_program_writes_its_rows_in_place(chip):
    """The chunk program (512 queries; ``plen``, ``count`` and ``slot``
    traced, so this is the program behind 15,872 rows too): the three rings
    alias the outputs and are updated by slice updates alone, no copy of a
    ring's size (a first form whose K and V pages kept a row contiguous was
    re-laid rows minor-most by the compiler, 3.09 GB a ring, and did not fit
    the chip); the index scores and the attention's tiles are the only large
    float32 blocks and stay under 256 MB; no weight is cast. Asked for the
    kernels as the engine asks on the chip, the chunk's attention stays the
    tiled XLA form: 32 heads x 512 queries x 512 rows of float32 scores are
    33.5 MB, under the line of ``chunk_form``."""
    from opendiloco_tpu.serve.engine import chunk_program

    cfg, engine, rings = _keye_cell(chip)
    params = bound(chip, cfg)
    vec = jax.ShapeDtypeStruct((engine["num_slots"],), jnp.int32, sharding=chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    ids = jax.ShapeDtypeStruct((1, cfg.q_chunk_size), jnp.int32, sharding=chip)
    last = jax.ShapeDtypeStruct((), jnp.bool_, sharding=chip)
    compiled = (
        jax.jit(chunk_program(cfg, compute_dtype=BF16, decode_kernel="pallas"),
                donate_argnums=(6, 7, 8, 9))
        .lower(params, ids, scalar, scalar, scalar, last, vec, *rings).compile()
    )
    text, mem = compiled.as_text(), compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in rings)
    print(f"keye chunk: arguments {mem.argument_size_in_bytes} temporaries {mem.temp_size_in_bytes} "
          f"aliased {mem.alias_size_in_bytes} program {program_bytes(compiled):.0f}")
    assert mem.alias_size_in_bytes >= held == 7_059_013_632
    assert mem.temp_size_in_bytes < 512e6
    assert program_bytes(compiled) < HBM_BYTES
    assert not ring_copies(text, rings[0].shape) and not ring_copies(text, rings[2].shape)
    assert not leaf_shaped_casts(text, {tuple(x.shape) for x in jax.tree.leaves(params)})
    assert not f32_blocks_over(text, 256e6)
    assert "f32[512,16896]" in text  # a chunk's index scores: 35 MB, never a block a head
    assert "odtp_chunk_attn" not in text


def test_keye_index_ring_write_kernel(chip):
    """``odtp_index_ring_write`` alone at the cell's shapes: a grid step a
    layer and slot, the ring aliased through."""
    _, engine, rings = _keye_cell(chip)
    L, S, di, _ = rings[2].shape
    keys = jax.ShapeDtypeStruct((L, S, di), BF16, sharding=chip)
    lens = jax.ShapeDtypeStruct((S,), jnp.int32, sharding=chip)
    compiled = (
        jax.jit(decode_kernels.index_ring_write, donate_argnums=(0,))
        .lower(rings[2], keys, lens).compile()
    )
    assert "odtp_index_ring_write" in compiled.as_text()
    assert compiled.memory_analysis().alias_size_in_bytes >= rings[2].size * 2
    assert not ring_copies(compiled.as_text(), rings[2].shape)


# --- dots3-note-prev: two kinds of latent attention, three rings (PR 54) ----


def _dots3_cell(chip):
    """-> (configuration, engine options, the three rings as shapes: the full
    layers' latent ring, the sliding layers' ring that wraps, the index ring)."""
    from opendiloco_tpu.models.ring_cache import sliding_ring_rows

    cfg, engine = serve_cell("dots3-note-prev", "serve-dots3-notes")
    slots, rows = engine["num_slots"], engine["max_context"]
    full = jax.ShapeDtypeStruct(
        cache_shape(cfg.num_full_layers, slots, rows, 1, cfg.latent_row_dim), BF16, sharding=chip)
    sliding = jax.ShapeDtypeStruct(
        cache_shape(cfg.num_sliding_layers, slots, sliding_ring_rows(cfg), 1, cfg.sliding_row_dim),
        BF16, sharding=chip)
    index = jax.ShapeDtypeStruct(
        (cfg.num_full_layers, slots, cfg.index_head_dim, rows), BF16, sharding=chip)
    return cfg, engine, (full, sliding, index)


@once_a_session
def _dots3_program(chip, which):
    from opendiloco_tpu.serve.engine import chunk_program, serving_programs

    cfg, engine, rings = _dots3_cell(chip)
    params = bound(chip, cfg)
    vec = jax.ShapeDtypeStruct((engine["num_slots"],), jnp.int32, sharding=chip)
    if which == "decode":
        _, decode, _, n = serving_programs(cfg, compute_dtype=BF16, decode_kernel="pallas")
        assert n == 3
        lowered = jax.jit(decode, donate_argnums=(4, 5, 6)).lower(params, vec, vec, vec, *rings)
    else:
        scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        ids = jax.ShapeDtypeStruct((1, cfg.q_chunk_size), jnp.int32, sharding=chip)
        last = jax.ShapeDtypeStruct((), jnp.bool_, sharding=chip)
        lowered = jax.jit(
            chunk_program(cfg, compute_dtype=BF16, decode_kernel="pallas"),
            donate_argnums=(6, 7, 8, 9),
        ).lower(params, ids, scalar, scalar, scalar, last, vec, *rings)
    return cfg, params, rings, lowered.compile()


@pytest.mark.parametrize("which", ["decode", "chunk"])
def test_dots3_programs_copy_no_ring_and_cast_no_weight(chip, which):
    """The engine's decode and chunk programs for dots3-note-prev at 12 slots
    of 25,088 rows, published widths: 4,087,154,176 parameters held once in
    bf16; the three rings (the full layers' 576-wide latent ring, the sliding
    layers' 1,088-wide ring of 1,024 rows, the index ring) alias the outputs
    and none is copied; no weight is cast; the program fits the chip. The
    decode step holds ``odtp_mla_decode_attn`` (under the selection and under
    the window: the kernel, not its XLA form) and the index ring's column
    write; the chunk holds its index scores [512, 25088] once and no float32
    block over 512 MB."""
    cfg, params, rings, compiled = _dots3_program(chip, which)
    assert (cfg.num_full_layers, cfg.num_sliding_layers, cfg.latent_row_dim, cfg.sliding_row_dim) == (
        2, 3, 576, 1088)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in rings)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == 8_174_308_352 and held == 927_989_760
    print(f"dots3 {which}: arguments {mem.argument_size_in_bytes} temporaries "
          f"{mem.temp_size_in_bytes} aliased {mem.alias_size_in_bytes} "
          f"program {program_bytes(compiled):.0f}")
    assert mem.alias_size_in_bytes >= held
    assert program_bytes(compiled) < HBM_BYTES
    for ring in rings:
        assert not ring_copies(text, ring.shape), which
    assert not leaf_shaped_casts(text, {tuple(x.shape) for x in jax.tree.leaves(params)})
    if which == "decode":
        assert "odtp_mla_decode_attn" in text and "odtp_index_ring_write" in text
        # what a slot's step hands back of either latent ring: the 128 rows
        # that hold its row, of a tile of 512 (PR 60)
        assert {blocks[-1] for blocks in kernel_windows(text, "odtp_mla_decode_attn")} == {
            (1, 1, 1, 576, 128), (1, 1, 1, 1088, 128)}
        assert mem.temp_size_in_bytes < 256e6
        assert not f32_blocks_over(text, 256e6)
    else:
        assert "f32[512,25088]" in text and "odtp_chunk_attn" not in text  # latent rows: their own kernel
        assert mem.temp_size_in_bytes < 2e9
        assert not f32_blocks_over(text, 512e6)


def test_dots3_chunk_runs_its_full_layers_through_the_latent_kernel(chip):
    """The notes cell's chunk program with the kernels as the engine asks on
    the chip (PR 63): both runs of full layers attend through
    ``odtp_latent_chunk_attn`` (8 heads' 512 absorbed queries of 576 values, a
    tile of 512 rows of the page, the selection's int8 tile, 8 heads' 512
    outputs of 512 a grid step), so neither the float32 score tile [512, 128,
    512] nor the running sum of the same shape (134 MB each) is anywhere in
    the program; the sliding layers keep the XLA form (their tile, 64 heads:
    67 MB); the temporaries are 482 MB where the XLA form's were 610."""
    _, _, _, compiled = _dots3_program(chip, "chunk")
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert kernel_windows(text, "odtp_latent_chunk_attn") == [
        [(8, 512, 576), (576, 512), (512, 512), (8, 512, 512)]] * 2
    assert "f32[512,128,512]" not in text and "f32[512,64,512]" in text
    assert mem.temp_size_in_bytes < 500e6


# --- Laguna-S-2.1: two kinds of grouped-query attention, rings by kind (PR 56) ----


@once_a_session
def _laguna_program(chip, which):
    import dataclasses

    from opendiloco_tpu.models.ring_cache import init_kv_cache
    from opendiloco_tpu.serve.engine import chunk_program, serving_programs

    cfg, engine = serve_cell("laguna-s-2.1", "serve-laguna-repoedit")
    cfg = dataclasses.replace(cfg, q_chunk_size=engine["prefill_chunk"])  # as the engine lays it
    cache = jax.eval_shape(
        lambda: init_kv_cache(cfg, engine["num_slots"], engine["max_context"], BF16))
    rings = on_chip(chip, (cache["k"], cache["v"]))
    params = bound(chip, cfg)
    vec = jax.ShapeDtypeStruct((engine["num_slots"],), jnp.int32, sharding=chip)
    if which == "decode":
        _, decode, _, n = serving_programs(cfg, compute_dtype=BF16, decode_kernel="pallas")
        assert n == 2
        lowered = jax.jit(decode, donate_argnums=(4, 5)).lower(params, vec, vec, vec, *rings)
    else:
        scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        ids = jax.ShapeDtypeStruct((1, cfg.q_chunk_size), jnp.int32, sharding=chip)
        last = jax.ShapeDtypeStruct((), jnp.bool_, sharding=chip)
        lowered = jax.jit(
            chunk_program(cfg, compute_dtype=BF16, decode_kernel="pallas"),
            donate_argnums=(6, 7, 8, 9),
        ).lower(params, ids, scalar, scalar, scalar, last, vec, *rings, None)
    return cfg, params, rings, lowered.compile()


@pytest.mark.parametrize("which", ["decode", "chunk"])
def test_laguna_programs_copy_no_ring_and_cast_no_weight(chip, which):
    """The engine's decode and chunk programs for Laguna-S-2.1 at 12 slots of
    18,432 rows under chunks of 2,048, published widths: 5,034,052,608
    parameters held once in bf16; the four rings (the full layers' K and V as
    long as the context, the sliding layers' of 4,096 rows that wrap) alias the
    outputs and none is copied; no weight is cast; the program fits the chip.
    The decode step holds ``odtp_paged_decode_attn`` for both kinds (under the
    window for the sliding layers: the kernel, not its XLA form); the chunk
    holds no float32 block over 512 MB (no [72, 2048, 4096] scores: the band's
    blocks) and its full layers run ``odtp_chunk_attn`` (PR 62: 512 queries of
    a KV head's 6 heads over 1,024 rows a grid step, and no [8, 6, 2048, 512]
    float32 tile of scores, 201 MB, in memory)."""
    cfg, params, rings, compiled = _laguna_program(chip, which)
    assert (cfg.num_full_layers, cfg.num_sliding_layers) == (2, 6)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    flat = jax.tree.leaves(rings)
    held = sum(x.size * x.dtype.itemsize for x in flat)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == 10_068_105_216 and held == 1_811_939_328 + 1_207_959_552
    print(f"laguna {which}: arguments {mem.argument_size_in_bytes} temporaries "
          f"{mem.temp_size_in_bytes} aliased {mem.alias_size_in_bytes} "
          f"program {program_bytes(compiled):.0f}")
    assert mem.alias_size_in_bytes >= held
    assert program_bytes(compiled) < HBM_BYTES
    for ring in flat:
        assert not ring_copies(text, ring.shape), which
    assert not leaf_shaped_casts(text, {tuple(x.shape) for x in jax.tree.leaves(params)})
    if which == "decode":
        assert text.count("odtp_paged_decode_attn") >= 2
        assert mem.temp_size_in_bytes < 512e6  # the dense layer's FFN, cut from its stack of one
        assert not f32_blocks_over(text, 256e6)
    else:
        assert mem.temp_size_in_bytes < 2.5e9
        assert not f32_blocks_over(text, 512e6)
        assert "f32[8,6,2048,512]" not in text
        assert {tuple(b[:4]) for b in kernel_windows(text, "odtp_chunk_attn")} == {
            ((1, 6, 512, 128), (512, 1), (1, 128, 1024), (1, 128, 1024))}


# --- MiniCPM-SALA: lightning states beside a selection by blocks (PR 61) ----------


@once_a_session
def _sala_program(chip, which):
    import dataclasses

    from opendiloco_tpu.models.ring_cache import (
        init_kv_cache, init_lightning_state, init_pooled_cache,
    )
    from opendiloco_tpu.serve.engine import serving_programs, state_chunk_program

    cfg, engine = serve_cell("minicpm-sala", "serve-sala-longdoc")
    cfg = dataclasses.replace(cfg, q_chunk_size=engine["prefill_chunk"])  # as the engine lays it
    slots, rows = engine["num_slots"], engine["max_context"]
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, slots, rows, BF16))
    rings = on_chip(chip, (
        cache["k"], cache["v"],
        jax.eval_shape(lambda: init_pooled_cache(cfg, slots, rows, BF16)),
        jax.eval_shape(lambda: init_lightning_state(cfg, slots)),
    ))
    params = bound(chip, cfg)
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
    if which == "decode":
        _, decode, _, n = serving_programs(cfg, compute_dtype=BF16, decode_kernel="pallas")
        assert n == 4
        lowered = jax.jit(decode, donate_argnums=(4, 5, 6, 7)).lower(params, vec, vec, vec, *rings)
    else:
        scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        ids = jax.ShapeDtypeStruct((1, cfg.q_chunk_size), jnp.int32, sharding=chip)
        last = jax.ShapeDtypeStruct((), jnp.bool_, sharding=chip)
        lowered = jax.jit(
            state_chunk_program(cfg, compute_dtype=BF16, decode_kernel="pallas"),
            donate_argnums=(7, 8, 9, 10, 11),
        ).lower(params, ids, scalar, scalar, scalar, scalar, last, vec, *rings)
    return cfg, params, rings, lowered.compile()


@pytest.mark.parametrize("which", ["decode", "chunk"])
def test_sala_programs_copy_no_ring_and_no_state_and_cast_no_weight(chip, which):
    """The engine's decode and chunk programs for MiniCPM-SALA at 12 slots of
    34,816 rows under chunks of 2,048, published widths, 18 of 32 layers:
    5,609,898,496 parameters held once in bf16; the K and V rings of the four
    sparse layers, their pooled-key ring and the fourteen lightning layers'
    float32 states alias the outputs and none is copied; no weight is cast; the
    program fits the chip. The decode step holds ``odtp_block_decode_attn`` (the
    kernel over the chosen blocks' tiles, a tile of 128 rows of one KV head a
    grid step under its 16 query heads) and the rings' writers behind the
    layers; the chunk's temporaries stay under 1 GB (a head group's scores over
    the pooled keys are 71 MB, a layer's choice a row in int8 142 MB) and its
    attention is ``odtp_chunk_attn`` (PR 62: 512 queries of a KV head's 16 heads
    over 1,024 rows and their choice a grid step; no [2, 16, 2048, 512] float32
    tile of scores, 134 MB, in memory)."""
    cfg, params, rings, compiled = _sala_program(chip, which)
    assert (cfg.num_attention_layers, cfg.num_lightning_layers) == (4, 14)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in rings)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == 11_219_796_992
    assert held == 2 * 855_638_016 + 53_477_376 + 352_321_536 == 12 * 176_422_912
    print(f"sala {which}: arguments {mem.argument_size_in_bytes} temporaries "
          f"{mem.temp_size_in_bytes} aliased {mem.alias_size_in_bytes} "
          f"program {program_bytes(compiled):.0f}")
    assert mem.alias_size_in_bytes >= held
    assert program_bytes(compiled) < HBM_BYTES
    for ring in rings:
        assert not ring_copies(text, ring.shape), which
    assert not leaf_shaped_casts(text, {tuple(x.shape) for x in jax.tree.leaves(params)})
    if which == "decode":
        assert "odtp_block_decode_attn" in text and text.count("odtp_index_ring_write") >= 3
        blocks = kernel_windows(text, "odtp_block_decode_attn")
        assert {b[1] for b in blocks} == {(1, 1, 1, 128, 128)} and {b[0] for b in blocks} == {(1, 1, 16, 128)}
        assert "odtp_ring_rows_sum" in text  # the closing window's rows, no gather of the ring
        assert mem.temp_size_in_bytes < 256e6
    else:
        # (the temporaries and not ``f32_blocks_over``: the float32 states are updated in
        # place at their own shape, and a fusion's inside holds the head widened for one row)
        assert mem.temp_size_in_bytes < 1e9
        assert "f32[2,16,2048,512]" not in text
        assert {tuple(b[:5]) for b in kernel_windows(text, "odtp_chunk_attn")} == {
            ((1, 16, 512, 128), (512, 1), (1, 128, 1024), (1, 128, 1024), (1, 512, 1024))}


# --- Solar-Open2: kda states and tails beside one (k, v) ring, 40 of 320 experts (PR 64) ---


@once_a_session
def _solar2_program(chip, which):
    import dataclasses

    from opendiloco_tpu.models.ring_cache import init_kda_state, init_kv_cache
    from opendiloco_tpu.serve.engine import kda_chunk_program, serving_programs

    cfg, engine = serve_cell("solar-open2-250b", "serve-solar2-reason")
    cfg = dataclasses.replace(cfg, q_chunk_size=engine["prefill_chunk"])  # as the engine lays it
    slots, rows = engine["num_slots"], engine["max_context"]
    cache = jax.eval_shape(lambda: init_kv_cache(cfg, slots, rows, BF16))
    state = jax.eval_shape(lambda: init_kda_state(cfg, slots, BF16))
    rings = on_chip(chip, (cache["k"], cache["v"], state["state"], state["tail"]))
    params = bound(chip, cfg)
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
    if which == "decode":
        _, decode, _, n = serving_programs(cfg, compute_dtype=BF16, decode_kernel="pallas")
        assert n == 4
        lowered = jax.jit(decode, donate_argnums=(4, 5, 6, 7)).lower(params, vec, vec, vec, *rings)
    else:
        scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
        ids = jax.ShapeDtypeStruct((1, cfg.q_chunk_size), jnp.int32, sharding=chip)
        last = jax.ShapeDtypeStruct((), jnp.bool_, sharding=chip)
        lowered = jax.jit(
            kda_chunk_program(cfg, compute_dtype=BF16, decode_kernel="pallas"),
            donate_argnums=(6, 7, 8, 9, 10),
        ).lower(params, ids, scalar, scalar, scalar, last, vec, *rings)
    return cfg, params, rings, lowered.compile()


@pytest.mark.parametrize("which", ["decode", "chunk"])
def test_solar2_programs_copy_no_ring_no_state_no_tail_and_cast_no_weight(chip, which):
    """The engine's decode and chunk programs for Solar-Open2-250B at 128 slots
    of 5,120 rows under chunks of 2,048, published widths, 4 of 48 layers with
    40 of 320 experts: 3,308,377,920 parameters held once in bf16; the gqa
    layer's K and V ring, the three kda layers' float32 states and their
    convolutions' tails alias the outputs and none is copied; no weight is
    cast; no layer's experts are cut out of their stack; the program fits the
    chip. The decode step holds ``odtp_paged_decode_attn`` (a slot a grid step:
    a slot at ``lens`` 0 is written nothing) and ``odtp_kda_step`` (a slot's
    block of heads a grid step, its states aliased in and out of the stack,
    no layer's states beside them); the chunk's gqa attention is
    ``odtp_chunk_attn`` (no [8, 8, 2048, 512] float32 tile of scores, 268 MB,
    in memory)."""
    cfg, params, rings, compiled = _solar2_program(chip, which)
    assert (cfg.num_attention_layers, cfg.num_kda_layers) == (1, 3)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in rings)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == 6_616_755_840
    assert held == 128 * 33_996_800
    print(f"solar2 {which}: arguments {mem.argument_size_in_bytes} temporaries "
          f"{mem.temp_size_in_bytes} aliased {mem.alias_size_in_bytes} "
          f"program {program_bytes(compiled):.0f}")
    assert mem.alias_size_in_bytes >= held
    assert program_bytes(compiled) < HBM_BYTES
    whole = {line.split(" = ")[0].strip(): line for line in text.splitlines() if " = " in line}
    for ring in rings:
        # (the step's shift of the tails is a slice update in place under a plain fusion's name)
        assert not [line for line in ring_copies(text, ring.shape)
                    if 'odtp_kda_conv/dynamic_update_slice"' not in whole[line.split(" = ")[0].strip()]], which
    assert not leaf_shaped_casts(text, {tuple(x.shape) for x in jax.tree.leaves(params)})
    assert not [line for line in text.splitlines()
                if "dynamic-slice_bitcast_fusion" in line and "bf16[40,4096,1280]" in line]
    if which == "decode":
        assert "odtp_paged_decode_attn" in text
        assert mem.temp_size_in_bytes < 1e9
        # the kda layers' step (PR 65): one kernel inside the scan over the three layers,
        # 16 heads of a slot's states in and out a grid step beside their five rows a head;
        # nothing of a layer's states' size, 537 MB, is cut out, copied or kept beside it
        assert kernel_windows(text, "odtp_kda_step") == [[
            (1, 5, 1, 16, 128), (1, 1, 16, 128, 128), (1, 1, 16, 128), (1, 1, 16, 128, 128)]]
        assert "f32[128,64,128,128]" not in text
        assert all("get-tuple-element(" in line for line in f32_blocks_over(text, 256e6))  # the stack handed on
    else:
        assert mem.temp_size_in_bytes < 3e9
        assert "f32[8,8,2048,512]" not in text
        assert kernel_windows(text, "odtp_chunk_attn")
        assert "odtp_kda_step" not in text  # the chunk's form is the chunked one
