"""The main path's Pallas kernels, compiled for the chip without the chip.

Interpret-mode parity (test_attention, test_decode_kernels) cannot see what
the TPU's own compiler refuses: a slice Mosaic cannot lay out, a scalar
store to VMEM, a tile that outgrows VMEM. Every kernel variant the code can
call is lowered AND compiled for a described v5e (``described_chip.py``) at
published widths in bf16, and must come out as a ``tpu_custom_call``: the
kernel, not its XLA stand-in. So must OLMoE's routed FFN: the TPU's own
grouped matmul.

Training's step is compiled at the two training cells' block shapes over a cut
of the layers (a quarter and half a minute): what it must hold is each
attention kernel once (ISSUE 41). The whole-depth compiles (a minute each)
stay in the builder's rehearsal.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from described_chip import (
    BF16, compiled_text, flash_kernels, kernel_windows, narrow_kernel_arrays, olmoe_cell, ring_copies,
)

from opendiloco_tpu.models.ring_cache import cache_shape, layer_rows_insert, slot_layer_pages
from opendiloco_tpu.ops import decode_kernels
from opendiloco_tpu.ops.attention import tiled_sparse_attention
from opendiloco_tpu.ops.decode_kernels import paged_decode_attention
from opendiloco_tpu.ops.flash_attention import flash_attention
from opendiloco_tpu.ops.fused_xent import fused_linear_cross_entropy

pytest_plugins = ("described_chip",)
pytestmark = pytest.mark.usefixtures("for_the_chip")

SEQ = 1024
# (query heads, kv heads, head_dim) of the configs the repo ships
HEADS = {"150m": (16, 16, 64), "1b": (32, 4, 64)}
# and of the two serving cells whose configurations the continued prefill
# takes (keys-and-values rows, no state beside them)
TAIL_HEADS = {**HEADS, "360m": (15, 5, 64), "olmoe": (16, 16, 128)}


@pytest.mark.parametrize("model", list(HEADS))
def test_flash_attention_fwd_bwd(chip, model):
    hq, hkv, d = HEADS[model]

    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    text = compiled_text(
        chip,
        jax.grad(loss, argnums=(0, 1, 2)),
        ((2, SEQ, hq, d), BF16),
        ((2, SEQ, hkv, d), BF16),
        ((2, SEQ, hkv, d), BF16),
    )
    # forward, dq and dk/dv kernels are all in the program
    assert text.count("tpu_custom_call") >= 3


# (batch a chip, seq, query heads, kv heads, head size) the training kernels
# meet in the benchmark: both train cells' shapes, and the forward alone at
# EvaByte's prefill (two windows of 2,048 as a batch, heads of 128)
FLASH_CELLS = {
    "train-360m-h16": (8, 2048, 15, 5, 64),
    "train-1.7b-fsdp4-h8": (4, 2048, 32, 32, 64),
    "serve-evabyte-complete": (2, 2048, 32, 32, 128),
}


@pytest.mark.parametrize("cell", list(FLASH_CELLS))
def test_flash_kernels_compile_at_the_cells_shapes(chip, cell):
    """The kernels over rows ``[B, T, H * D]`` (the heads a grid step holds cut
    out of a tile in VMEM, rotary on the way, the sub-tile walk at 1,024-row
    blocks) compile for the chip, all three at the train cells' shapes, and
    the forward, with its log-sum-exp, at the serve cell's; no operand or
    result of a call has fewer than 128 minor lanes but the rotary tables."""
    from opendiloco_tpu.ops.flash_attention import Rope, flash_attention_lse, lanes_of

    b, t, hq, hkv, d = FLASH_CELLS[cell]
    if cell.startswith("serve"):
        shapes = (((b, t, hq, d), BF16), ((b, t, hkv, d), BF16), ((b, t, hkv, d), BF16))
        text = compiled_text(chip, functools.partial(flash_attention_lse, interpret=False), *shapes)
        assert flash_kernels(text) == ["odtp_flash_fwd"]
        return

    def loss(q, k, v, cos, sin):
        out = flash_attention(q, k, v, head_dim=d, rope=Rope(cos, sin, d))
        return out.astype(jnp.float32).sum()

    shapes = (((b, t, hq * d), BF16), ((b, t, hkv * d), BF16), ((b, t, hkv * d), BF16))
    tables = (((b, t, lanes_of(d)[1]), jnp.float32),) * 2
    text = compiled_text(chip, jax.grad(loss, argnums=(0, 1, 2)), *shapes, *tables)
    assert flash_kernels(text) == ["odtp_flash_dkv", "odtp_flash_dq", "odtp_flash_fwd"]
    assert narrow_kernel_arrays(text) == []


def test_ring_flash_chunks_compile(topo):
    """Ring attention over four chips, the flash-chunk form: the diagonal
    chunk through the causal kernels (the walk, float32 gradients, a
    ``vma``), the chunks before it through the unmasked ones. Over a mesh
    whose one axis is the ring's: under the trainer's four-axis mesh the
    region is manual over ``sp`` alone and Mosaic refuses the kernel
    ("cannot be automatically partitioned"), before PR 42 as after it
    (PERF.md section 7)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from opendiloco_tpu.ops import ring_attention as ra

    mesh = Mesh(np.asarray(topo.devices[:4]), ("sp",))
    assert ra._flash_chunk_block(mesh, "sp", jax.ShapeDtypeStruct((2, 8192, 4, 64), BF16), True) == 1024

    def loss(q, k, v):
        return ra.ring_attention_auto(q, k, v, mesh=mesh, axis="sp").astype(jnp.float32).sum()

    on_ring = NamedSharding(mesh, P(None, "sp", None, None))
    args = [
        jax.ShapeDtypeStruct((2, 8192, h, 64), BF16, sharding=on_ring) for h in (4, 2, 2)
    ]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*args).compile().as_text()
    # causal and full forms of each kernel: six calls, three names
    assert sorted(set(flash_kernels(text))) == ["odtp_flash_dkv", "odtp_flash_dq", "odtp_flash_fwd"]
    assert len(flash_kernels(text)) >= 6


# the training cells' configuration, layout over the described chips and
# global batch (benchmark/workloads/train-*.json), at seq 2,048
TRAIN_CELLS = {
    "train-360m-h16": ("smollm2-360m", "NO_SHARD", 1, 8),
    "train-1.7b-fsdp4-h8": ("smollm2-1.7b", "FULL_SHARD", 4, 16),
}


@pytest.mark.parametrize("cell", list(TRAIN_CELLS))
def test_train_step_runs_each_attention_kernel_once(topo, cell):
    """The cell's train step under full remat, two of its layers under the
    looped scan its depth resolves to: the forward scan's body holds
    ``odtp_flash_fwd``, the backward scan's ``odtp_flash_dq`` and
    ``odtp_flash_dkv``, and no second forward beside them (the kernel's
    output and log-sum-exp come out of the forward scan). On four chips the
    kernel runs through ``flash_attention_sharded`` under FULL_SHARD."""
    import json
    import pathlib

    from opendiloco_tpu.models.llama import LlamaConfig
    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

    config, strategy, n, batch = TRAIN_CELLS[cell]
    root = pathlib.Path(__file__).resolve().parents[1]
    published = json.loads((root / "benchmark/configs" / f"{config}.json").read_text())
    cfg = LlamaConfig.from_dict({**published, "num_hidden_layers": 2})
    tc = TrainerConfig(precision="bf16-mixed", remat=True, attn_impl="pallas", scan_unroll=1)
    trainer = InnerTrainer(cfg, tc, build_mesh(strategy, devices=list(topo.devices)[:n]))
    text = trainer.lower_abstract(batch, 2048).compile().as_text()
    calls = re.findall(r"^\s*(?:ROOT )?%(odtp_flash_\w+?)[.\d]* = .*custom-call\(", text, re.M)
    assert sorted(calls) == ["odtp_flash_dkv", "odtp_flash_dq", "odtp_flash_fwd"]
    rows = batch // n
    heads = cfg.num_attention_heads
    # what leaves the forward scan for the backward beside the layers' inputs:
    # the log-sum-exp, and the kernel's own output, rows [rows a chip, seq,
    # heads x 64] (the shape of a layer's input here): nothing head-major,
    # whose 64 lanes of 128 would double its bytes
    assert f"f32[2,{rows},{heads},1,2048]" in text
    assert f"bf16[2,{rows},2048,{heads * 64}]" in text
    assert f"bf16[2,{rows},{heads},2048,64]" not in text
    assert trainer.attn_residual_bytes == 2 * rows * heads * 2048 * (64 * 2 + 4)
    # between the projections and ``o_proj``, in the forward, the remat pass
    # and the backward: the kernels read and write rows, and no instruction
    # under ``odtp_attention`` makes an activation (an array over the chip's
    # rows and the sequence) of fewer than 128 minor lanes
    assert narrow_kernel_arrays(text) == []
    narrow = []
    for line in text.splitlines():
        made = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = \(?([a-z]+\d+\[([\d,]+)\])", line)
        if made is None or "odtp_attention" not in line:
            continue
        dims = [int(x) for x in made.group(3).split(",")]
        if len(dims) >= 3 and dims[0] == rows and 2048 in dims and dims[-1] < 128:
            narrow.append(made.group(1, 2))
    assert narrow == []


def test_fused_xent_fwd_bwd(chip):
    n, d, v = 4096, 2048, 32000  # the 1b lm-head, where fused_loss is auto-on
    text = compiled_text(
        chip,
        jax.value_and_grad(fused_linear_cross_entropy, argnums=(0, 1)),
        ((n, d), BF16),
        ((d, v), BF16),
        ((n,), jnp.int32),
    )
    assert "tpu_custom_call" in text


# (query heads, kv heads, head_dim, ring rows) the decode kernel is compiled
# at: the shipped configs over a 1,024-row ring, and the serve cells' own
# (SmolLM2-360M's one 256-row tile; OLMoE's and granite's 128-row tiles under
# MHA and GQA heads of 128; the held-back chat cell's 32 MHA heads of 64)
DECODE = {
    **{name: (*heads, SEQ) for name, heads in HEADS.items()},
    "smollm2-360m": (15, 5, 64, 256),
    "olmoe-1b-7b": (16, 16, 128, 3200),
    "granite-4.0-h": (32, 8, 128, 2176),
    "smollm2-1.7b": (32, 32, 64, 2048),
    "zaya1-8b": (8, 2, 128, 1536),  # head_dim a key: 8 x 128 is not the hidden 2,048
}


def _kernel_blocks(fn, *shapes):
    """-> the block shapes of the one ``pallas_call`` in ``fn``: (of its
    inputs, of its outputs), a squeezed dimension as None."""
    args = [jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in shapes]
    (call,) = [
        e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns if e.primitive.name == "pallas_call"
    ]
    grid = call.params["grid_mapping"]
    blocks = [
        tuple(getattr(b, "block_size", None) for b in m.block_shape)
        for m in grid.block_mappings
    ]
    return blocks[:grid.num_inputs], blocks[grid.num_inputs:]


@pytest.mark.parametrize("return_stats", [False, True])
@pytest.mark.parametrize("model", list(DECODE))
def test_paged_decode_attention(chip, model, return_stats):
    """The kernel alone, handed a cache of two layers and the second's
    index: the read and the row write in one ``tpu_custom_call``, under the
    plan the shapes give; what it reads is a ``(heads, d, block_t)`` tile of
    K and of V, what it hands back the 128-row block that holds the row.
    Where the plan puts several slots in a grid step (SmolLM2-360M's ring of
    one tile) the tile holds theirs, and the caches come back whole, in no
    block: each slot's 128-row block by the kernel's own copy."""
    hq, hkv, d, rows = DECODE[model]
    s = 8
    cache = (cache_shape(2, s, rows, hkv, d), BF16)

    def step(q, k, v, ck, cv, lens):
        return paged_decode_attention(
            q, k, v, ck, cv, lens, 1, interpret=False, return_stats=return_stats
        )

    shapes = (
        ((s, hq, d), BF16),
        ((s, hkv, d), BF16),
        ((s, hkv, d), BF16),
        cache,
        cache,
        ((s,), jnp.int32),
    )
    compiled = jax.jit(step, donate_argnums=(3, 4)).lower(
        *(jax.ShapeDtypeStruct(shape, dtype, sharding=chip) for shape, dtype in shapes)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    heads, block_t, slots = decode_kernels.decode_plan(
        hkv, d, rows, 2, num_slots=s, interpret=False
    )
    assert (slots > 1) == (model == "smollm2-360m") and s % slots == 0
    ins, outs = _kernel_blocks(step, *shapes)
    if slots == 1:
        assert ins[-2:] == [(None, None, heads, d, block_t)] * 2
        assert outs[1:3] == [(None, None, heads, d, 128)] * 2
        assert outs[0] == (None, None, heads * (hq // hkv), d)
    else:
        assert ins[-2:] == [(None, slots, heads, d, block_t)] * 2
        assert outs[1:3] == [cache[0]] * 2  # the whole array: ``pl.ANY``
        assert outs[0] == (slots, None, heads * (hq // hkv), d)
    # the (donated) caches go back where they lie under either
    cache_bytes = 2 * np.prod(cache[0])
    assert compiled.memory_analysis().alias_size_in_bytes >= 2 * cache_bytes
    assert compiled.memory_analysis().temp_size_in_bytes < cache_bytes // 2


def _suffix_memory(chip, model, kq, rows):
    """A layer of the continued prefill as prefix reuse runs it: a suffix
    bucket's K and V rows into slot 1's pages behind a prefix of any length,
    then its queries over the slot's rows in tiles of 512."""
    hq, hkv, d = TAIL_HEADS[model]

    def layer(q, k, v, ck, cv, plen, count):
        ck, cv = layer_rows_insert(ck, cv, 0, 1, k, v, plen, count, whole_chunks=False)
        seen = jnp.arange(rows)[None] <= plen + jnp.arange(kq)[:, None]
        pages = lambda c: slot_layer_pages(c, 0, 1)
        return tiled_sparse_attention(q, pages(ck), pages(cv), seen, plen + count, 512), ck, cv

    ring = (cache_shape(1, 2, rows, hkv, d), BF16)
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
        for shape, dtype in (((kq, hq, d), BF16), ((kq, hkv, d), BF16), ((kq, hkv, d), BF16),
                             ring, ring, ((), jnp.int32), ((), jnp.int32))
    ]
    compiled = jax.jit(layer, donate_argnums=(3, 4)).lower(*args).compile()
    return compiled.memory_analysis(), compiled.as_text()


@pytest.mark.parametrize(
    "model,kq",
    [
        ("150m", 1024),
        ("1b", 512),
        # a whole GQA group's 8 x 1024 rows, which the tail kernel this path
        # replaced could not hold in VMEM
        ("1b", 1024),
        # the serving cells' own heads at their own prefill buckets: three
        # query heads a KV head at head_dim 64, and heads of 128
        ("360m", 32),
        ("360m", 128),
        ("olmoe", 1024),
        # its largest bucket, whose 3072 x 3072 scores a head the kernel refused
        ("olmoe", 3072),
    ],
)
def test_continued_prefill_layer(chip, model, kq):
    """The continued prefill's layer as prefix reuse calls it, for a described
    v5e: one slot of a ring of 4,096 rows, the suffix a whole bucket. It
    compiles at every bucket, the ring is updated in place (aliased, and the
    temporaries hold no second ring), and the scores it holds are a tile's:
    [heads, bucket, 512] float32 and what the softmax keeps beside them, never
    [heads, bucket, ring]."""
    rows = 4096
    hq, hkv, d = TAIL_HEADS[model]
    mem, text = _suffix_memory(chip, model, kq, rows)
    ring = 2 * rows * hkv * d * 2
    assert mem.alias_size_in_bytes == 2 * ring
    scores = hq * kq * 512 * 4  # one tile's, float32
    print(f"suffix layer {model} {kq}: temporaries {mem.temp_size_in_bytes}, a tile's scores {scores}")
    assert mem.temp_size_in_bytes < 6 * scores + 2 * 2**20
    if kq >= 512:  # where a [heads, bucket, ring] block would be far more
        assert 6 * scores + 2 * 2**20 < hq * kq * rows * 4
    assert f"f32[{hkv},{hq // hkv},{kq},{rows}]" not in text


@pytest.mark.parametrize("rows", [3072, 16])
def test_olmoe_routed_ffn_is_the_grouped_matmul(chip, rows):
    """One layer's routed FFN at a prefill's 3,072 rows and at a decode
    step's 16: XLA's ``ragged_dot`` comes out as the TPU's own grouped-matmul
    call, under the result name the benchmark's reader looks for."""
    from opendiloco_tpu.models.llama import _routed_ffn, shapes

    cfg, _ = olmoe_cell()
    layer = {
        name: jax.ShapeDtypeStruct(leaf.shape[1:], BF16, sharding=chip)
        for name, leaf in shapes(cfg)["layers"].items()
        if name in ("router", "gate_proj", "up_proj", "down_proj")
    }
    x = jax.ShapeDtypeStruct((1, rows, cfg.hidden_size), BF16, sharding=chip)
    text = (
        jax.jit(lambda x, layer: _routed_ffn(cfg, x, layer, None))
        .lower(x, layer).compile().as_text()
    )
    assert text.count("%ragged-dot") >= 3 and "tpu_custom_call" in text


@pytest.mark.parametrize("slots", [64, 8])
def test_mla_decode_attention(chip, slots):
    """The kernel at the published sizes (20 heads over rows of 512 + 64, a
    ring of 2,048 rows, 24 layers): the Mosaic kernel and not its XLA
    stand-in, the ring aliased to the output, no temporary. It reads a
    ``576 x 512`` tile of the ring a grid step, and what it hands back is the
    ``576 x 128`` block that holds the step's row, not the tile (PR 60)."""
    from opendiloco_tpu.ops.decode_kernels import mla_decode_attention

    ring = cache_shape(24, slots, 2048, 1, 576)
    compiled = jax.jit(
        lambda q, row, cache, lens, layer: mla_decode_attention(
            q, row, cache, lens, layer[0], scale=1 / 16, value_dim=512, interpret=False),
        donate_argnums=(2,),
    ).lower(*(
        jax.ShapeDtypeStruct(shape, dtype, sharding=chip) for shape, dtype in (
            ((slots, 20, 576), BF16), ((slots, 576), BF16), (ring, BF16),
            ((slots,), jnp.int32), ((1,), jnp.int32),
        )
    )).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "odtp_mla_decode_attn" in text and "tpu_custom_call" in text
    ring_bytes = 2 * 24 * slots * 576 * 2048
    assert mem.alias_size_in_bytes >= ring_bytes and mem.temp_size_in_bytes < ring_bytes // 24
    (blocks,) = kernel_windows(text, "odtp_mla_decode_attn")
    assert blocks[-3:] == [(1, 1, 1, 576, 512), (1, 20, 512), (1, 1, 1, 576, 128)]
    assert not ring_copies(text, ring)
