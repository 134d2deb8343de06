"""The main path's Pallas kernels, compiled for the chip without the chip.

Interpret-mode parity (test_attention, test_decode_kernels) cannot see what
the TPU's own compiler refuses: a slice Mosaic cannot lay out, a scalar
store to VMEM, a tile that outgrows VMEM. libtpu is installed here and
compiles for a *described* v5e (``jax.experimental.topologies``), so every
kernel variant the code can call is lowered AND compiled at published
widths in bf16, and must come out as a ``tpu_custom_call`` — the kernel,
not its XLA stand-in. A compile that passes is not a chip run; it only
means the chip run will not die at its first compile.

The serving cells' decode step and insert are compiled whole (seconds each):
what they must not hold is a copy of the ring cache (ISSUE 29), and only the
compiled program says whether they do. Training's step is compiled at the
two training cells' block shapes over a cut of the layers (a quarter and
half a minute): what it must hold is each attention kernel once (ISSUE 41).
The whole-depth compiles (a minute each) stay in the builder's rehearsal.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from opendiloco_tpu.models.ring_cache import cache_shape, layer_rows_insert, slot_layer_pages
from opendiloco_tpu.ops import decode_kernels
from opendiloco_tpu.ops.attention import tiled_sparse_attention
from opendiloco_tpu.ops.decode_kernels import paged_decode_attention
from opendiloco_tpu.ops.flash_attention import flash_attention
from opendiloco_tpu.ops.fused_xent import fused_linear_cross_entropy

BF16 = jnp.bfloat16
SEQ = 1024
# (query heads, kv heads, head_dim) of the configs the repo ships
HEADS = {"150m": (16, 16, 64), "1b": (32, 4, 64)}
# and of the two serving cells whose configurations the continued prefill
# takes (keys-and-values rows, no state beside them)
TAIL_HEADS = {**HEADS, "360m": (15, 5, 64), "olmoe": (16, 16, 128)}


@pytest.fixture(scope="module")
def topo():
    """A described (not attached) v5e:2x2 host, with the persistent compile
    cache off around the module: a deviceless executable can be written to
    the cache but not read back, and the next compile would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    # another process of this sandbox may hold libtpu's lock file
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"v5e topology cannot be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """One chip of the described host."""
    return SingleDeviceSharding(topo.devices[0])


def compiled_text(chip, fn, *shapes):
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
        for shape, dtype in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


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


def _flash_kernels(text):
    return sorted(re.findall(r"^\s*(?:ROOT )?%\w*?(odtp_flash_[a-z]+)[\w.]* = .*custom-call\(", text, re.M))


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
        assert _flash_kernels(text) == ["odtp_flash_fwd"]
        return

    def loss(q, k, v, cos, sin):
        out = flash_attention(q, k, v, head_dim=d, rope=Rope(cos, sin, d))
        return out.astype(jnp.float32).sum()

    shapes = (((b, t, hq * d), BF16), ((b, t, hkv * d), BF16), ((b, t, hkv * d), BF16))
    tables = (((b, t, lanes_of(d)[1]), jnp.float32),) * 2
    text = compiled_text(chip, jax.grad(loss, argnums=(0, 1, 2)), *shapes, *tables)
    assert _flash_kernels(text) == ["odtp_flash_dkv", "odtp_flash_dq", "odtp_flash_fwd"]
    assert _narrow_kernel_arrays(text) == []


def _narrow_kernel_arrays(text):
    """The operands and results of the flash kernels' calls in a compiled
    text whose minor dimension is under 128 (the rotary tables are a unit of
    128 lanes wide too: ``flash_attention.lanes_of``)."""
    narrow = []
    for line in text.splitlines():
        if not re.match(r"\s*(?:ROOT )?%\w*odtp_flash_[a-z]+[\w.]* = .*custom-call\(", line):
            continue
        for shape in re.findall(r"\b[a-z]+\d+\[[\d,]+\]", line.split(", custom_call_target")[0]):
            if int(shape[:-1].rsplit("[", 1)[1].split(",")[-1]) < 128:
                narrow.append(shape)
    return narrow


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
    assert sorted(set(_flash_kernels(text))) == ["odtp_flash_dkv", "odtp_flash_dq", "odtp_flash_fwd"]
    assert len(_flash_kernels(text)) >= 6


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
    assert _narrow_kernel_arrays(text) == []
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


# ---------------------------------------------------------------------------
# the OLMoE serving cell's programs, at the published widths and the depth
# the cell runs (benchmark/configs/olmoe-1b-7b.json and its cell's file)
# ---------------------------------------------------------------------------

HBM_BYTES = 16e9  # what the cell's sizing counts against


def _serve_cell(config, workload, **cut):
    """-> (a benchmark configuration as ``LlamaConfig``, with ``cut`` laid
    over the published values, and its cell's engine options)."""
    import json

    from opendiloco_tpu.models.llama import LlamaConfig

    bench = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "configs", f"{config}.json")) as f:
        cfg = LlamaConfig.from_dict({**json.load(f), **cut})
    with open(os.path.join(bench, "workloads", f"{workload}.json")) as f:
        return cfg, json.load(f)["engine"]


def _olmoe_cell():
    return _serve_cell("olmoe-1b-7b", "serve-olmoe-fewshot")


def _on_chip(chip, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree
    )


def _program_bytes(compiled) -> float:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )


@pytest.mark.parametrize("rows", [3072, 16])
def test_olmoe_routed_ffn_is_the_grouped_matmul(chip, rows):
    """One layer's routed FFN at a prefill's 3,072 rows and at a decode
    step's 16: XLA's ``ragged_dot`` comes out as the TPU's own grouped-matmul
    call, under the result name the benchmark's reader looks for."""
    from opendiloco_tpu.models.llama import _routed_ffn, shapes

    cfg, _ = _olmoe_cell()
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


def test_olmoe_prefill_program_at_the_largest_bucket(chip):
    from opendiloco_tpu.models.llama import prefill_forward, shapes

    cfg, engine = _olmoe_cell()
    bucket = max(engine["prefill_buckets"])
    compiled = (
        jax.jit(lambda p, ids, n: prefill_forward(
            p, ids, n, cfg, return_moe_counts=True))
        .lower(
            _on_chip(chip, shapes(cfg)),
            jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=chip),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=chip),
        ).compile()
    )
    cache = engine["num_slots"] * engine["max_context"] * 2 * 2 * (
        cfg.num_hidden_layers * cfg.kv_heads * cfg.head_dim
    )
    # the prefill runs beside the resident cache (the cell's ``sizing``)
    assert _program_bytes(compiled) + cache < HBM_BYTES


def test_olmoe_decode_program_at_16_slots(chip, monkeypatch):
    """16 slots, 16 KV heads of 128, the cell's rows a slot: the Pallas
    decode kernel and the grouped matmuls in one program that fits."""
    from opendiloco_tpu.models.llama import decode_forward, shapes

    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
    cfg, engine = _olmoe_cell()
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
        ).lower(_on_chip(chip, shapes(cfg)), vec, vec, cache, cache).compile()
    )
    text = compiled.as_text()
    assert "odtp_paged_decode_attn" in text and "%ragged-dot" in text
    assert _program_bytes(compiled) < HBM_BYTES


# ---------------------------------------------------------------------------
# the decode step and the insert of both serving cells: nothing of the ring
# cache's size, or of one layer's pages, is produced on the way (ISSUE 29)
# ---------------------------------------------------------------------------


def _batch_cell():
    """SmolLM2-360M's widths (15/5 heads of 64) at 8 of 32 layers, on the batch
    cell's engine: 256 slots of 256 rows, buckets 32 and 128."""
    return _serve_cell("smollm2-360m", "serve-360m-batch", num_hidden_layers=8)


CELLS = {"smollm2-360m": _batch_cell, "olmoe-1b-7b": _olmoe_cell}

_RESULT = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+)\[([\d,]*)\]")  # no tuples
# what may yield a cache- or layer-shaped array without moving one: the
# parameters themselves and their passage through the scan's tuples, and the
# kernel, whose cache results alias its operands
_MOVES_NOTHING = ("parameter(", "get-tuple-element(", "tpu_custom_call", "bitcast(")


def _cache_shaped_results(text: str, cache_shape: tuple) -> list[str]:
    """Instructions of a compiled program whose result has the dimensions of
    the cache or of one layer's pages, in any order (a copy, a transpose, a
    slice, an update, a scatter, a fresh buffer), other than those of
    ``_MOVES_NOTHING``."""
    def dims(shape):
        return sorted(d for d in shape if d != 1)

    wanted = (dims(cache_shape), dims(cache_shape[1:]))
    found = []
    for line in text.splitlines():
        m = _RESULT.match(line)
        if not m or any(k in line for k in _MOVES_NOTHING):
            continue
        if dims(int(d) for d in m.group(3).split(",") if d) in wanted:
            found.append(line.strip()[:160])
    return found


def _serving_shapes(chip, cell):
    from opendiloco_tpu.models.llama import shapes

    cfg, engine = CELLS[cell]()
    slots, rows = engine["num_slots"], engine["max_context"]
    cache = jax.ShapeDtypeStruct(
        cache_shape(cfg.num_hidden_layers, slots, rows, cfg.kv_heads, cfg.head_dim),
        BF16, sharding=chip,
    )
    return cfg, engine, _on_chip(chip, shapes(cfg)), cache


@pytest.mark.parametrize("cell", list(CELLS))
def test_decode_step_moves_no_cache(chip, cell, monkeypatch):
    """The engine's ``_decode`` (kernel ``pallas``, caches donated) at the
    cell's slots and rows: the decode kernel is in it; its temporaries stay under the bf16
    copy of the weights plus one layer's pages; and no copy, transpose,
    scatter, slice, update or fresh buffer in it has the shape of the cache
    or of one layer's pages. The batch cell's plan holds several slots a grid
    step (ISSUE 50): its caches come back in ``pl.ANY``, written by the
    kernel's own copies, and alias their inputs all the same."""
    from opendiloco_tpu.models.llama import decode_forward

    # off the TPU the wrappers would interpret the kernel; this is the chip's
    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
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
    assert not _cache_shaped_results(text, cache.shape)


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
            line for line in _cache_shaped_results(compiled.as_text(), cache.shape)
            if "dynamic-update-slice" not in line
        ]
        assert not moved, (bucket, moved)


def test_the_engines_own_programs_move_no_cache_either(chip, monkeypatch):
    """What the engine jits at the batch cell's shapes (``serving_programs``,
    ISSUE 38): the decode step that takes a fresh slot's token from the
    first-token vector, and the admission's insert that writes it there
    beside the prompt's rows. The select and the scalar write cost no copy:
    both caches alias, nothing cache- or page-shaped is moved, and the vector
    goes back in place."""
    from opendiloco_tpu.serve.engine import serving_programs

    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
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
    assert not _cache_shaped_results(text, cache.shape)
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
            line for line in _cache_shaped_results(compiled.as_text(), cache.shape)
            if "dynamic-update-slice" not in line
        ]
        assert not moved, (bucket, moved)


def test_the_decode_step_takes_the_token_in_flight_without_a_copy(chip, monkeypatch):
    """The decode program as the engine jits it since ISSUE 48, at the batch
    cell's shapes: ``tokens`` selects between the host's token, the slot's
    entry of ``first`` and its entry of ``prev``, the step before's output.
    Both caches still alias, nothing cache- or page-shaped is moved, and the
    two vectors are read where they lie: no copy of a slots-long int32 but the
    prefetch of each argument into fast memory."""
    from opendiloco_tpu.serve.engine import serving_programs

    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
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
    assert not _cache_shaped_results(text, cache.shape)
    entry = text[text.index("ENTRY "):]
    slots_long = [
        line.strip()[:160] for line in entry.splitlines()
        if (m := _RESULT.match(line)) and m.group(2) == "s32"
        and m.group(3) == str(engine["num_slots"])
    ]
    assert sum("parameter(" in line for line in slots_long) == 4  # tokens, lens, first, prev
    # each is prefetched into fast memory (``S(1)``) as ``tokens`` and ``first``
    # were, and nothing else of that length is copied anywhere
    moved = [line for line in slots_long if "copy" in line]
    assert len(moved) == 4 and all("S(1)} copy-done(" in line for line in moved), moved


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

    cfg, engine = _serve_cell("granite-4.0-h-small", "serve-granite-h-docqa")
    slots, rows = engine["num_slots"], engine["max_context"]
    cache = jax.ShapeDtypeStruct(
        cache_shape(cfg.num_attention_layers, slots, rows, cfg.kv_heads, cfg.head_dim),
        BF16, sharding=chip,
    )
    ssm, conv = mamba.state_shapes(cfg, slots)
    return (
        cfg, engine, _on_chip(chip, shapes(cfg)), cache,
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
    assert _program_bytes(compiled) + resident < HBM_BYTES


def test_granite_decode_step_updates_the_state_in_place(chip, monkeypatch):
    """32 slots: the Pallas decode kernel over the one attention layer's ring,
    the grouped matmuls over the 9 held experts, and the two runs of Mamba-2
    layers carrying 1.2 GB of recurrent state that is aliased to the output
    and never copied: the temporaries stay under the bf16 copy of the weights
    plus one layer's state, and no ``copy`` has the state's shape."""
    from opendiloco_tpu.models.llama import decode_forward

    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
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
    assert _program_bytes(compiled) < HBM_BYTES
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
# the serving weights bound once (ISSUE 31): given the tree as the engine holds
# it, the three cells' decode program and largest prefill cast no weight leaf
# ---------------------------------------------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(
    r"^\s*(ROOT )?(%[\w.\-]+) = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\((.*)$"
)


def _top_level(text: str) -> tuple[list, dict]:
    """A compiled program's instructions that run as operations of their own
    (outside the fused computations), each as (opcode, result dtype, result
    dimensions, the computation a fusion calls, the line's head), and every
    computation's root opcode."""
    roots, fused, found, name = {}, set(), [], None
    for line in text.splitlines():
        m = _COMPUTATION.match(line.strip())
        if m:
            name = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        root, _, result, dims, opcode, rest = m.groups()
        shape = tuple(int(d) for d in dims.split(",") if d)
        called = re.search(r"calls=(%[\w.\-]+)", rest) if opcode == "fusion" else None
        if called:
            fused.add(called.group(1))
        if root:
            roots[name] = opcode
        found.append((name, opcode, result, shape, called and called.group(1), line.strip()[:160]))
    return [x[1:] for x in found if x[0] not in fused], roots


def _leaf_shaped_casts(text: str, leaf_shapes: set, dtype: str = "bf16") -> list[str]:
    """Instructions of a compiled program that run as an operation of their
    own and are a ``convert``, or a fusion whose root is one, with a result in
    ``dtype`` of a weight leaf's shape: what ``_serving_boundary`` emits for a
    leaf that did not come in the compute dtype."""
    instructions, roots = _top_level(text)
    return [
        line for opcode, result, shape, called, line in instructions
        if result == dtype and shape in leaf_shapes
        and (opcode == "convert" or roots.get(called) == "convert")
    ]


def _bound(chip, cfg):
    """The parameters as ``ServeEngine._bind`` leaves them under bf16 compute
    (every leaf through the engine's own ``_fresh_copy``), as shapes on the
    described chip."""
    from opendiloco_tpu.models.llama import shapes
    from opendiloco_tpu.serve.engine import _fresh_copy

    leaves, treedef = jax.tree.flatten(shapes(cfg))
    held = jax.eval_shape(lambda xs: _fresh_copy(xs, BF16), leaves)
    assert all(x.dtype == BF16 for x in held)
    return _on_chip(chip, jax.tree.unflatten(treedef, held))


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


def _engine_program(chip, config, workload, program, bucket=None):
    """A serve cell's decode step or a prefill (its largest, or ``bucket``'s), published widths
    and the cell's cuts, lowered as the engine lowers it (kernel ``pallas``,
    counts where routed, caches and state donated) with the bf16 tree the
    engine holds, compiled for the described chip -> (the compiled program,
    the configuration, the parameters, the bytes the step carries)."""
    from opendiloco_tpu.models import mamba
    from opendiloco_tpu.models.llama import decode_forward, prefill_forward

    cfg, engine = _serve_cell(config, workload)
    params = _bound(chip, cfg)
    moe = bool(cfg.num_experts)
    if program == "prefill":
        bucket = bucket or max(engine["prefill_buckets"])
        compiled = (
            jax.jit(lambda p, ids, n: prefill_forward(
                p, ids, n, cfg, decode_kernel="pallas", return_moe_counts=moe))
            .lower(
                params,
                jax.ShapeDtypeStruct((1, bucket), jnp.int32, sharding=chip),
                jax.ShapeDtypeStruct((), jnp.int32, sharding=chip),
            ).compile()
        )
        return compiled, cfg, params, 0
    slots, rows = engine["num_slots"], engine["max_context"]
    width = (1, cfg.latent_row_dim) if cfg.latent else (cfg.kv_heads, cfg.head_dim)
    ring = jax.ShapeDtypeStruct(
        cache_shape(cfg.num_attention_layers, slots, rows, *width), BF16, sharding=chip
    )
    carried = [ring] if cfg.latent else [ring, ring]  # a latent ring has no values
    if cfg.hybrid:
        ssm, conv = mamba.state_shapes(cfg, slots)
        carried += [
            jax.ShapeDtypeStruct(ssm, jnp.float32, sharding=chip),
            jax.ShapeDtypeStruct(conv, BF16, sharding=chip),
        ]
    if cfg.cca:  # what each layer's projection keeps of a slot's last token
        carried.append(jax.ShapeDtypeStruct(
            (cfg.num_hidden_layers, slots, cfg.cca_state_dim), BF16, sharding=chip))
    vec = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=chip)
    names = ("cca_state",) if cfg.cca else ("ssm_state", "conv_state")

    def step(p, tok, lens, ck, *rest):
        cv, *state = (None, *rest) if cfg.latent else rest
        return decode_forward(
            p, tok, lens, ck, cv, cfg, decode_kernel="pallas", return_moe_counts=moe,
            **dict(zip(names, state)),
        )

    compiled = (
        jax.jit(step, donate_argnums=tuple(range(3, 3 + len(carried))))
        .lower(params, vec, vec, *carried).compile()
    )
    return compiled, cfg, params, sum(x.size * x.dtype.itemsize for x in carried)


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("workload", list(SERVE_CELLS))
def test_serving_programs_cast_no_weights(chip, workload, program, monkeypatch):
    """Each serve cell whole (``_engine_program``): no cast of a weight leaf
    is left, the temporaries are a fraction of the weights (the per-call bf16
    copy was all of them: 0.73 / 3.63 / 3.83 GB in the three decode programs
    at PR 30), and a decode step still updates caches and state where they
    are."""
    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
    compiled, cfg, params, carried = _engine_program(
        chip, SERVE_CELLS[workload], workload, program
    )
    leaves = jax.tree.leaves(params)
    assert not _leaf_shaped_casts(compiled.as_text(), {tuple(x.shape) for x in leaves})
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
    assert _program_bytes(compiled) < HBM_BYTES


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
    chip, workload, config, bucket, monkeypatch
):
    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
    compiled, cfg, _, _ = _engine_program(chip, config, workload, "prefill", bucket)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "odtp_flash_fwd" in text and "tpu_custom_call" in text
    assert not _spans_twice(text, bucket), _spans_twice(text, bucket)
    # compiled here: 0.115 GB (OLMoE, 0.86 at the parent's 2,560), 0.087 (GLM, 0.31)
    assert mem.temp_size_in_bytes < 0.15e9, mem.temp_size_in_bytes


@pytest.mark.parametrize("bucket", [32, 128])
def test_the_batch_cells_prefill_keeps_the_xla_form(chip, bucket, monkeypatch):
    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
    compiled, cfg, _, _ = _engine_program(
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
def test_serving_programs_copy_no_experts(chip, workload, program, monkeypatch):
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

    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
    compiled, cfg, params, _ = _engine_program(
        chip, ROUTED_CELLS[workload], workload, program
    )
    stacks = params["layers"] if cfg.layers_by_kind else {"attention": params["layers"]}
    experts = [  # every routed kind's [L, Eh, in, out] stacks
        stack[name].shape for stack in stacks.values() if "router" in stack
        for name in EXPERT_LEAVES
    ]
    assert experts and all(shape[1] == cfg.held_experts for shape in experts)
    text = compiled.as_text()
    instructions, _ = _top_level(text)

    of_a_layer = {tuple(sorted(shape[1:])) for shape in experts}
    written = [
        line for opcode, _, shape, _, line in instructions
        if tuple(sorted(d for d in shape if d != 1)) in of_a_layer
        and not any(k in line for k in _MOVES_NOTHING)
    ]
    assert not written, written

    results = {m.group(1): tuple(int(d) for d in m.group(3).split(",") if d)
               for m in map(_RESULT.match, text.splitlines()) if m}
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
# the GLM-4.7-Flash cell (ISSUE 32): the latent decode kernel alone, then the
# cell's largest prefill and its decode step whole, published widths, 24
# layers, with the bf16 tree the engine holds. They fit the chip beside what
# the engine keeps; the decode step reads the one latent ring where it lies,
# copies nothing of a layer's size and casts no weight
# ---------------------------------------------------------------------------


def _glm_cell(chip):
    """-> (configuration, the latent ring as a shape on the described chip)."""
    cfg, engine = _serve_cell("glm-4.7-flash", "serve-glm-flash-agent")
    ring = jax.ShapeDtypeStruct(
        cache_shape(cfg.num_hidden_layers, engine["num_slots"], engine["max_context"],
                    1, cfg.latent_row_dim),
        BF16, sharding=chip,
    )
    return cfg, ring


@pytest.mark.parametrize("slots", [64, 8])
def test_mla_decode_attention(chip, slots):
    """The kernel at the published sizes (20 heads over rows of 512 + 64, a
    ring of 2,048 rows, 24 layers): the Mosaic kernel and not its XLA
    stand-in, the ring aliased to the output, no temporary."""
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


def test_glm_prefill_program_at_the_largest_bucket(chip, monkeypatch):
    """Bucket 1,792 in the rebuilt form: the grouped matmuls over the 8 held
    experts are in it, it casts no weight, its temporaries (since PR 55 without
    the scores of 20 heads over 1,792 x 1,792: the flash forward kernel) stay
    under half the weights, and it fits beside the resident ring."""
    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
    cfg, ring = _glm_cell(chip)
    assert (cfg.leading_dense, cfg.held_experts, cfg.num_experts, cfg.latent_row_dim) == (1, 8, 64, 576)
    compiled, _, params, _ = _engine_program(
        chip, "glm-4.7-flash", "serve-glm-flash-agent", "prefill"
    )
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "%ragged-dot" in text
    leaves = jax.tree.leaves(params)
    assert not _leaf_shaped_casts(text, {tuple(x.shape) for x in leaves})
    weights = sum(x.size * x.dtype.itemsize for x in leaves)
    assert mem.temp_size_in_bytes < weights / 2
    assert _program_bytes(compiled) + 2 * ring.size < HBM_BYTES


def test_glm_decode_step_reads_the_latent_ring_in_place(chip, monkeypatch):
    """64 slots (or what the cell's file says): the latent kernel over the
    one ring, the grouped matmuls, no cast of a weight, the ring aliased to
    the output, and no copy, transpose, scatter, slice, update or fresh
    buffer of the ring's shape or of one layer's pages."""
    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
    _, ring = _glm_cell(chip)
    compiled, _, params, carried = _engine_program(
        chip, "glm-4.7-flash", "serve-glm-flash-agent", "decode"
    )
    assert carried == 2 * ring.size
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "odtp_mla_decode_attn" in text and "%ragged-dot" in text
    assert "odtp_paged_decode_attn" not in text
    leaves = jax.tree.leaves(params)
    assert not _leaf_shaped_casts(text, {tuple(x.shape) for x in leaves})
    weights = sum(x.size * x.dtype.itemsize for x in leaves)
    assert mem.temp_size_in_bytes < weights / 4
    assert mem.alias_size_in_bytes >= 2 * ring.size
    assert _program_bytes(compiled) < HBM_BYTES
    assert not _cache_shaped_results(text, ring.shape)


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
    cfg, engine = _serve_cell("zaya1-8b", "serve-zaya1-reason")
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
    compiled, _, params, _ = _engine_program(chip, "zaya1-8b", "serve-zaya1-reason", "prefill")
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "%ragged-dot" in text
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == 5_225_940_328
    assert mem.temp_size_in_bytes < weights / 4
    resident = 2 * 2 * ring.size + 2 * state.size
    assert _program_bytes(compiled) + resident < HBM_BYTES


def test_zaya_decode_step_carries_ring_and_state_in_place(chip, monkeypatch):
    """128 slots (or what the cell's file says): the decode kernel under the
    plan for (2, 128, 1,536), the grouped matmuls, both rings and the state
    aliased to the outputs, no copy, transpose, scatter, slice, update or
    fresh buffer of a ring's shape or of one layer's pages, and of the
    state's shape nothing that runs as an operation of its own but the
    in-place update of a layer's rows. The tied table: what the step says of
    its re-order is recorded, not asserted away (ISSUE 37: removing it is a
    ``perf_opt`` of its own)."""
    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
    cfg, engine, ring, state = _zaya_cell(chip)
    compiled, _, params, carried = _engine_program(chip, "zaya1-8b", "serve-zaya1-reason", "decode")
    assert carried == 2 * 2 * ring.size + 2 * state.size
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert "odtp_paged_decode_attn" in text and "%ragged-dot" in text
    plan = decode_kernels.decode_plan(2, 128, engine["max_context"], 2, interpret=False)
    assert plan.heads == 2 and engine["max_context"] % plan.block_t == 0
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert mem.temp_size_in_bytes < weights / 4
    assert mem.alias_size_in_bytes >= carried
    assert _program_bytes(compiled) < HBM_BYTES
    assert not _cache_shaped_results(text, ring.shape)
    # the state: written where it lies (a dynamic-update-slice, alone or as a
    # fusion's root), never copied, transposed or allocated anew
    instructions, roots = _top_level(text)
    of_the_state = [
        (opcode, roots.get(called), line) for opcode, _, shape, called, line in instructions
        if shape == state.shape and not any(k in line for k in _MOVES_NOTHING)
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

    cfg, engine = _serve_cell("evabyte-6.5b", "serve-evabyte-complete")
    L, slots = cfg.num_hidden_layers, engine["num_slots"]
    ring = jax.ShapeDtypeStruct(
        cache_shape(L, slots, cfg.window_size, cfg.kv_heads, cfg.head_dim), BF16, sharding=chip)
    pooled = jax.ShapeDtypeStruct(
        cache_shape(L, slots, eva_pooled_rows(cfg, engine["max_context"]), cfg.kv_heads, cfg.head_dim),
        BF16, sharding=chip)
    stats = jax.ShapeDtypeStruct(
        (L, slots, cfg.kv_heads, 2 * cfg.head_dim + 2), jnp.float32, sharding=chip)
    return cfg, engine, (ring, ring, pooled, pooled, stats)


def test_eva_decode_step_moves_neither_ring(chip, monkeypatch):
    """The engine's own decode program (``serving_programs``) at 24 slots: the
    decode kernel over the window's ring and its pooled form over the pooled
    ring are both in it; both rings and the stats alias the outputs; no copy,
    transpose, scatter, slice, update or fresh buffer has the shape of either
    ring or of one layer's pages; no weight is cast; arguments and temporaries
    fit the chip."""
    from opendiloco_tpu.serve.engine import serving_programs

    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
    cfg, engine, carried = _eva_cell(chip)
    assert (cfg.kv_heads, cfg.head_dim, cfg.window_size, cfg.eva_chunks_per_window) == (32, 128, 2048, 128)
    assert carried[2].shape[-1] == 384
    params = _bound(chip, cfg)
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
          f"aliased {mem.alias_size_in_bytes} program {_program_bytes(compiled):.0f}")
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < weights / 4
    assert _program_bytes(compiled) < HBM_BYTES
    assert not _cache_shaped_results(text, carried[0].shape)
    assert not _cache_shaped_results(text, carried[2].shape)
    assert not _leaf_shaped_casts(text, {tuple(x.shape) for x in jax.tree.leaves(params)})


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

    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
    cfg, engine, carried = _eva_cell(chip)
    assert decode_kernels.eva_prefill_form(cfg.window_size, cfg.head_dim) == "flash"
    if form == "xla":
        monkeypatch.setattr(decode_kernels, "eva_prefill_form", lambda *a, **kw: "xla")
    params = _bound(chip, cfg)
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
        for m in map(_RESULT.match, text.splitlines()) if m
    ]
    # a head's scores: an array over the 32 heads with two dimensions of
    # positions (a window's 2,048 or more); none spans the bucket twice over
    scores = [s for s in shapes_in if cfg.num_attention_heads in s and sum(d >= 2048 for d in s) >= 2]
    assert all(max(s) <= cfg.window_size + cfg.eva_chunks_per_window for s in scores), scores
    assert ("odtp_flash_fwd" in text) == (form == "flash") == (not scores)
    held = sum(x.size * x.dtype.itemsize for x in carried)
    print(f"eva prefill ({form}): arguments {mem.argument_size_in_bytes} temporaries {mem.temp_size_in_bytes} "
          f"program {_program_bytes(compiled):.0f}")
    assert _program_bytes(compiled) + held < HBM_BYTES
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
        for line in _cache_shaped_results(compiled.as_text(), shape)
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
    cfg, engine = _serve_cell("keye-vl-2.0-30b-a3b", "serve-keye-videoqa")
    L, slots, rows = cfg.num_hidden_layers, engine["num_slots"], engine["max_context"]
    kv = jax.ShapeDtypeStruct(
        cache_shape(L, slots, rows, cfg.kv_heads, cfg.head_dim), BF16, sharding=chip)
    index = jax.ShapeDtypeStruct((L, slots, cfg.index_head_dim, rows), BF16, sharding=chip)
    return cfg, engine, (kv, kv, index)


def _ring_copies(text: str, shape: tuple) -> list[str]:
    """Instructions that make an array of a ring's shape anew: everything
    ``_cache_shaped_results`` finds of the whole ring's dimensions but the
    updates in place (a slice update or the kernels' aliased outputs write
    into the ring they are given)."""
    whole = sorted(d for d in shape if d != 1)
    found = []
    for line in _cache_shaped_results(text, shape):
        m = _RESULT.match(line)
        if sorted(int(d) for d in m.group(3).split(",") if int(d) != 1) != whole:
            continue  # one layer's pages: judged by its caller
        if "dynamic-update-slice(" in line or "dynamic-update-slice_fusion" in line:
            continue
        found.append(line)
    return found


def _f32_blocks_over(text: str, nbytes: float) -> list[str]:
    found = []
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m and m.group(2) == "f32":
            size = 4
            for d in m.group(3).split(","):
                size *= int(d) if d else 1
            if size > nbytes and "parameter(" not in line:
                found.append(line.strip()[:160])
    return found


def test_keye_decode_step_moves_no_ring(chip, monkeypatch):
    """The engine's own decode program at 12 slots of 16,896 rows: the decode
    kernel under its selection operand and the index ring's column write are in
    it; the three rings alias the outputs; nothing has the shape of the K and V
    rings or of a layer's pages of them, nothing copies the index ring (a
    layer's 26 MB of index keys may be cut out for the scoring); no weight is
    cast; no float32 block over 256 MB; temporaries are a few megabytes."""
    from opendiloco_tpu.serve.engine import serving_programs

    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
    cfg, engine, rings = _keye_cell(chip)
    assert (cfg.kv_heads, cfg.head_dim, cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk) == (
        4, 128, 16, 64, 2048)
    params = _bound(chip, cfg)
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
          f"aliased {mem.alias_size_in_bytes} program {_program_bytes(compiled):.0f}")
    assert mem.alias_size_in_bytes >= held
    assert mem.temp_size_in_bytes < 64e6
    assert _program_bytes(compiled) < HBM_BYTES
    assert not _cache_shaped_results(text, rings[0].shape)
    assert not _ring_copies(text, rings[2].shape)
    assert not _leaf_shaped_casts(text, {tuple(x.shape) for x in jax.tree.leaves(params)})
    assert not _f32_blocks_over(text, 256e6)


def test_keye_chunk_program_writes_its_rows_in_place(chip):
    """The chunk program (512 queries; ``plen``, ``count`` and ``slot``
    traced, so this is the program behind 15,872 rows too): the three rings
    alias the outputs and are updated by slice updates alone, no copy of a
    ring's size (a first form whose K and V pages kept a row contiguous was
    re-laid rows minor-most by the compiler, 3.09 GB a ring, and did not fit
    the chip); the index scores and the attention's tiles are the only large
    float32 blocks and stay under 256 MB; no weight is cast."""
    from opendiloco_tpu.serve.engine import chunk_program

    cfg, engine, rings = _keye_cell(chip)
    params = _bound(chip, cfg)
    vec = jax.ShapeDtypeStruct((engine["num_slots"],), jnp.int32, sharding=chip)
    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)
    ids = jax.ShapeDtypeStruct((1, cfg.q_chunk_size), jnp.int32, sharding=chip)
    last = jax.ShapeDtypeStruct((), jnp.bool_, sharding=chip)
    compiled = (
        jax.jit(chunk_program(cfg, compute_dtype=BF16), donate_argnums=(6, 7, 8, 9))
        .lower(params, ids, scalar, scalar, scalar, last, vec, *rings).compile()
    )
    text, mem = compiled.as_text(), compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in rings)
    print(f"keye chunk: arguments {mem.argument_size_in_bytes} temporaries {mem.temp_size_in_bytes} "
          f"aliased {mem.alias_size_in_bytes} program {_program_bytes(compiled):.0f}")
    assert mem.alias_size_in_bytes >= held == 7_059_013_632
    assert mem.temp_size_in_bytes < 512e6
    assert _program_bytes(compiled) < HBM_BYTES
    assert not _ring_copies(text, rings[0].shape) and not _ring_copies(text, rings[2].shape)
    assert not _leaf_shaped_casts(text, {tuple(x.shape) for x in jax.tree.leaves(params)})
    assert not _f32_blocks_over(text, 256e6)
    assert "f32[512,16896]" in text  # a chunk's index scores: 35 MB, never a block a head


def test_keye_index_ring_write_kernel(chip, monkeypatch):
    """``odtp_index_ring_write`` alone at the cell's shapes: a grid step a
    layer and slot, the ring aliased through."""
    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
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
    assert not _ring_copies(compiled.as_text(), rings[2].shape)


# --- dots3-note-prev: two kinds of latent attention, three rings (PR 54) ----


def _dots3_cell(chip):
    """-> (configuration, engine options, the three rings as shapes: the full
    layers' latent ring, the sliding layers' ring that wraps, the index ring)."""
    from opendiloco_tpu.models.ring_cache import sliding_ring_rows

    cfg, engine = _serve_cell("dots3-note-prev", "serve-dots3-notes")
    slots, rows = engine["num_slots"], engine["max_context"]
    full = jax.ShapeDtypeStruct(
        cache_shape(cfg.num_full_layers, slots, rows, 1, cfg.latent_row_dim), BF16, sharding=chip)
    sliding = jax.ShapeDtypeStruct(
        cache_shape(cfg.num_sliding_layers, slots, sliding_ring_rows(cfg), 1, cfg.sliding_row_dim),
        BF16, sharding=chip)
    index = jax.ShapeDtypeStruct(
        (cfg.num_full_layers, slots, cfg.index_head_dim, rows), BF16, sharding=chip)
    return cfg, engine, (full, sliding, index)


def _dots3_program(chip, monkeypatch, which):
    from opendiloco_tpu.serve.engine import chunk_program, serving_programs

    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
    cfg, engine, rings = _dots3_cell(chip)
    params = _bound(chip, cfg)
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
            chunk_program(cfg, compute_dtype=BF16), donate_argnums=(6, 7, 8, 9)
        ).lower(params, ids, scalar, scalar, scalar, last, vec, *rings)
    return cfg, params, rings, lowered.compile()


@pytest.mark.parametrize("which", ["decode", "chunk"])
def test_dots3_programs_copy_no_ring_and_cast_no_weight(chip, monkeypatch, which):
    """The engine's decode and chunk programs for dots3-note-prev at 12 slots
    of 25,088 rows, published widths: 4,087,154,176 parameters held once in
    bf16; the three rings (the full layers' 576-wide latent ring, the sliding
    layers' 1,088-wide ring of 1,024 rows, the index ring) alias the outputs
    and none is copied; no weight is cast; the program fits the chip. The
    decode step holds ``odtp_mla_decode_attn`` (under the selection and under
    the window: the kernel, not its XLA form) and the index ring's column
    write; the chunk holds its index scores [512, 25088] once and no float32
    block over 512 MB."""
    cfg, params, rings, compiled = _dots3_program(chip, monkeypatch, which)
    assert (cfg.num_full_layers, cfg.num_sliding_layers, cfg.latent_row_dim, cfg.sliding_row_dim) == (
        2, 3, 576, 1088)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    held = sum(x.size * x.dtype.itemsize for x in rings)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == 8_174_308_352 and held == 927_989_760
    print(f"dots3 {which}: arguments {mem.argument_size_in_bytes} temporaries "
          f"{mem.temp_size_in_bytes} aliased {mem.alias_size_in_bytes} "
          f"program {_program_bytes(compiled):.0f}")
    assert mem.alias_size_in_bytes >= held
    assert _program_bytes(compiled) < HBM_BYTES
    for ring in rings:
        assert not _ring_copies(text, ring.shape), which
    assert not _leaf_shaped_casts(text, {tuple(x.shape) for x in jax.tree.leaves(params)})
    if which == "decode":
        assert "odtp_mla_decode_attn" in text and "odtp_index_ring_write" in text
        assert mem.temp_size_in_bytes < 256e6
        assert not _f32_blocks_over(text, 256e6)
    else:
        assert "f32[512,25088]" in text
        assert mem.temp_size_in_bytes < 2e9
        assert not _f32_blocks_over(text, 512e6)


# --- Laguna-S-2.1: two kinds of grouped-query attention, rings by kind (PR 56) ----


def _laguna_program(chip, monkeypatch, which):
    import dataclasses

    from opendiloco_tpu.models.ring_cache import init_kv_cache
    from opendiloco_tpu.serve.engine import chunk_program, serving_programs

    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: False)
    cfg, engine = _serve_cell("laguna-s-2.1", "serve-laguna-repoedit")
    cfg = dataclasses.replace(cfg, q_chunk_size=engine["prefill_chunk"])  # as the engine lays it
    cache = jax.eval_shape(
        lambda: init_kv_cache(cfg, engine["num_slots"], engine["max_context"], BF16))
    rings = _on_chip(chip, (cache["k"], cache["v"]))
    params = _bound(chip, cfg)
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
            chunk_program(cfg, compute_dtype=BF16), donate_argnums=(6, 7, 8, 9)
        ).lower(params, ids, scalar, scalar, scalar, last, vec, *rings, None)
    return cfg, params, rings, lowered.compile()


@pytest.mark.parametrize("which", ["decode", "chunk"])
def test_laguna_programs_copy_no_ring_and_cast_no_weight(chip, monkeypatch, which):
    """The engine's decode and chunk programs for Laguna-S-2.1 at 12 slots of
    18,432 rows under chunks of 2,048, published widths: 5,034,052,608
    parameters held once in bf16; the four rings (the full layers' K and V as
    long as the context, the sliding layers' of 4,096 rows that wrap) alias the
    outputs and none is copied; no weight is cast; the program fits the chip.
    The decode step holds ``odtp_paged_decode_attn`` for both kinds (under the
    window for the sliding layers: the kernel, not its XLA form); the chunk
    holds no float32 block over 512 MB (no [72, 2048, 4096] scores: the band's
    blocks and the full layers' tiles)."""
    cfg, params, rings, compiled = _laguna_program(chip, monkeypatch, which)
    assert (cfg.num_full_layers, cfg.num_sliding_layers) == (2, 6)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    flat = jax.tree.leaves(rings)
    held = sum(x.size * x.dtype.itemsize for x in flat)
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert weights == 10_068_105_216 and held == 1_811_939_328 + 1_207_959_552
    print(f"laguna {which}: arguments {mem.argument_size_in_bytes} temporaries "
          f"{mem.temp_size_in_bytes} aliased {mem.alias_size_in_bytes} "
          f"program {_program_bytes(compiled):.0f}")
    assert mem.alias_size_in_bytes >= held
    assert _program_bytes(compiled) < HBM_BYTES
    for ring in flat:
        assert not _ring_copies(text, ring.shape), which
    assert not _leaf_shaped_casts(text, {tuple(x.shape) for x in jax.tree.leaves(params)})
    if which == "decode":
        assert text.count("odtp_paged_decode_attn") >= 2
        assert mem.temp_size_in_bytes < 512e6  # the dense layer's FFN, cut from its stack of one
        assert not _f32_blocks_over(text, 256e6)
    else:
        assert mem.temp_size_in_bytes < 2.5e9
        assert not _f32_blocks_over(text, 512e6)
