"""Pallas serving-kernel parity tests (ops/decode_kernels.py).

The dispatch contract is token-bit-exact: ``decode_kernel="pallas"``
must emit exactly the token stream the stock XLA path emits. On this
CPU rig the kernels run in Pallas interpret mode — slower, but it is the
kernel's own dataflow (masks, online softmax, in-register dequant, the
in-place row write), so parity pinned here carries to the Mosaic lowering.
Ring pages are built through ``models.ring_cache`` (rows minor-most), never
spelled here.

Oracles:
- paged decode attention matches ``decode_step_attention`` (the XLA row
  write, then ``decode_attention``) over ragged lens (empty slot, mid-page, last row,
  lens >= T sliding window, both sides of a 128-row tile edge) and every
  GQA head ratio the configs use, the caches coming back bit-equal (one
  row written per slot, nothing else touched) — and its stats variant
  proves dead ring blocks are skipped, not masked
- a ring with no 128-row tile, and a head size off the sublanes, keep the
  XLA path per call
- the latent decode kernel (one ring of latent rows, every head against the
  same tile, values from the row's leading part) matches
  ``latent_decode_step_attention`` over the same ragged lens, layers and
  block sizes, the ring coming back with one row written per slot
- a slot's pages survive the host tier's round trip (``fetch_pages`` ->
  ``cache_insert``) bit for bit
- the continued prefill's attention, a tile of ring rows at a time under an
  online softmax, matches one softmax over the slot's rows, and its rows go
  into the ring where a bucket's padding would pass the ring's end
- ``auto`` never selects Pallas off-TPU
- engine-level: identical token streams xla vs pallas(interpret) across
  prefill buckets and ring wrap, for every architecture family the
  benchmark's cells serve — including under the continuous batcher
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_evabyte
import test_serve_deferred_admit
from opendiloco_tpu.models.ring_cache import (
    cache_insert,
    cache_shape,
    fetch_pages,
    layer_pages,
    layer_rows_insert,
)
from opendiloco_tpu.ops import decode_kernels
from opendiloco_tpu.ops.attention import (
    decode_attention,
    decode_step_attention,
    latent_decode_step_attention,
    tiled_sparse_attention,
)
from opendiloco_tpu.ops.decode_kernels import (
    mla_decode_attention,
    paged_decode_attention,
    resolve_decode_kernel,
)
from opendiloco_tpu.serve import ContinuousBatcher, ServeEngine


def _rng(seed=0):
    return np.random.default_rng(seed)


def _randn(rng, *shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _ring(rng, L, S, Kh, D, T):
    """A cache of L layers whose every ring row holds random K/V, built the
    way the engine fills one: rows [L, T, Kh, D] inserted slot by slot."""
    ck = cv = jnp.zeros(cache_shape(L, S, T, Kh, D), jnp.float32)
    for slot in range(S):
        ck, cv = cache_insert(
            ck, cv, _randn(rng, L, T, Kh, D), _randn(rng, L, T, Kh, D), slot
        )
    return ck, cv


def _pages(rng, S, Kh, D, T):
    """One layer's pages, as the verify pass's scan hands them out."""
    return layer_pages(*_ring(rng, 1, S, Kh, D, T), 0)


def _assert_decode_parity(rng, S, H, Kh, D, T, lens, *, layers=2, **kw):
    """Kernel == reference on the last layer of a ``layers``-deep cache: the
    attention output to rounding, both caches bit for bit."""
    ck, cv = _ring(rng, layers, S, Kh, D, T)
    q, k, v = _randn(rng, S, H, D), _randn(rng, S, Kh, D), _randn(rng, S, Kh, D)
    lens = jnp.asarray(lens, jnp.int32)
    ref, rk, rv = decode_step_attention(q, k, v, ck, cv, lens, layers - 1)
    out, ok, ov = paged_decode_attention(
        q, k, v, ck, cv, lens, layers - 1, interpret=True, **kw
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(rk))
    np.testing.assert_array_equal(np.asarray(ov), np.asarray(rv))
    # and the write touched one row a slot: layer 0 is as it was
    np.testing.assert_array_equal(np.asarray(ok[0]), np.asarray(ck[0]))


# ---------------------------------------------------------------------------
# (a) ragged paged decode attention, and the step's row written in place
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "heads",
    [(8, 8), (8, 2), (4, 1), (8, 4), (15, 5, 64), (6, 6, 8), (9, 3, 24), (4, 2, 128)],
    ids=[
        "mha8", "gqa8_2", "mqa4_1", "gqa8_4", "gqa15_5x64", "rep1_narrow", "width72",
        "gqa4_2x128",
    ],
)
def test_paged_decode_attention_parity(heads):
    """Every head layout the configs use. All the KV heads of a slot share a
    grid step, their queries laid block-diagonally over their tiles: GQA, MHA
    (rep 1 under a narrow head), one KV head (the diagonal is the head's own
    rows), SmolLM2-360M's 15/5 of 64, a heads * d that is no multiple of the
    128 lanes (heads of 24: nor does a head divide them), and heads of 128
    (granite's, OLMoE's)."""
    H, Kh, D = (*heads, 16)[:3]
    T = 32
    plan = decode_kernels.decode_plan(Kh, D, T, 4, num_slots=5, block_t=8, interpret=True)
    assert plan == (Kh, 8, 1) and plan.block_diagonal == (Kh > 1)
    # ragged: empty slot, mid-page, last live row, exactly T, wrapped
    _assert_decode_parity(
        _rng(H * 31 + Kh), 5, H, Kh, D, T, [0, 5, T - 1, T, 2 * T + 3],
        block_t=8,
    )


@pytest.mark.parametrize("block_t", [128, None], ids=["tile128", "tile256"])
@pytest.mark.parametrize("heads", [(15, 5), (4, 4)], ids=["gqa15_5", "mha"])
def test_paged_decode_writes_the_row_in_place(heads, block_t):
    """A 256-row ring in 128-row tiles, as on the chip, and as the one 256-row
    tile of which only the 128-row block that holds the row goes back: the
    written row at ring row 0, at both sides of the 128-row edge, at the last
    row, and the same again once the ring has wrapped. Every ring row holds
    something (``_ring``), so the row at ``lens % T`` is a stale one."""
    H, Kh = heads
    T = 256
    lens = [0, 127, 128, T - 1, T, T + 127, T + 128, 3 * T + 5]
    _assert_decode_parity(_rng(H), len(lens), H, Kh, 8, T, lens, block_t=block_t)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_paged_decode_stale_row_does_not_leak(dtype):
    """The attention reads the tile as the cache holds it and patches the
    step's row into the scores and the values. A wrapped ring holds an old
    row at ``lens % T``: made huge here, it must show nowhere in the output;
    and in bf16 (16-bit rows move as 32-bit words) the caches still come back
    bit for bit."""
    S, H, Kh, D, T = 7, 6, 2, 16, 256
    lens = jnp.asarray([0, 127, 128, T - 1, T, T + 128, 3 * T + 5], jnp.int32)
    rng = _rng(23)
    ck, cv = _ring(rng, 2, S, Kh, D, T)
    at = (1, jnp.arange(S), slice(None), slice(None), jnp.mod(lens, T))
    ck, cv = ck.at[at].set(1e4).astype(dtype), cv.at[at].set(-1e4).astype(dtype)
    q, k, v = (_randn(rng, S, n, D).astype(dtype) for n in (H, Kh, Kh))
    ref, rk, rv = decode_step_attention(q, k, v, ck, cv, lens, 1)
    out, ok, ov = paged_decode_attention(q, k, v, ck, cv, lens, 1, interpret=True)
    assert float(jnp.max(jnp.abs(ref.astype(jnp.float32)))) < 10  # the oracle has no trace of it
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol)
    np.testing.assert_array_equal(np.asarray(ok, np.float32), np.asarray(rk, np.float32))
    np.testing.assert_array_equal(np.asarray(ov, np.float32), np.asarray(rv, np.float32))


@pytest.mark.parametrize(
    "cell,shape,plan",
    [
        # (kv heads, head size, ring rows, bytes an element, slots) ->
        # (heads, rows, slots) a grid step; the eight configurations first
        ("serve-360m-batch", (5, 64, 256, 2, 256), (5, 256, 8)),
        ("serve-olmoe-fewshot", (16, 128, 3200, 2, 16), (16, 128, 1)),
        ("serve-granite-h-docqa", (8, 128, 2176, 2, 32), (8, 128, 1)),
        ("serve-glm-flash-agent", (1, 576, 2048, 2, 64), (1, 256, 1)),  # (its ring takes another kernel)
        ("serve-zaya1-reason", (2, 128, 1536, 2, 128), (2, 512, 1)),
        ("serve-evabyte-complete", (32, 128, 2048, 2, 24), (8, 256, 1)),
        ("serve-keye-videoqa", (4, 128, 16896, 2, 1), (4, 512, 1)),
        ("serve-1.7b-chat", (32, 64, 2048, 2, 64), (16, 256, 1)),
        ("float32", (5, 64, 256, 4, 1), (5, 256, 1)),
        ("a_ring_of_one_512_row_tile", (2, 128, 512, 2, 1), (2, 512, 1)),
        ("a_ring_512_does_not_divide", (2, 128, 768, 2, 64), (2, 256, 1)),
        ("rows_off_the_sublanes", (4, 8, 256, 2, 64), (1, 256, 1)),
        ("head_dim_12", (2, 12, 256, 4, 64), None),
        ("ring_of_96_rows", (2, 16, 96, 4, 64), None),
    ],
    ids=lambda x: x if isinstance(x, str) else None,
)
def test_decode_plan_is_a_function_of_shapes(cell, shape, plan):
    """The plan at the cells' shapes, on the chip (not interpreted): a grid
    step filled to the tile budget by heads (all the KV heads of a slot that
    fit 512 KB of K tile under one pair of MXU calls), then by rows (512 a
    tile where the heads leave room and 512 divides the ring), then by slots
    (where the whole ring of all the heads is one tile); one head a step where
    the heads' rows do not merge into whole sublane tiles; none (the XLA path)
    where the kernel cannot tile the shape."""
    *shape, num_slots = shape
    got = decode_kernels.decode_plan(*shape, num_slots=num_slots, interpret=False)
    assert got == plan
    if plan:
        assert got.block_diagonal == (plan[0] > 1)
        assert num_slots % got.slots == 0 and 128 % got.slots == 0


@pytest.mark.parametrize(
    "num_slots,fit,slots",
    [(256, 3, 2), (256, 4, 4), (256, 9, 8), (24, 8, 8), (12, 8, 4), (7, 8, 1), (256, 0, 1), (384, 200, 8)],
)
def test_slots_a_step_divide_the_slots_and_the_lanes(monkeypatch, num_slots, fit, slots):
    """As many slots a grid step as the budget holds tiles, 8 at most, of the
    divisors of the slot count that divide the 128 lanes too (a step's slots
    lie in one block of the slots-as-lanes rows)."""
    monkeypatch.setattr(decode_kernels, "_SLOTS_TILE_BYTES", fit * 1024)
    assert decode_kernels._slots_per_step(num_slots, 1024) == slots


def _several_slots(monkeypatch, slots, Kh, D, T, itemsize=4):
    """Hold the plan of a one-tile ring to ``slots`` slots a grid step."""
    monkeypatch.setattr(decode_kernels, "_SLOTS_TILE_BYTES", slots * Kh * D * T * itemsize)


@pytest.mark.parametrize("heads", [(15, 5), (4, 4), (4, 1)], ids=["gqa15_5", "mha", "mqa"])
@pytest.mark.parametrize("slots", [2, 4])
def test_paged_decode_several_slots_a_grid_step(monkeypatch, slots, heads):
    """A ring of one 256-row tile under a plan of 2 and of 4 slots a grid
    step, against ``decode_step_attention`` and against the one-slot plan to
    the bit: ragged ``lens`` (0, mid-tile, ``T - 1``, wrapped past ``T``),
    neighbours whose rows fall in different 128-row blocks within one grid
    step (each goes back by a copy of its own), and 12 slots, which 4 divides
    and 8 would not."""
    H, Kh = heads
    S, D, T = 12, 8, 256
    lens = [0, 200, 127, 128, T - 1, 3, T, T + 127, T + 128, 3 * T + 5, 64, 255]
    _several_slots(monkeypatch, slots, Kh, D, T)
    plan = decode_kernels.decode_plan(Kh, D, T, 4, num_slots=S, interpret=True)
    assert plan == (Kh, T, slots) and plan.grid(S, Kh, T) == (S // slots, 1, 1)
    rng = _rng(slots * 7 + H)
    ck, cv = _ring(rng, 2, S, Kh, D, T)
    q, k, v = _randn(rng, S, H, D), _randn(rng, S, Kh, D), _randn(rng, S, Kh, D)
    lens = jnp.asarray(lens, jnp.int32)
    got = paged_decode_attention(q, k, v, ck, cv, lens, 1, interpret=True, return_stats=True)
    ref = decode_step_attention(q, k, v, ck, cv, lens, 1)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref[0]), atol=2e-6)
    monkeypatch.setattr(decode_kernels, "_SLOTS_TILE_BYTES", 0)
    one = paged_decode_attention(q, k, v, ck, cv, lens, 1, interpret=True, return_stats=True)
    for a, b, r in zip(got, one, (*ref, None)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        if r is not None and a.ndim == 5:  # both caches: the reference's, bit for bit
            np.testing.assert_array_equal(np.asarray(a), np.asarray(r))
    np.testing.assert_array_equal(np.asarray(got[1][0]), np.asarray(ck[0]))  # layer 0 as it was


def test_paged_decode_several_slots_in_16_bit_rows(monkeypatch):
    """The same in bf16, where a row moves into its block as 32-bit words:
    heads of 16 merge (whole sublane tiles of 16 rows), so 3 KV heads and 4
    slots share a grid step, and the caches come back bit for bit."""
    S, H, Kh, D, T = 8, 6, 3, 16, 256
    _several_slots(monkeypatch, 4, Kh, D, T, itemsize=2)
    assert decode_kernels.decode_plan(Kh, D, T, 2, num_slots=S, interpret=True) == (Kh, T, 4)
    rng = _rng(50)
    ck, cv = (c.astype(jnp.bfloat16) for c in _ring(rng, 2, S, Kh, D, T))
    q, k, v = (_randn(rng, S, n, D).astype(jnp.bfloat16) for n in (H, Kh, Kh))
    lens = jnp.asarray([0, 127, 128, T - 1, T, T + 128, 3 * T + 5, 77], jnp.int32)
    ref, rk, rv = decode_step_attention(q, k, v, ck, cv, lens, 1)
    out, ok, ov = paged_decode_attention(q, k, v, ck, cv, lens, 1, interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=2e-2)
    np.testing.assert_array_equal(np.asarray(ok, np.float32), np.asarray(rk, np.float32))
    np.testing.assert_array_equal(np.asarray(ov, np.float32), np.asarray(rv, np.float32))


@pytest.mark.parametrize("heads", [(8, 2), (4, 4)], ids=["gqa8_2", "mha"])
def test_paged_decode_512_row_tiles_match_256_row_tiles(heads):
    """A ring of 1,024 rows under the plan's own tile (512 rows: the heads
    leave room) and under 256 asked for, ``lens`` in the first tile and the
    last, at a tile's two edges, empty and wrapped: both caches bit for bit,
    and the reference's; the outputs to float32 rounding of each other and of
    the reference (one softmax either way, but an online softmax over wider
    tiles adds its terms in another order), and to the bit where the live rows
    are one tile under both."""
    H, Kh = heads
    S, D, T = 8, 8, 1024
    assert decode_kernels.decode_plan(Kh, D, T, 4, num_slots=S, interpret=True) == (Kh, 512, 1)
    assert decode_kernels.decode_plan(Kh, D, T, 4, block_t=256, interpret=True) == (Kh, 256, 1)
    rng = _rng(H + 512)
    ck, cv = _ring(rng, 2, S, Kh, D, T)
    q, k, v = _randn(rng, S, H, D), _randn(rng, S, Kh, D), _randn(rng, S, Kh, D)
    lens = jnp.asarray([0, 100, 511, 512, 700, T - 1, T, 2 * T + 300], jnp.int32)
    wide = paged_decode_attention(q, k, v, ck, cv, lens, 1, interpret=True, return_stats=True)
    narrow = paged_decode_attention(
        q, k, v, ck, cv, lens, 1, interpret=True, return_stats=True, block_t=256
    )
    for a, b in zip(wide[1:3], narrow[1:3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_allclose(np.asarray(wide[0]), np.asarray(narrow[0]), atol=1e-6)
    np.testing.assert_array_equal(np.asarray(wide[0][:2]), np.asarray(narrow[0][:2]))
    ref = decode_step_attention(q, k, v, ck, cv, lens, 1)
    np.testing.assert_allclose(np.asarray(wide[0]), np.asarray(ref[0]), atol=2e-6)
    np.testing.assert_array_equal(np.asarray(wide[1]), np.asarray(ref[1]))
    np.testing.assert_array_equal(np.asarray(wide[2]), np.asarray(ref[2]))
    # live tiles walked: half as many, rounded up
    assert np.asarray(wide[3])[:, 0].tolist() == [1, 1, 1, 2, 2, 2, 2, 2]
    assert np.asarray(narrow[3])[:, 0].tolist() == [1, 1, 2, 3, 3, 4, 4, 4]


def test_paged_decode_attention_default_tile_and_head_groups(monkeypatch):
    """No tile asked for: a 256-row ring is one tile, and a grid step takes
    as many KV heads as the tile budget holds (here 2 of 4)."""
    monkeypatch.setattr(decode_kernels, "_HEAD_TILE_BYTES", 2 * 8 * 256 * 4)
    assert decode_kernels._heads_per_step(4, 8 * 256 * 4) == 2
    _assert_decode_parity(_rng(3), 3, 8, 4, 8, 256, [0, 200, 300])


def test_paged_decode_attention_skips_dead_blocks():
    S, T, H, Kh, D = 4, 32, 4, 2, 16
    rng = _rng(1)
    ck, cv = _ring(rng, 1, S, Kh, D, T)
    q, k, v = _randn(rng, S, H, D), _randn(rng, S, Kh, D), _randn(rng, S, Kh, D)
    lens = jnp.asarray([0, 5, 17, 64], jnp.int32)
    out, _, _, stats = paged_decode_attention(
        q, k, v, ck, cv, lens, 0, block_t=8, interpret=True, return_stats=True
    )
    ref, _, _ = decode_step_attention(q, k, v, ck, cv, lens, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)
    # processed ring blocks per slot: ceil((min(lens, T-1)+1) / block_t),
    # the whole page only once lens covers it — dead blocks never ran
    expected = [1, 1, 3, 4]
    assert np.asarray(stats).tolist() == [[e] * Kh for e in expected]


@pytest.mark.parametrize(
    "T,D", [(16, 12), (96, 16)], ids=["head_dim_12", "ring_of_96_rows"]
)
def test_paged_decode_attention_untileable_shape_falls_back(T, D):
    """D % 8 != 0, or a ring that no 128-row tile divides: the XLA path,
    per call, with the same results and the same caches."""
    S, H, Kh = 2, 2, 2
    rng = _rng(2)
    ck, cv = _ring(rng, 1, S, Kh, D, T)
    q, k, v = _randn(rng, S, H, D), _randn(rng, S, Kh, D), _randn(rng, S, Kh, D)
    lens = jnp.asarray([3, T + 4], jnp.int32)
    ref, rk, rv = decode_step_attention(q, k, v, ck, cv, lens, 0)
    out, ok, ov, stats = paged_decode_attention(
        q, k, v, ck, cv, lens, 0, interpret=True, return_stats=True
    )
    assert stats is None  # no kernel ran
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(rk))
    np.testing.assert_array_equal(np.asarray(ov), np.asarray(rv))


# ten slots over a latent ring of 512 rows whose tile holds several 128-row
# blocks: empty, on both sides of a block's edge inside a tile and of a tile's
# edge, at the ring's last row, and past the wrap once and twice
_LENS_OVER_BLOCKS = (0, 127, 128, 255, 256, 383, 384, 511, 512 + 129, 1024 + 300)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("block_t", [8, 16, 32, 256, 512])
@pytest.mark.parametrize("layer", [0, 2])
def test_mla_decode_attention_parity(layer, block_t, dtype):
    """The latent kernel, interpreted, against the XLA absorbed path: slots
    that are empty, mid-page, at a tile's edge on both sides, at the ring's
    last row, and past the wrap (the row written at ``lens % T``, the whole
    ring live); more slots than one lane-block's share is not needed, but the
    slot's lane is picked by its index, so a few are enough to show it. A tile
    of 256 or 512 rows (a ring of 512) holds several of the 128-row blocks of
    which the row's alone goes back: ``lens`` on both sides of a block's edge
    inside a tile, at a tile's edge, at the ring's last row and past the wrap."""
    L, S, H, Dl, Dv, T = 3, 7, 4, 24, 16, 32
    lens = jnp.array([0, 5, 15, 16, 31, 40, 17], jnp.int32)
    if block_t > 32:
        S, T = 10, 512
        lens = jnp.array(_LENS_OVER_BLOCKS, jnp.int32)
    keys = jax.random.split(jax.random.key(layer * 10 + block_t), 3)
    cache = jax.random.normal(keys[0], cache_shape(L, S, T, 1, Dl), dtype)
    q = jax.random.normal(keys[1], (S, H, Dl), dtype)
    row = jax.random.normal(keys[2], (S, Dl), dtype)
    want, ring_x = latent_decode_step_attention(
        q, row, cache, lens, layer, scale=0.25, value_dim=Dv)
    got, ring_p = mla_decode_attention(
        q, row, cache, lens, layer, scale=0.25, value_dim=Dv, block_t=block_t, interpret=True)
    assert got.shape == (S, H, Dv)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(ring_p, np.float32), np.asarray(ring_x, np.float32))
    # one row a slot, in the one layer: everything else as it was
    changed = np.asarray(ring_p != cache)
    assert not changed[[i for i in range(L) if i != layer]].any()
    rows_written = np.asarray(jnp.mod(lens, T))
    for s_ in range(S):
        touched = np.flatnonzero(changed[layer, s_, 0].any(axis=0))
        assert set(touched) <= {int(rows_written[s_])}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("block_t", [8, 16, 256, 512])
@pytest.mark.parametrize("under", ["selection", "window", "window_of_a_tile"])
def test_mla_decode_attention_under_a_selection_and_under_a_window(under, block_t, dtype):
    """The latent kernel's two further operands, interpreted, against the XLA
    absorbed path (PR 54): under ``chosen`` only the indexer's rows of a slot's
    live rows enter the softmax (the step's own row among them or not; a tile
    with no chosen row); under ``window`` the ring wraps and a slot reads the
    rows of its last positions alone, before the wrap, across it, and where the
    window is as long as a tile; with ``live_only`` a slot at ``lens`` 0 is
    written nothing and its blocks come back as they were. Under a tile of 256
    or 512 rows (a ring of 512) the block that goes back is one of the tile's
    several: the row on both sides of a block's and of a tile's edge, a window
    that crosses either, and the slot at ``lens`` 0 handing block 0 back."""
    L, S, H, Dl, Dv, T = 2, 7, 4, 24, 16, 32
    lens, window = jnp.array([0, 5, 15, 16, 31, 40, 77], jnp.int32), 5
    if block_t > 16:
        S, T, window = 10, 512, 131
        lens = jnp.array(_LENS_OVER_BLOCKS, jnp.int32)
    keys = jax.random.split(jax.random.key(block_t), 4)
    cache = jax.random.normal(keys[0], cache_shape(L, S, T, 1, Dl), dtype)
    q = jax.random.normal(keys[1], (S, H, Dl), dtype)
    row = jax.random.normal(keys[2], (S, Dl), dtype)
    extra = {"live_only": True}
    if under == "selection":
        lens = jnp.minimum(lens, T - 1)  # a ring under an indexer holds its context
        live = jnp.arange(T)[None] <= lens[:, None]
        chosen = (jax.random.uniform(keys[3], (S, T)) < 0.4) & live
        # never an empty set (the selection keeps min(topk, live) rows), and the
        # step's own row in some slots, not in others
        own = jnp.arange(T)[None] == lens[:, None]
        chosen = jnp.where((jnp.arange(S) % 2 == 0)[:, None], chosen | own, chosen & ~own)
        extra["chosen"] = chosen.at[:, 0].set(True)
    else:
        extra["window"] = window if under == "window" else block_t
    want, ring_x = latent_decode_step_attention(
        q, row, cache, lens, 1, scale=0.25, value_dim=Dv, **extra)
    got, ring_p = mla_decode_attention(
        q, row, cache, lens, 1, scale=0.25, value_dim=Dv, block_t=block_t, interpret=True, **extra)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(  # slot 0 holds no sequence: its output is read by no one
        np.asarray(got[1:], np.float32), np.asarray(want[1:], np.float32), rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(ring_p, np.float32), np.asarray(ring_x, np.float32))
    np.testing.assert_array_equal(np.asarray(ring_p[:, 0], np.float32), np.asarray(cache[:, 0], np.float32))
    if under != "selection":  # what the window's mask is: the rows of the last positions
        from opendiloco_tpu.ops.attention import ring_window_rows

        reads = np.asarray(ring_window_rows(lens, T, extra["window"]))
        for s_ in range(1, S):
            at = int(lens[s_])
            rows = {p % T for p in range(max(0, at - extra["window"] + 1), at + 1)}
            assert set(np.flatnonzero(reads[s_])) == rows


@pytest.mark.parametrize(
    "block_t,back", [(512, 128), (256, 128), (128, 128), (8, 8), (0, 0)],
    ids=["tile_512", "tile_256", "tile_128", "interpreted_8", "no_tile"],
)
def test_mla_rows_written_back(block_t, back):
    """What a slot's step hands back of the tile that holds its row: the 128
    rows around it, the tile where it is smaller, nothing without a tile; and
    the kernel's aliased output block is that many rows wide."""
    assert decode_kernels.mla_rows_written_back(block_t) == back
    if not block_t:
        return
    T, Dl = 4 * max(block_t, 8), 24
    fn = lambda q, row, cache, lens: mla_decode_attention(
        q, row, cache, lens, 0, scale=0.5, value_dim=16, block_t=block_t, interpret=True)
    args = (jnp.ones((3, 2, Dl)), jnp.ones((3, Dl)), jnp.zeros(cache_shape(1, 3, T, 1, Dl)),
            jnp.array([0, 4, 9], jnp.int32))
    (call,) = [e for e in jax.make_jaxpr(fn)(*args).jaxpr.eqns if e.primitive.name == "pallas_call"]
    grid = call.params["grid_mapping"]
    blocks = [tuple(getattr(b, "block_size", None) for b in m.block_shape) for m in grid.block_mappings]
    assert blocks[grid.num_inputs - 1] == (None, None, None, Dl, block_t)  # the tile read
    assert blocks[-1] == (None, None, None, Dl, back)  # the block handed back


def test_mla_decode_attention_untileable_shape_falls_back():
    """A ring no tile divides (interpreted: 30 rows, tile 8), or a row off
    the sublanes, keeps the XLA path: same results, no kernel."""
    for T, Dl, Dv in ((30, 24, 16), (32, 20, 12)):
        cache = jnp.zeros(cache_shape(2, 3, T, 1, Dl), jnp.float32)
        q = jnp.ones((3, 2, Dl)); row = jnp.ones((3, Dl)); lens = jnp.array([0, 4, 9], jnp.int32)
        fn = lambda *a: mla_decode_attention(*a, 1, scale=0.5, value_dim=Dv, block_t=8, interpret=True)
        assert "odtp_mla_decode_attn" not in str(jax.make_jaxpr(fn)(q, row, cache, lens))
        got, _ = fn(q, row, cache, lens)
        want, _ = latent_decode_step_attention(q, row, cache, lens, 1, scale=0.5, value_dim=Dv)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_ring_tile_is_a_multiple_of_128_rows_on_the_chip(monkeypatch):
    tile = decode_kernels._ring_block
    assert tile(256, None, False) == 256 and tile(3200, None, False) == 128
    assert tile(96, None, False) == 0 and tile(24, None, True) == 0
    assert tile(32, 8, True) == 8 and tile(32, 8, False) == 0
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "128")
    assert tile(256, None, False) == 128 and tile(192, None, False) == 0


def test_page_out_page_in_round_trip_is_bit_equal():
    """The host tier's contract over the rows-minor storage: a slot's pages
    after kernel-written decode steps, fetched as rows and inserted into
    another slot, are the same bytes, and attend the same."""
    S, H, Kh, D, T, L = 3, 4, 2, 8, 128, 2
    rng = _rng(9)
    ck, cv = _ring(rng, L, S, Kh, D, T)
    lens = jnp.asarray([40, 0, 7], jnp.int32)
    for _ in range(3):  # three steps' rows, written by the kernel
        for layer in range(L):
            _, ck, cv = paged_decode_attention(
                _randn(rng, S, H, D), _randn(rng, S, Kh, D),
                _randn(rng, S, Kh, D), ck, cv, lens, layer, interpret=True,
            )
        lens = lens + 1
    rows = 64  # a page-out bucket beyond slot 0's 43 live rows
    pk, pv = fetch_pages(ck, cv, jnp.int32(0), rows)
    assert pk.shape == (L, rows, Kh, D)
    ck2, cv2 = cache_insert(ck, cv, pk, pv, jnp.int32(1))
    for a in (ck2, cv2):
        np.testing.assert_array_equal(
            np.asarray(a[:, 1, :, :, :rows]), np.asarray(a[:, 0, :, :, :rows])
        )
    q = _randn(rng, S, H, D)
    both = jnp.asarray([43, 43, 0], jnp.int32)
    out = decode_attention(q.at[1].set(q[0]), *layer_pages(ck2, cv2, 1), both)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(out[1]))


# ---------------------------------------------------------------------------
# (b) the continued prefill's attention and its rows
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("heads", [(8, 2), (4, 1), (4, 4)])
def test_tiled_attention_is_one_softmax_over_the_slots_rows(heads):
    """3 queries at positions 13, 14, 15 of a slot of 32 rows, every row up
    to a query's own chosen: tiles of 8 rows under an online softmax (the
    last two never visited) against one softmax over the rows; and under a
    selection that leaves some tiles with no chosen row."""
    H, Kh = heads
    T, C, D, plen = 32, 3, 16, 13
    rng = _rng(H)
    q = _randn(rng, C, H, D)
    (ck, cv) = (x[0] for x in _pages(rng, 1, Kh, D, T))  # [Kh, D, T]
    seen = jnp.arange(T)[None] <= plen + jnp.arange(C)[:, None]
    few = seen & (jnp.arange(T)[None] % 9 < 2)

    def plain(chosen):
        qg = q.reshape(C, Kh, H // Kh, D)
        s = jnp.einsum("cgrd,gdt->cgrt", qg, ck) * D**-0.5
        p = jax.nn.softmax(jnp.where(chosen[:, None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("cgrt,gdt->cgrd", p, cv).reshape(C, H, D)

    for chosen in (seen, few):
        out = tiled_sparse_attention(q, ck, cv, chosen, plen + C, 8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(plain(chosen)), atol=2e-6)
        whole = tiled_sparse_attention(q, ck, cv, chosen, plen + C, T)  # a ring of one tile
        np.testing.assert_allclose(np.asarray(whole), np.asarray(out), atol=2e-6)


@pytest.mark.parametrize("start, count", [(0, 5), (20, 8), (27, 5), (29, 3), (31, 1)])
def test_a_runs_rows_land_where_its_bucket_would_pass_the_rings_end(start, count):
    """A run of ``count`` rows in a bucket of 8 into rows [start, start +
    count) of a ring of 32: the block written is the ring's last 8 rows where
    ``start + 8`` would pass its end, the run's rows moved down within it;
    nothing else changes, in this slot, layer or any other."""
    L, S, Kh, D, T, C = 2, 3, 2, 8, 32, 8
    rng = _rng(start)
    ck, cv = _ring(rng, L, S, Kh, D, T)
    k, v = _randn(rng, C, Kh, D), _randn(rng, C, Kh, D)
    gk, gv = jax.jit(lambda *a: layer_rows_insert(*a, whole_chunks=False))(
        ck, cv, 1, 2, k, v, start, count)
    for got, before, rows in ((gk, ck, k), (gv, cv, v)):
        want = np.array(before)
        want[1, 2, :, :, start : start + count] = np.moveaxis(np.asarray(rows[:count]), 0, -1)
        np.testing.assert_array_equal(np.asarray(got), want)
    if start + C <= T:  # whole chunks: the same block, by the path a prompt's chunks take
        wk, _ = layer_rows_insert(ck, cv, 1, 2, k, v, start, count)
        np.testing.assert_array_equal(np.asarray(wk), np.asarray(gk))


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def test_the_platform_chooses_where_nothing_is_passed(monkeypatch):
    """No name: the kernels on a TPU backend, the XLA paths elsewhere. A name is
    itself (the tests' seam), and no environment name is read."""
    assert jax.default_backend() != "tpu"
    assert resolve_decode_kernel() == resolve_decode_kernel(None) == "xla"
    assert resolve_decode_kernel("xla") == "xla"
    assert resolve_decode_kernel("pallas") == "pallas"
    monkeypatch.setenv("ODTP_DECODE_KERNEL", "pallas")
    assert resolve_decode_kernel() == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_decode_kernel() == "pallas"
    assert resolve_decode_kernel("xla") == "xla"
    for gone in ("auto", "mosaic", ""):
        with pytest.raises(ValueError, match="unknown decode kernel"):
            resolve_decode_kernel(gone)


# ---------------------------------------------------------------------------
# engine-level token parity
# ---------------------------------------------------------------------------


@pytest.fixture
def small_tiles(request, monkeypatch):
    """The engines below keep 24-row rings (cheap to wrap); interpreted, the
    kernels cut them into 8-row tiles instead of leaving them to XLA (EVA's
    two rings, a window of 16 rows and 24 pooled ones, into tiles of 4)."""
    family = getattr(request.node, "callspec", None) and request.node.callspec.params.get("family")
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "4" if family == "eva" else "8")


def _runs_the_decode_kernel(engine) -> bool:
    S = engine.num_slots
    vec = jnp.zeros((S,), jnp.int32)
    jaxpr = str(jax.make_jaxpr(engine._decode)(
        engine.params, vec, vec, engine.cache_k, engine.cache_v,
        *engine._ssm, *engine._cca, *engine._eva, *engine._index,
    ))
    return "odtp_paged_decode_attn" in jaxpr or "odtp_mla_decode_attn" in jaxpr


# the architecture families the benchmark's cells serve, each the tiny
# configuration its own suite builds: (configuration, parameters)
FAMILIES = {
    **test_serve_deferred_admit.KINDS,
    "eva": lambda _: test_evabyte.model()[1:],
}
_EVA_GEOMETRY = dict(max_context=6 * test_evabyte.WINDOW, prefill_buckets=(16, 32))


def _make_engine(tiny_cfg, decode_kernel, family="dense", **kw):
    cfg, params = FAMILIES[family](tiny_cfg)
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_context", 24)
    kw.setdefault("prefill_buckets", (8, 16))
    if cfg.eva:
        kw.update(_EVA_GEOMETRY)
    kw.setdefault("compute_dtype", jnp.float32)
    return ServeEngine(cfg, params, decode_kernel=decode_kernel, **kw)


def _generate(engine, prompt, n, slot=0):
    tok, _ = engine.admit(slot, prompt)
    toks = [tok]
    cache_len = len(prompt)
    S = engine.num_slots
    for _ in range(n - 1):
        tokens = np.zeros((S,), np.int32)
        lens = np.zeros((S,), np.int32)
        tokens[slot], lens[slot] = toks[-1], cache_len
        nxt, _ = engine.decode_step(tokens, lens)
        toks.append(int(nxt[slot]))
        cache_len += 1
    return toks


@pytest.mark.parametrize("family", list(FAMILIES))
def test_engine_token_streams_identical(tiny_cfg, family, small_tiles):
    rng = _rng(11)
    # both prefill buckets, and enough new tokens to wrap the T=24 ring (EVA:
    # to restart its window of 16 and read the pooled ring)
    vocab = FAMILIES[family](tiny_cfg)[0].vocab_size
    prompts = [rng.integers(1, vocab, 5).tolist(), rng.integers(1, vocab, 12).tolist()]
    e_x = _make_engine(tiny_cfg, "xla", family)
    e_p = _make_engine(tiny_cfg, "pallas", family)
    assert (e_x.decode_kernel, e_p.decode_kernel) == ("xla", "pallas")
    assert _runs_the_decode_kernel(e_p) and not _runs_the_decode_kernel(e_x)
    for slot, prompt in enumerate(prompts):
        tx = _generate(e_x, prompt, 20, slot=slot)
        tp = _generate(e_p, prompt, 20, slot=slot)
        assert tx == tp


@pytest.mark.parametrize("family", list(FAMILIES))
def test_batcher_token_streams_identical(tiny_cfg, family, small_tiles):
    rng = _rng(17)
    vocab = FAMILIES[family](tiny_cfg)[0].vocab_size
    prompts = [rng.integers(1, vocab, n).tolist() for n in (4, 9, 14)]
    results = []
    for kernel in ("xla", "pallas"):
        engine = _make_engine(tiny_cfg, kernel, family, num_slots=4)
        batcher = ContinuousBatcher(engine).start()
        try:
            reqs = []
            for p in prompts:
                reqs.append(batcher.submit(p, max_new_tokens=8))
                time.sleep(0.01)
            for r in reqs:
                assert r.wait(120) and r.error is None
            results.append([list(r.tokens) for r in reqs])
        finally:
            batcher.stop()
    assert results[0] == results[1]


def test_engine_plan_stats_are_zeros_on_the_xla_path(tiny_cfg):
    eng = _make_engine(tiny_cfg, "xla")
    out = eng.decode_plan_stats()
    assert set(out) == {
        "decode_plan_heads", "decode_plan_block_t", "decode_plan_block_diagonal",
        "decode_plan_slots", "decode_grid_steps",
    }
    assert all(v == 0 for v in out.values())  # the XLA path: no kernel, no plan


def test_engine_plan_stats_carry_the_plan(tiny_cfg, small_tiles):
    """Under ``pallas`` ``decode_plan_stats()`` (and so ``GET /stats``) says
    which form of the decode kernel the engine's shapes take."""
    eng = _make_engine(tiny_cfg, "pallas")
    assert _runs_the_decode_kernel(eng)
    out = eng.decode_plan_stats()
    want = decode_kernels.decode_plan(
        tiny_cfg.kv_heads, tiny_cfg.head_dim, eng.max_context, 4,
    )
    assert want is not None
    assert out["decode_plan_heads"] == want.heads
    assert out["decode_plan_block_t"] == want.block_t == 8
    assert out["decode_plan_block_diagonal"] == float(want.block_diagonal)
    # three tiles a ring: a grid step is one slot's, and a decode step makes
    # slots x head groups x tiles of them a layer
    assert out["decode_plan_slots"] == want.slots == 1
    steps = tiny_cfg.num_hidden_layers * 2 * (tiny_cfg.kv_heads // want.heads) * 3
    assert out["decode_grid_steps"] == steps
    # what ``GET /stats`` carries
    assert ContinuousBatcher(eng).stats()["decode_plan"] == {
        "serve_decode_plan_heads": want.heads, "serve_decode_plan_block_t": 8,
        "serve_decode_plan_block_diagonal": int(want.block_diagonal),
        "serve_decode_plan_slots": 1, "serve_decode_grid_steps": steps,
    }


def test_engine_plan_of_several_slots_a_step(tiny_cfg, monkeypatch):
    """An engine whose ring is one tile: ``decode_plan_stats()`` and ``GET /stats`` say how
    many slots share a grid step and how many grid steps that leaves, and the
    engine's tokens under that plan are the XLA path's."""
    kw = dict(num_slots=4, max_context=128, prefill_buckets=(8,))
    row = tiny_cfg.kv_heads * tiny_cfg.head_dim * 128 * 4
    monkeypatch.setattr(decode_kernels, "_SLOTS_TILE_BYTES", 2 * row)
    eng = _make_engine(tiny_cfg, "pallas", **kw)
    stats = eng.decode_plan_stats()
    assert stats["decode_plan_slots"] == 2 and stats["decode_plan_block_t"] == 128
    assert stats["decode_grid_steps"] == tiny_cfg.num_hidden_layers * 2
    prompt = list(range(3, 9))
    assert _generate(eng, prompt, 6, slot=1) == _generate(
        _make_engine(tiny_cfg, "xla", **kw), prompt, 6, slot=1
    )


# --- a window over a ring that wraps; the band of a chunk (PR 56) -------------


@pytest.mark.parametrize("rep, window, t, block_t", [
    (6, 0, 32, 8), (9, 5, 16, 4), (9, 12, 32, 8), (6, 9, 32, 8), (9, 7, 8, 8), (6, 8, 8, 4),
])
def test_paged_decode_under_a_window_is_the_xla_form(rep, window, t, block_t):
    """``paged_decode_attention(window=, live_only=)`` interpreted against
    ``decode_step_attention`` under the same arguments, at 6 and 9 query heads
    a KV head: a ring that has not wrapped, one that has several times, a
    window that crosses one, two and three tiles, a ring of one tile, and a
    slot at ``lens`` 0, which is written nothing; the rings to the bit."""
    from opendiloco_tpu.ops.attention import decode_step_attention
    from opendiloco_tpu.ops.decode_kernels import paged_decode_attention

    rng = np.random.default_rng(rep * 100 + window)
    s, kh, d, layers = 5, 2, 16, 2
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    ck, cv = draw(layers, s, kh, d, t), draw(layers, s, kh, d, t)
    for lens in ([0, 3, t - 1, t, 2 * t + 3], [2, 0, 9, 5 * t - 1, 33]):
        lens = jnp.asarray(lens, jnp.int32)
        q, k, v = draw(s, kh * rep, d), draw(s, kh, d), draw(s, kh, d)
        want = decode_step_attention(q, k, v, ck, cv, lens, 1, window=window, live_only=True)
        got = paged_decode_attention(
            q, k, v, ck, cv, lens, 1, window=window, live_only=True, block_t=block_t, interpret=True)
        live = np.asarray(lens) > 0
        np.testing.assert_allclose(np.asarray(got[0])[live], np.asarray(want[0])[live], atol=2e-6)
        for ours, theirs, before in zip(got[1:], want[1:], (ck, cv)):
            np.testing.assert_array_equal(ours, theirs)
            np.testing.assert_array_equal(ours[:, ~live], before[:, ~live])  # nothing written


@pytest.mark.parametrize("rep, chunk, window, ring, block", [
    (6, 8, 5, 16, 8), (9, 16, 5, 32, 8), (9, 16, 12, 48, 4), (6, 8, 9, 24, 8),
])
def test_the_band_of_a_chunk_is_the_masked_tiles(rep, chunk, window, ring, block):
    """``banded_chunk_attention`` against ``tiled_sparse_attention`` over every
    tile of the ring under the window's mask, and both against
    ``window_attention`` over the whole sequence: chunk after chunk into a ring
    that wraps, at 6 and 9 query heads a KV head, a window within a block and
    one that reaches three blocks back."""
    from opendiloco_tpu.models.ring_cache import layer_rows_insert
    from opendiloco_tpu.ops.attention import (
        band_block, banded_chunk_attention, ring_window_rows, tiled_sparse_attention,
        window_attention,
    )

    assert band_block(chunk, ring, window, block) == block
    rng = np.random.default_rng(rep + window)
    kh, d, total = 2, 16, 5 * chunk
    draw = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k, v = draw(total, kh * rep, d), draw(total, kh, d), draw(total, kh, d)
    want = window_attention(q[None], k[None], v[None], window)[0]
    ck, cv = jnp.zeros((1, 1, kh, d, ring)), jnp.zeros((1, 1, kh, d, ring))
    for plen in range(0, total, chunk):
        rows = slice(plen, plen + chunk)
        ck, cv = layer_rows_insert(ck, cv, 0, 0, k[rows], v[rows], plen % ring, chunk)
        got = banded_chunk_attention(q[rows], ck[0, 0], cv[0, 0], plen, window, block)
        reads = ring_window_rows(jnp.arange(plen, plen + chunk), ring, window)
        masked = tiled_sparse_attention(q[rows], ck[0, 0], cv[0, 0], reads, ring, block)
        np.testing.assert_allclose(got, want[rows], atol=3e-6)
        np.testing.assert_allclose(masked, want[rows], atol=3e-6)
    assert band_block(2048, 4096, 512) == 512 and band_block(8, 12, 5) == 0  # a ring of no whole blocks


# --- a decode step over chosen blocks (PR 61): the tiles that hold none stay unread ---


def _chosen_blocks(rng, lens, sizes, kh, blocks):
    """A choice as ``ops.attention.choose_blocks`` makes it: the forced blocks
    and random others up to ``topk``, every block up to the slot's own under
    ``dense_len``; nothing for a slot that holds no sequence."""
    chosen = np.zeros((len(lens), kh, blocks), bool)
    for s, n in enumerate(lens):
        own = n // sizes.block_size
        for g in range(kh):
            if n == 0:
                continue
            if n + 1 < sizes.dense_len:
                chosen[s, g, : own + 1] = True
                continue
            forced = {0, *range(max(own - sizes.window_size // sizes.block_size + 1, 0), own + 1)}
            free = [b for b in range(own + 1) if b not in forced]
            more = rng.choice(free, min(len(free), sizes.topk - len(forced)), replace=False)
            chosen[s, g, list(forced | set(int(b) for b in more))] = True
    return jnp.asarray(chosen)


@pytest.mark.parametrize("rep, lens", [
    (16, (0, 37, 200, 255)),  # an empty slot, one under ``dense_len``, two past it; 16 heads a KV head
    (4, (47, 48, 129, 7)),  # both sides of ``dense_len`` (the rows at the call: lens + 1) and of a tile's edge
])
def test_block_decode_attention_reads_the_chosen_tiles_as_its_gather(rep, lens, monkeypatch):
    """``odtp_block_decode_attn`` interpreted against the XLA gather of the
    chosen blocks: the same output to rounding, the step's own row merged in
    under the one softmax, the rings untouched; and the lists its grid walks
    hold the tiles with a chosen block and no other."""
    from opendiloco_tpu.ops.attention import BlockSizes, block_decode_step_attention
    from opendiloco_tpu.ops.decode_kernels import (
        block_decode_attention, block_tile_lists, block_tile_plan, block_tiles_held,
    )

    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "16")
    sizes = BlockSizes(kernel_size=8, kernel_stride=4, block_size=8, topk=6, init_blocks=1,
                       window_size=16, dense_len=48)
    kh, d, t, layers = 2, 16, 256, 3
    s_ = len(lens)
    rng = np.random.default_rng(rep)
    keys = jax.random.split(jax.random.key(rep), 5)
    q = jax.random.normal(keys[0], (s_, kh * rep, d), jnp.float32)
    k, v = (jax.random.normal(kk, (s_, kh, d), jnp.float32) for kk in keys[1:3])
    ck, cv = (jax.random.normal(kk, (layers, s_, kh, d, t), jnp.float32) for kk in keys[3:])
    lens = jnp.asarray(lens, jnp.int32)
    chosen = _chosen_blocks(rng, np.asarray(lens), sizes, kh, t // 8)
    assert block_tile_plan(d, t, sizes, interpret=True) == 16
    want = block_decode_step_attention(q, k, v, chosen, ck, cv, lens, 1, sizes)
    got = block_decode_attention(q, k, v, chosen, ck, cv, lens, 1, sizes, interpret=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # a slot that holds nothing reads nothing: its own row alone
    empty = np.flatnonzero(np.asarray(lens) == 0)
    for s in empty:
        np.testing.assert_allclose(got[s], jnp.repeat(v[s], rep, axis=0), rtol=1e-6)
    tiles, counts, bits = (np.asarray(x) for x in block_tile_lists(chosen, lens, 8, 16, 16))
    by_tile = np.asarray(chosen).reshape(s_, kh, -1, 2)
    for s in range(s_):
        for g in range(kh):
            held = [i for i in range(t // 16)
                    if (by_tile[s, g, i] & (np.arange(2) * 8 + i * 16 < int(lens[s]))).any()]
            assert counts[s, g] == len(held) and list(tiles[s, g, : len(held)]) == held
            assert all(tiles[s, g, len(held):] == (held[-1] if held else 0))  # no index moves: no DMA
            for i, tile in enumerate(held):
                want_bits = sum(int(by_tile[s, g, tile, b] and tile * 16 + b * 8 < int(lens[s])) << b
                                for b in range(2))
                assert bits[s, g, i] == want_bits
    assert int(block_tiles_held(chosen, lens, sizes, t)[0]) == int(counts.sum())
    live = sum(kh * -(-int(n) // 16) for n in np.asarray(lens))
    assert int(counts.sum()) < live  # tiles stay unread


def test_ring_rows_sum_kernel_is_the_gather():
    """The rows a closing window is pooled from, summed where the ring lies:
    across a tile's edge, at the ring's start and at its end."""
    from opendiloco_tpu.ops.attention import ring_rows_sum as gather
    from opendiloco_tpu.ops.decode_kernels import ring_rows_sum

    ring = jax.random.normal(jax.random.key(3), (2, 5, 2, 16, 256), jnp.float32)
    first = jnp.asarray([0, 100, 120, 225, 128], jnp.int32)  # 120 + 31 crosses row 128; 225 + 31 ends the ring
    got = ring_rows_sum(ring, 1, first, 31, interpret=True)
    np.testing.assert_allclose(got, gather(ring, 1, first, 31), rtol=1e-5, atol=1e-5)
    assert got.shape == (5, 2, 16)


# --- a chunk's attention over a slot's pages with the scores in VMEM (PR 62) ---


def _block_choice(rng, kh, at, blocks, block, *, dense=False, leave_out=()):
    """Each query's blocks as ``choose_blocks`` would: block 0, its own and the
    one before, and a random one of the others up to its own (every block up
    to its own: ``dense``), none of ``leave_out``."""
    chosen = np.zeros((kh, len(at), blocks), bool)
    for g in range(kh):
        for i, pos in enumerate(at):
            own = pos // block
            if dense:
                chosen[g, i, : own + 1] = True
                continue
            chosen[g, i, [0, own, max(own - 1, 0), int(rng.integers(0, own + 1))]] = True
    chosen[..., list(leave_out)] = False
    return chosen


# what; heads (H, Kh); plen, count (of 16 queries); queries and ring rows a grid step
CHUNK_CASES = {
    "sala_first_chunk": ("blocks", (8, 2), 0, 16, 8, 8),
    "sala_mid_prompt": ("blocks", (8, 2), 32, 16, 8, 8),
    "sala_padded_last_chunk": ("blocks", (8, 2), 32, 5, 8, 8),
    "sala_two_tiles_a_step": ("blocks", (8, 2), 32, 16, 16, 16),
    "sala_dense_len_side": ("dense", (8, 2), 32, 16, 8, 8),
    "sala_a_tile_nobody_chose": ("skipped", (8, 2), 32, 16, 8, 8),
    "laguna_first_chunk": ("seen", (16, 8), 0, 16, 8, 8),
    "laguna_mid_prompt": ("seen", (16, 8), 24, 16, 8, 16),
    "laguna_padded_last_chunk": ("seen", (16, 8), 40, 3, 16, 8),
    "keye_rows_and_a_query_with_none": ("rows", (8, 2), 32, 16, 8, 8),
}


@pytest.mark.parametrize("case", list(CHUNK_CASES))
def test_chunk_attention_is_the_tiled_forms(case, monkeypatch):
    """``odtp_chunk_attn`` interpreted against ``tiled_block_attention`` (a
    selection by blocks of 4 rows, one a KV head) and ``tiled_sparse_attention``
    (the rows up to a query's own; a selection by rows): the same outputs to
    the rounding of a reordered float32 sum, the same ``visited``. 16 queries
    over a ring of 64 rows in tiles of 8. The grid steps it takes are the tiles
    before the chunk's last row, up to a block of queries' last, in which a
    query of the block chose something: a tile stepped over is never read (its
    rows hold NaN)."""
    from opendiloco_tpu.ops.attention import tiled_block_attention
    from opendiloco_tpu.ops.decode_kernels import chunk_attention, chunk_tiles_held

    what, (H, Kh), plen, count, bq, bk = CHUNK_CASES[case]
    monkeypatch.setattr(decode_kernels, "_CHUNK_QUERIES", bq)
    monkeypatch.setattr(decode_kernels, "_CHUNK_ROWS", bk)
    C, D, T, tile, block = 16, 16, 64, 8, 4
    rng = _rng(len(case))
    q = _randn(rng, C, H, D)
    ck, cv = (np.array(x[0]) for x in _pages(rng, 1, Kh, D, T))  # [Kh, D, T]
    at = plen + np.arange(C)
    live = plen + count
    seen = np.arange(T)[None] <= at[:, None]
    skipped = []
    if what == "seen":
        chosen, unit, rows = None, 1, seen
    elif what == "rows":
        rows = seen & (rng.random((C, T)) < 0.3)
        rows[5] = False  # a query that reads no row
        rows[:, 16:24] = False  # and a tile nobody chose
        chosen, unit, skipped = rows[None], 1, [2]
    else:
        skipped = [3] if what == "skipped" else []
        leave_out = [b for i in skipped for b in range(i * tile // block, (i + 1) * tile // block)]
        chosen = _block_choice(rng, Kh, at, T // block, block, dense=what == "dense", leave_out=leave_out)
        unit = block
    at = jnp.asarray(at, jnp.int32)
    if what in ("seen", "rows"):
        want = tiled_sparse_attention(q, jnp.asarray(ck), jnp.asarray(cv), jnp.asarray(rows), live, tile)
        want_visited = min(-(-live // tile), T // tile) - len(skipped)
    else:
        want, want_visited = tiled_block_attention(
            q, jnp.asarray(ck), jnp.asarray(cv), jnp.asarray(chosen), at, live, tile, block
        )
    ck, cv = ck.copy(), cv.copy()  # (``jnp.asarray`` may share a numpy buffer on the CPU)
    for i in skipped:  # never read: not masked, stepped over
        ck[..., i * tile : (i + 1) * tile] = np.nan
        cv[..., i * tile : (i + 1) * tile] = np.nan
    ck, cv = jnp.asarray(ck), jnp.asarray(cv)
    got, visited = chunk_attention(
        q, ck, cv, at, live, tile, None if chosen is None else jnp.asarray(chosen), unit, interpret=True
    )
    assert np.isfinite(np.asarray(got)[:count]).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6, atol=2e-6)
    assert int(visited) == int(want_visited)
    if what == "rows":
        assert not np.asarray(got)[5].any()  # no row read: zero
    if what == "dense":  # every row up to a query's own: the plain suffix's attention
        plain = tiled_sparse_attention(q, ck, cv, jnp.asarray(seen), live, tile)
        np.testing.assert_allclose(np.asarray(got), np.asarray(plain), rtol=2e-6, atol=2e-6)
    # the table the grid walks, from the same three rules
    nq, nk = C // bq, T // bk
    reads = np.broadcast_to(seen[None] if chosen is None else np.repeat(chosen, unit, -1) & seen, (Kh, C, T))
    by_step = reads.reshape(Kh, nq, bq, nk, bk).any(axis=(2, 4))
    last = np.asarray(at).reshape(nq, bq).max(axis=1)
    first = np.arange(nk) * bk
    by_step = by_step & (first < live)[None, None] & (first[None] <= np.asarray(last)[:, None])[None]
    if chosen is None or chosen.shape[0] == 1:
        by_step = by_step[:1]
    held = np.asarray(chunk_tiles_held(jnp.asarray(by_step)))
    taken = held == np.arange(nk)
    assert (taken == by_step).all()
    for row, steps in zip(held.reshape(-1, nk), by_step.reshape(-1, nk)):
        tiles = np.flatnonzero(steps)
        if not len(tiles):
            assert (row == -1).all()
            continue
        for ki in range(nk):  # the next tile it will read, fetched ahead; then nothing moves
            ahead = tiles[tiles >= ki]
            assert row[ki] == (ahead[0] if len(ahead) else tiles[-1])
    assert taken.sum() < Kh * nq * nk  # steps are skipped


# --- a chunk's latent attention over a slot's page with the scores in VMEM (PR 63) ---

# what the queries read; heads, heads a grid step; plen, count (of 16 queries); queries a grid step
LATENT_CHUNK_CASES = {
    "selection_mid_prompt": ("rows", 16, 8, 32, 16, 8),
    "seen_alone_mid_prompt": ("seen", 16, 8, 32, 16, 8),
    "selection_live_rows_inside_a_tile": ("rows", 16, 8, 32, 5, 16),
    "seen_alone_live_rows_at_a_tiles_edge": ("seen", 16, 8, 32, 8, 16),
    "selection_padded_last_chunk": ("padded", 16, 8, 40, 3, 8),
    "seen_alone_padded_last_chunk": ("seen_padded", 16, 8, 40, 11, 8),
    "selection_first_chunk": ("rows", 16, 8, 0, 16, 8),
    "seen_alone_first_chunk": ("seen", 16, 8, 0, 16, 16),
    "four_head_blocks": ("rows", 16, 4, 24, 16, 8),
    "heads_no_block_cuts": ("rows", 6, 8, 24, 16, 16),
}


@pytest.mark.parametrize("case", list(LATENT_CHUNK_CASES))
def test_latent_chunk_attention_is_the_tiled_form(case, monkeypatch):
    """``odtp_latent_chunk_attn`` interpreted against ``tiled_latent_attention``:
    the same o_lat to the rounding of a reordered float32 sum. 16 queries of
    rows of 24 values, the first 16 of them the values (``Dl`` no multiple of
    the value width), over a page of 64 rows in tiles of 8, under a selection
    and under the rows up to a query's own alone. The grid steps it takes are
    the tiles before ``live_rows`` in which a query of the block reads
    something: a tile stepped over is never read (its rows hold NaN), and a
    query that reads no row (a bucket's padding) comes out zero."""
    from opendiloco_tpu.ops.attention import tiled_latent_attention
    from opendiloco_tpu.ops.decode_kernels import chunk_tiles_held, latent_chunk_attention

    what, H, heads, plen, count, bq = LATENT_CHUNK_CASES[case]
    monkeypatch.setattr(decode_kernels, "_LATENT_HEADS", heads)
    monkeypatch.setattr(decode_kernels, "_CHUNK_QUERIES", bq)
    C, Dl, V, T, tile = 16, 24, 16, 64, 8
    rng = _rng(len(case))
    q = _randn(rng, C, H, Dl)
    page = np.array(_randn(rng, Dl, T))
    at = plen + np.arange(C)
    live = plen + count
    reads = np.arange(T)[None] <= at[:, None]
    skipped = []
    if what in ("rows", "padded"):
        reads = reads & (rng.random((C, T)) < 0.3)
        reads[min(5, count - 1)] = False  # a query that reads no row
        if plen >= 24:
            reads[:, 16:24] = False  # and a tile nobody reads
            skipped = [2]
    if what in ("padded", "seen_padded"):
        reads[count:] = False  # the bucket's padding
    sizes = dict(scale=Dl**-0.5, value_dim=V)
    want = tiled_latent_attention(q, jnp.asarray(page), jnp.asarray(reads), live, tile, **sizes)
    page = page.copy()  # (``jnp.asarray`` may share a numpy buffer on the CPU)
    for i in [*skipped, *range(-(-live // tile), T // tile)]:  # never read: not masked, stepped over
        page[:, i * tile : (i + 1) * tile] = np.nan
    got = latent_chunk_attention(
        q, jnp.asarray(page), jnp.asarray(reads), live, tile, **sizes, interpret=True
    )
    assert got.shape == (C, H, V) and got.dtype == q.dtype
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6, atol=2e-6)
    nothing = ~reads[:, : -(-live // tile) * tile].any(axis=1)
    assert nothing.any() == (what != "seen") and not np.asarray(got)[nothing].any()  # no row read: zero
    assert np.asarray(got)[~nothing].any(axis=(1, 2)).all()
    # the table the grid walks: the same for every head block
    nq, nk = C // bq, T // tile
    by_step = reads.reshape(nq, bq, nk, tile).any(axis=(1, 3)) & (np.arange(nk) * tile < live)[None]
    held = np.asarray(chunk_tiles_held(jnp.asarray(by_step)))
    assert ((held == np.arange(nk)) == by_step).all()
    assert by_step.sum() < nq * nk  # steps are skipped


# c, h, dl, value_dim, t, tile, decode_kernel, on the chip -> the form
LATENT_FORM_CASES = {
    "dots3_full_layers": ((512, 128, 576, 512, 25088, 512, "pallas"), True, "absorbed-pallas"),
    "dots3_full_layers_interpreted": ((512, 128, 576, 512, 25088, 512, "pallas"), False, "absorbed-pallas"),
    "dots3_sliding_layers_67_mb": ((512, 64, 1088, 1024, 1024, 512, "pallas"), True, "absorbed-xla"),
    "a_ring_tiles_do_not_cut": ((512, 128, 576, 512, 25088 + 256, 512, "pallas"), True, "absorbed-xla"),
    "decode_kernel_xla": ((512, 128, 576, 512, 25088, 512, "xla"), True, "absorbed-xla"),
    "off_the_chip_unasked": ((512, 128, 576, 512, 25088, 512, None), False, "absorbed-xla"),
    "rehearsal_widths": ((8, 4, 24, 16, 128, 8, "pallas"), False, "absorbed-xla"),
    "a_row_the_chip_does_not_tile": ((512, 128, 520, 512, 25088, 512, "pallas"), True, "absorbed-xla"),
    "a_chunk_past_a_block_of_queries": ((1000, 128, 576, 512, 24000, 1000, "pallas"), True, "absorbed-xla"),
}


@pytest.mark.parametrize("case", list(LATENT_FORM_CASES))
def test_latent_chunk_form_follows_from_what_the_call_sees(case, monkeypatch):
    """The rule that chooses a chunk's latent attention's form
    (``latent_chunk_form``): the kernel where ``decode_kernel`` resolves to the
    kernels, the ring is whole tiles, the chip tiles the blocks and the XLA
    form's float32 score tile (heads x chunk x tile x 4 B) reaches
    ``_PREFILL_SCORE_BYTES``: dots3-note-prev's full layers (134 MB) and not
    its sliding layers (67 MB)."""
    args, on_chip, form = LATENT_FORM_CASES[case]
    monkeypatch.setattr(decode_kernels, "_interpret", lambda interpret=None: not on_chip)
    assert decode_kernels.latent_chunk_form(*args) == form
