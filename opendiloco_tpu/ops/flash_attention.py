"""Pallas TPU flash attention (forward + backward), causal, GQA-aware.

TPU-native replacement for the reference's optional FlashAttention-2 CUDA
kernels (README.md:41-47, train_fsdp.py:107). FlashAttention-2-style online
softmax: never materializes the [T, T] score matrix; scores and softmax
statistics accumulate in float32 on the MXU/VPU while q/k/v stream through
VMEM tiles.

Layout: the HBM interface is the projections' own rows, q ``[B, T, Hq * D]``,
k and v ``[B, T, Hkv * D]``, and so are the output and the gradients: no
transpose and no head-major copy lies between ``q_proj`` and ``o_proj``.
Grid (batch, head group, q-block, k-block) with the k-block dimension
sequential ("arbitrary"): a step takes a tile of rows across the heads it
holds (``heads_a_step``: whole GQA groups filling 128-lane blocks, else the
whole row), moves it into VMEM scratch in units of 128 lanes (``lanes_of``:
two heads of 64; K and V packed as their lanes lie, a query head alone in a
unit, in its KV head's place and zero elsewhere: ``_packed_in``,
``_heads_in``; rotary, where the caller hands the tables, is applied on the
way), and walks the heads one after another over one traced body.
K/V stream through VMEM one tile of rows per step, fetched once for all the
query heads of their groups, while the online-softmax state (m, l, acc)
persists in VMEM scratch across k-steps. Per-step VMEM is O(block * heads a
step * D), independent of T, so sequence length is bounded by HBM, not
VMEM. The log-sum-exp and ``delta`` are ``[B, Hq, 1, T]``, rows as lanes.

Causal tiles above the diagonal are skipped with pl.when, and their
BlockSpec index maps clamp to the last needed tile so the revisited block
index elides the DMA too -- a skipped step costs neither compute nor HBM
traffic, only a grid step. Inside a grid step the ``[block_q, block_k]``
DMA tile is classified again (``_tiles``): a tile wholly on or below the
diagonal runs with no mask at all, and a tile the diagonal crosses is
walked as 128 x 128 compute sub-tiles (``_diagonal_items``): those above
the diagonal are not computed, those below run unmasked, and only the
ones on it meet a mask, whose offsets are static. ``causal_plan`` counts
what that comes to a head. The walk needs equal blocks; unequal ones
(only reachable through OPENDILOCO_TPU_FLASH_BLOCKS) keep their crossed
tiles whole under the mask, and full attention (``causal=False``: ring
attention's off-diagonal chunks) has no crossed tile.

Backward follows the standard FA2 recompute scheme: delta = rowsum(dO * O),
one kernel for dq (streaming k blocks), one for dk/dv (streaming q blocks,
accumulating over the rep q-heads of each kv head); under rotary dq and dk
are turned back in VMEM before they are written.
"""

from __future__ import annotations

import functools
import math
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from opendiloco_tpu.ops.pallas_util import (
    NEG_INF as _NEG_INF,
    pick_block as _pick_block,
)


# ---------------------------------------------------------------------------
# where the diagonal is
# ---------------------------------------------------------------------------

# rows (= columns) of the compute sub-tile a diagonal tile is walked in: the
# 128 lanes of a vector register, whatever the block and the head size. On
# the chip (PR 42, TPU v5e) 128 read fastest of 128 / 256 / 512 in all three
# kernels at d = 64 and in the forward at d = 128
_SUB_TILE = 128
# query rows an unmasked tile's forward update takes at a time: two halves
# of a 1,024-row tile run 10% faster than the whole (one half's matmuls
# beside the other's softmax); the backward kernels read the same either way
_FWD_ROWS = 512


class CausalPlan(NamedTuple):
    """What one head's ``[T, T]`` scores cost under ``causal_plan``'s
    arguments, counted in units of ``unit[0] x unit[1]`` score elements:
    the walk's ``c x c`` sub-tiles where diagonal tiles are walked, else
    whole ``[block_q, block_k]`` tiles (``c`` = 0)."""

    c: int
    unit: tuple
    computed: int  # matmuls, exps and accumulation run
    masked: int  # of those, the units the diagonal crosses: under the mask
    skipped: int  # no work at all (a skipped tile's grid step still passes)
    computed_share: float  # score elements computed over T x T
    masked_share: float

    def __str__(self) -> str:  # the train step's log line
        return (
            f"computed_share={self.computed_share:.5f} masked_share={self.masked_share:.5f} "
            f"sub_tile={self.c} computed={self.computed} masked={self.masked} "
            f"skipped={self.skipped} of {self.unit[0]}x{self.unit[1]} a head"
        )


def causal_plan(t: int, block_q: int, block_k: int, causal: bool) -> CausalPlan:
    """The three kernels' work a head, from shapes alone (they classify
    tiles alike): ``InnerTrainer`` logs it and the tests count with it."""
    nq, nk = t // block_q, t // block_k
    if not causal:
        return CausalPlan(0, (block_q, block_k), nq * nk, 0, 0, 1.0, 0.0)
    below = crossed = 0
    for q_lo in range(0, t, block_q):
        for k_lo in range(0, t, block_k):
            is_below, is_crossed = _tile_classes(q_lo, block_q, k_lo, block_k)
            below += is_below
            crossed += is_crossed
    if block_q == block_k:  # crossed tiles are walked
        c = _SUB_TILE  # divides every block: they are multiples of 128
        n = block_q // c
        unit = (c, c)
        computed = below * n * n + crossed * n * (n + 1) // 2
        masked = crossed * n
        total = nq * nk * n * n
    else:  # a crossed tile is computed and masked whole
        c, unit = 0, (block_q, block_k)
        computed, masked, total = below + crossed, crossed, nq * nk
    return CausalPlan(
        c, unit, computed, masked, total - computed,
        computed / total, masked / total,
    )


def _scale_on_operand(scale: float) -> bool:
    """A power of two (d = 64: 1/8) multiplies a ``[block, d]`` operand
    instead of the ``[block_q, block_k]`` scores: every product is the same
    bit for bit and one pass over the tile goes. Elsewhere (d = 128) the
    scale stays on the float32 scores."""
    return math.frexp(scale)[0] == 0.5


def _tile_classes(q_lo, block_q, k_lo, block_k):
    """(wholly on or below the diagonal, crossed by it) for the tile whose
    first query row is ``q_lo`` and first key column ``k_lo``; Python ints
    or traced grid indices."""
    below = k_lo + block_k - 1 <= q_lo
    reached = k_lo <= q_lo + block_q - 1  # true of every tile below
    return below, reached != below


def _lower_triangle(rows: int, cols: int, q_lo=0, k_lo=0, keys_first: bool = False):
    """q_pos >= k_pos over a ``[rows, cols]`` tile of scores (query rows by
    key columns; ``keys_first``: key rows by query columns). The offsets of
    a walk's sub-tiles are static, and the compiler folds their masks."""
    q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), int(keys_first))
    k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), int(not keys_first))
    return q_pos >= k_pos


def _diagonal_items(block: int, keys_first: bool = False) -> list:
    """The walk of a ``[block, block]`` tile on the diagonal, as (slice,
    slice, mask) items. By query rows (forward, dq): sub-block i against the
    key columns up to its own. ``keys_first`` (dkv): key sub-block j against
    the query rows from its own on. Either way the sub-tiles above the
    diagonal are in no item, and the mask touches the diagonal's alone."""
    c = _SUB_TILE
    items = []
    for i in range(block // c):
        own = slice(i * c, (i + 1) * c)
        if keys_first:
            mask = _lower_triangle(c, block - i * c, keys_first=True)
            items.append((own, slice(i * c, block), mask))
        else:
            mask = _lower_triangle(c, (i + 1) * c, q_lo=i * c)
            items.append((own, slice(0, (i + 1) * c), mask))
    return items


def _run(phases, items) -> None:
    """Run the generator ``phases(*item)`` of every item phase by phase:
    all items' first phase, then all items' second... The items are
    independent and each of an item's phases waits for the one before, so
    this order lets one item's matmuls run beside another's vector work
    (the forward's walk, item by item: 1.88 ms a call; so: 1.45)."""
    live, done = [phases(*item) for item in items], object()
    while live:
        live = [g for g in live if next(g, done) is not done]


def _div(a, b: int):
    """a // b and a % b of a head's index, a Python int (one head a step) or
    a loop's: never negative, so one instruction each (``//`` and ``%`` on a
    traced value are a dozen: half of a kernel's trace, once)."""
    return a // b if isinstance(a, int) else jax.lax.div(a, b)


def _rem(a, b: int):
    return a % b if isinstance(a, int) else jax.lax.rem(a, b)


def _over_heads(heads: int, fn) -> None:
    """``fn(h)`` for each head a grid step holds: one trace of the body
    under a loop on the scalar core (h indexes the scratch's leading
    dimension), or the body alone for one head."""
    if heads == 1:
        return fn(0)
    jax.lax.fori_loop(0, heads, lambda h, carry: (fn(h), carry)[1], 0)


def _tiles(phases, heads, causal, q_lo, block_q, k_lo, block_k, whole, keys_first=False):
    """One grid step of a kernel whose update of one head's one item is the
    generator ``phases(h, *item)``, head after head: the ``whole`` items,
    unmasked, for a tile wholly on or below the diagonal (and for every tile
    of full attention); for a tile the diagonal crosses, the walk where the
    blocks are equal, else the whole tile under its mask; nothing for a tile
    above the diagonal."""
    run = lambda items: _over_heads(
        heads, lambda h: _run(functools.partial(phases, h), items)
    )
    below, crossed = _tile_classes(q_lo, block_q, k_lo, block_k)
    pl.when(jnp.logical_or(not causal, below))(lambda: run(whole))
    if not causal:
        return

    @pl.when(crossed)
    def _diagonal():
        if block_q == block_k:
            return run(_diagonal_items(block_q, keys_first))
        first, second = (block_k, block_q) if keys_first else (block_q, block_k)
        mask = _lower_triangle(first, second, q_lo, k_lo, keys_first)
        run([(slice(0, first), slice(0, second), mask)])


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((), ())),
        preferred_element_type=jnp.float32,
    )


_NT = (1, 1)  # a @ b.T
_NN = (1, 0)  # a @ b


# ---------------------------------------------------------------------------
# rows in, heads in VMEM
# ---------------------------------------------------------------------------


def heads_a_step(hq: int, hkv: int, d: int) -> tuple:
    """(query heads, KV heads) one grid step holds, from the shapes alone: the
    fewest whole GQA groups whose K columns fill 128-lane blocks of the
    ``[B, T, Hkv * d]`` array (a head of 128: one; heads of 64 at 32 / 32: two;
    at 32 / 4: two KV heads and their sixteen query heads), else the whole row
    (15 / 5 heads of 64: a block's last dimension is a multiple of 128 or the
    array's own)."""
    rep = hq // hkv
    for g in range(1, hkv):
        if hkv % g == 0 and g * d % 128 == 0:
            return g * rep, g
    return hq, hkv


def lanes_of(d: int) -> tuple:
    """(heads, lanes) of one unit of the kernels' VMEM scratch: as many heads
    as fill the 128 lanes of a vector register (two of 64), a head alone from
    128 on. K, V and their gradients lie packed so, a unit as its 128-lane
    block of the rows came; a query head lies alone in a unit, in its KV
    head's place and zero elsewhere, so that a contraction over the unit's
    lanes is the head's own and an MXU pass is as wide as over 64."""
    pack = 128 // d if d < 128 and 128 % d == 0 else 1
    return pack, pack * d


class Rope(NamedTuple):
    """Rotary tables for the kernels, a row a position and a lane a value of
    one head, the head's ``d`` values repeated over a unit's lanes
    (``lanes_of``): ``cos`` [B, T, lanes] float32 (1 past a head's rotated
    values), ``sin`` [B, T, lanes] signed so that ``x * cos + swap(x) * sin``
    is the rotation (-sin over the first half of a head's ``rot`` rotated
    values, +sin over the second, 0 past them), ``swap`` exchanging the
    halves."""

    cos: jax.Array
    sin: jax.Array
    rot: int


def rope_rows(cos: jax.Array, sin: jax.Array, d: int) -> Rope:
    """``Rope`` for heads of ``d`` from the model's tables [B, T, 1, rot / 2]
    (``llama._rope_tables``): built once a step, outside the layers' scan."""
    b, t = cos.shape[:2]
    cos = cos.reshape(b, t, -1).astype(jnp.float32)
    sin = sin.reshape(b, t, -1).astype(jnp.float32)
    rot = 2 * cos.shape[-1]
    rest = jnp.ones((b, t, d - rot), jnp.float32)
    pack, _ = lanes_of(d)
    return Rope(
        jnp.tile(jnp.concatenate((cos, cos, rest), axis=-1), pack),
        jnp.tile(jnp.concatenate((-sin, sin, 0 * rest), axis=-1), pack),
        rot,
    )


def _swap_matrix(w: int, d: int, rot: int, dtype):
    """[w, w]: x @ it exchanges the two halves of each head's first ``rot``
    values of x [n, w] (heads of ``d``) and zeroes the rest."""
    r = jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (w, w), 1)
    at = _rem(r, d)  # the value's place in its head
    partner = jnp.where(at < rot // 2, r + rot // 2, r - rot // 2)
    return jnp.logical_and(c == partner, at < rot).astype(dtype)


def _turn(x, cos, sin, d: int, rot: int, back: bool = False, narrow=None):
    """x [n, heads * d] rotated by position under ``Rope``'s tables (their
    first lanes), in float32 (the model's ``_rope_apply`` rounds each product
    to the activations' dtype; this rounds once, where the caller casts);
    ``back``: by the opposite angle, which is the rotation's transpose and
    turns a gradient of rotated rows into one of the rows. The exchange of a
    head's halves is a matmul with a permutation: exact, and on the MXU,
    which these kernels leave idle most of a step. A float32 x (a gradient's
    sum) goes through it whole, six passes, or, where the caller will round
    the result to a ``narrow`` dtype, as its leading part and the rest in
    that dtype: two passes, sixteen bits where eight are kept."""
    w = x.shape[-1]
    part = narrow if x.dtype == jnp.float32 and narrow not in (None, jnp.float32) else x.dtype
    matrix = _swap_matrix(w, d, rot, part)

    def swap(y, **kw):
        return jax.lax.dot_general(
            y, matrix, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32, **kw
        )

    if part != x.dtype:
        lead = x.astype(part)
        swapped = swap(lead) + swap((x - lead.astype(jnp.float32)).astype(part))
    elif x.dtype == jnp.float32:
        swapped = swap(x, precision=jax.lax.Precision.HIGHEST)
    else:
        swapped = swap(x)
    swapped = swapped * sin[:, :w]
    return x.astype(jnp.float32) * cos[:, :w] + (-swapped if back else swapped)


def _units(width: int, lanes: int):
    """(unit, first lane, lanes held) over a tile of ``width`` lanes: whole
    units and, where the row ends inside one (fifteen heads of 64), the
    part."""
    return [(c, lo, min(lanes, width - lo)) for c, lo in enumerate(range(0, width, lanes))]


# The moves between a tile of rows and the scratch. Only the copies of whole
# units are unrolled code (two statements a unit); whatever works on a unit
# or a head (the rotation, a head's place) is one traced body under
# ``_over_heads``, so a kernel's trace does not grow with its heads: fifteen
# heads unrolled through these moves made the three kernels 5,200 equations
# for the parent's 1,000 and cost the 360M cell 8 s of ``setup_s``.


def _packed_in(src_ref, dst_scr, d, rope=None, rot: int = 0, scale=None) -> None:
    """A tile of rows ``[block, heads * d]`` as it came from HBM -> scratch
    ``[units, block, lanes]``, unit after unit as the lanes lie (the lanes a
    row's end leaves empty zeroed); then, unit after unit in place, rotated
    under ``rope`` (cos, sin refs ``[block, lanes]``) and scaled."""
    units, block, lanes = dst_scr.shape
    for c, lo, w in _units(src_ref.shape[1], lanes):
        dst_scr[c, :, :w] = src_ref[:, lo : lo + w]
        if w < lanes:
            dst_scr[c, :, w:] = jnp.zeros((block, lanes - w), dst_scr.dtype)
    if rope is None and scale is None:
        return

    def finish(c):
        x = dst_scr[c]
        if rope is not None:
            x = _turn(x, rope[0][...], rope[1][...], d, rot)
        if scale is not None:
            x = x * scale
        dst_scr[c] = x.astype(dst_scr.dtype)

    _over_heads(units, finish)


def _packed_out(src_scr, dst_ref, d, rope=None, rot: int = 0) -> None:
    """Scratch ``[units, block, lanes]`` float32, packed as the rows' lanes
    lie -> the output tile of rows; under ``rope`` turned back first, in
    place (a gradient of rotated rows)."""
    units, _, lanes = src_scr.shape
    if rope is not None:

        def finish(c):
            src_scr[c] = _turn(
                src_scr[c], rope[0][...], rope[1][...], d, rot, back=True, narrow=dst_ref.dtype
            )

        _over_heads(units, finish)
    for c, lo, w in _units(dst_ref.shape[1], lanes):
        dst_ref[:, lo : lo + w] = src_scr[c, :, :w].astype(dst_ref.dtype)


def _moved(x, places, d: int):
    """A unit x [n, lanes] with every head ``places`` (a traced count) places
    further on, the last coming round: a rotate of 32-bit lanes, made only
    where a head moves."""
    pack = x.shape[-1] // d
    y = x
    for s in range(1, pack):
        y = jax.lax.cond(
            places == s,
            lambda: pltpu.roll(x.astype(jnp.float32), s * d, 1).astype(x.dtype),
            lambda y=y: y,
        )
    return y


def _heads_in(src_ref, units_scr, dst_scr, d, place, rope=None, rot: int = 0, scale=None) -> None:
    """A tile of query rows (q, dO) ``[block, heads * d]`` -> scratch
    ``[heads, block, lanes]``, a head a unit: its ``d`` values in place
    ``place(h)`` of the unit (its KV head's in the packed K and V) and zero
    elsewhere. First packed, rotated and scaled like K (``units_scr``; a head
    of 128 lanes or more is a unit, and that is all), then head after head
    from its unit."""
    if units_scr is None:
        return _packed_in(src_ref, dst_scr, d, rope, rot, scale)
    _packed_in(src_ref, units_scr, d, rope, rot, scale)
    heads, block, lanes = dst_scr.shape
    pack = lanes // d
    lane = _div(jax.lax.broadcasted_iota(jnp.int32, (block, lanes), 1), d)

    def put(h):
        to = place(h)
        x = _moved(units_scr[_div(h, pack)], _rem(to - _rem(h, pack) + pack, pack), d)
        dst_scr[h] = jnp.where(lane == to, x, jnp.zeros_like(x))

    _over_heads(heads, put)


def _heads_out(src_scr, units_scr, dst_ref, d, place, rope=None, rot: int = 0, over=None) -> None:
    """Scratch ``[heads, block, lanes]`` float32, head h's values in place
    ``place(h)`` of its unit (whatever lies in the others is dropped) -> the
    output tile of rows ``[block, heads * d]``: head after head, divided by
    ``over(h)`` (a column: one division a row) where given, into its own
    lanes of the packed ``units_scr``, and from there like ``_packed_out``."""
    if units_scr is None:  # a head is a unit
        units_scr = src_scr
        if over is not None:

            def divide(h):
                src_scr[h] = src_scr[h] * (1.0 / over(h))

            _over_heads(src_scr.shape[0], divide)
        return _packed_out(units_scr, dst_ref, d, rope, rot)
    heads, block, lanes = src_scr.shape
    pack = lanes // d
    lane = _div(jax.lax.broadcasted_iota(jnp.int32, (block, lanes), 1), d)
    if dst_ref.shape[1] % lanes:  # the row ends inside the last unit: no head fills the rest
        units_scr[units_scr.shape[0] - 1] = jnp.zeros((block, lanes), jnp.float32)

    def take(h):
        y = src_scr[h]
        if over is not None:
            y = y * (1.0 / over(h))
        at, unit = _rem(h, pack), _div(h, pack)
        y = _moved(y, _rem(at - place(h) + pack, pack), d)
        units_scr[unit] = jnp.where(lane == at, y, units_scr[unit])

    _over_heads(heads, take)
    _packed_out(units_scr, dst_ref, d, rope, rot)


def _split(refs, n_in: int, rot: int):
    """A kernel's refs -> (its ``n_in`` operands, the query rows' (cos, sin)
    and the key rows', or None twice without rotary, the rest)."""
    if not rot:
        return refs[:n_in], None, None, refs[n_in:]
    t = refs[n_in : n_in + 4]
    return refs[:n_in], t[:2], t[2:], refs[n_in + 4 :]


def _vmem_limit(*arrays) -> int:
    """``vmem_limit_bytes`` for a call whose blocks (twice: double buffered)
    and scratch are ``arrays`` [(shape, dtype)]: their bytes with the last
    dimension padded to 128 lanes, and room for the score tiles."""
    total = 0
    for shape, dtype in arrays:
        lanes = -(-shape[-1] // 128) * 128
        rows = -(-(shape[-2] if len(shape) > 1 else 1) // 8) * 8
        total += math.prod(shape[:-2]) * rows * lanes * jnp.dtype(dtype).itemsize
    return int(min(max(total + (24 << 20), 32 << 20), 110 << 20))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, d: int, scale: float, causal: bool, num_k: int, rep: int, rot: int):
    # q_ref/o_ref: [block_q, gq * d]; k_ref/v_ref: [block_k, gkv * d] (one
    # tile of rows a step); lse_ref: [gq, 1, block_q]; scratch by units of
    # lanes (``lanes_of``)
    (q_ref, k_ref, v_ref), rope_q, rope_k, rest = _split(refs, 3, rot)
    o_ref, lse_ref, q_scr, k_scr, v_scr, m_scr, l_scr, acc_scr, lse_scr, *units = rest
    q_units, o_units = units or (None, None)  # none where a head is a unit
    gq, block_q, lanes = q_scr.shape
    block_k = k_scr.shape[1]
    pack = lanes // d
    place = lambda h: _rem(_div(h, rep), pack)  # a query head's place: its KV head's
    qi, ki = pl.program_id(2), pl.program_id(3)
    on_q = _scale_on_operand(scale)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)
        _heads_in(q_ref, q_units, q_scr, d, place, rope_q, rot, scale if on_q else None)

    below, crossed = _tile_classes(qi * block_q, block_q, ki * block_k, block_k)

    @pl.when(jnp.logical_or(not causal, jnp.logical_or(below, crossed)))
    def _keys_in():
        _packed_in(k_ref, k_scr, d, rope_k, rot)
        _packed_in(v_ref, v_scr, d)

    def phases(h, rows, cols, mask):
        """One online-softmax update of head ``h``'s query rows ``rows``
        against key columns ``cols``. Matmul inputs stay in bf16 (f32 inputs
        run the MXU at ~1/8 rate on v5e); accumulation and softmax
        statistics are f32. The accumulator holds the head's output in its
        place of the unit (and the unit's other V heads under this head's
        weights beside it, which ``_heads_out`` drops)."""
        g = _div(h, rep * pack)
        s = _dot(q_scr[h, rows, :], k_scr[g, cols, :], _NT)  # [rows, cols]
        s = s if on_q else scale * s
        s = s if mask is None else jnp.where(mask, s, _NEG_INF)
        yield
        m_prev = m_scr[h, rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_scr[h, rows, :] = m_new
        corr = jnp.exp(m_prev - m_new)
        yield
        p = jnp.exp(s - m_new)
        yield
        l_scr[h, rows, :] = l_scr[h, rows, :] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[h, rows, :] = acc_scr[h, rows, :] * corr + _dot(
            p.astype(v_scr.dtype), v_scr[g, cols, :], _NN
        )

    rows = block_q if block_q % _FWD_ROWS else _FWD_ROWS
    _tiles(
        phases, gq, causal, qi * block_q, block_q, ki * block_k, block_k,
        [(slice(i, i + rows), slice(0, block_k), None) for i in range(0, block_q, rows)],
    )

    @pl.when(ki == num_k - 1)
    def _finish():
        def safe(h, rows=slice(None)):
            l = l_scr[h, rows, :]
            return jnp.where(l == 0, 1.0, l)

        _heads_out(acc_scr, o_units, o_ref, d, place, over=safe)

        # the log-sum-exp, columns of rows -> rows of lanes: 128 rows at a
        # time the heads' columns side by side, a head a lane, one transpose
        # of the lot, then head after head its row (a column turned alone,
        # head after head, was a quarter of the kernel)
        lane = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)
        for first in range(0, gq, 128):
            count = min(128, gq - first)

            def side_by_side(i, carry):
                rows = pl.ds(pl.multiple_of(i * 128, 128), 128)

                def column(j, ml):
                    m = jnp.where(lane == j, m_scr[first + j, rows, :], ml[0])
                    return m, jnp.where(lane == j, safe(first + j, rows), ml[1])

                ones = jnp.ones((128, 128), jnp.float32)
                m, l = jax.lax.fori_loop(0, count, column, (ones, ones))
                lse_scr[:, rows] = (m + jnp.log(l)).T
                return carry

            jax.lax.fori_loop(0, block_q // 128, side_by_side, 0)

            def row(j):
                lse_ref[first + j] = lse_scr[pl.ds(j, 1), :]

            _over_heads(count, row)


def _kv_rows(causal: bool, block_q: int, block_k: int):
    """The K / V tile a (query block, key block) step reads: causal steps
    above the diagonal are clamped to the last needed tile, and an unchanged
    block index re-uses the resident copy (no DMA)."""
    if not causal:
        return lambda qi, ki: ki
    return lambda qi, ki: jnp.minimum(ki, (qi * block_q + block_q - 1) // block_k)


def _rope_specs(rope, block_q, q_row, block_k, k_row):
    """Operands and BlockSpecs of the tables, the query rows' and the key
    rows' (the same two arrays under two index maps)."""
    if rope is None:
        return [], []
    lanes = rope.cos.shape[-1]
    q_spec = pl.BlockSpec((None, block_q, lanes), lambda bi, hi, i, j: (bi, q_row(i, j), 0))
    k_spec = pl.BlockSpec((None, block_k, lanes), lambda bi, hi, i, j: (bi, k_row(i, j), 0))
    return [rope.cos, rope.sin, rope.cos, rope.sin], [q_spec, q_spec, k_spec, k_spec]


def _fwd(q, k, v, rope=None, *, d: int, block_q: int, block_k: int, causal: bool,
         vma=None, interpret: bool = False):
    """q: [B, T, Hq * d]; k/v: [B, T, Hkv * d], the projections' rows ->
    (out [B, T, Hq * d], lse [B, Hq, 1, T]). Under ``rope`` q and k are
    rotated as their tiles enter VMEM.

    ``vma``: varying-manual-axes annotation for the outputs, required when
    called inside a shard_map manual region (the ring-attention chunks).
    When unset it is derived from q so the kernel types correctly in ANY
    manual region (e.g. flash_attention_sharded's batch/tp shard_map).
    """
    vma = jax.typeof(q).vma if vma is None else vma
    b, t, wq = q.shape
    hq, hkv = wq // d, k.shape[2] // d
    gq, gkv = heads_a_step(hq, hkv, d)
    pack, lanes = lanes_of(d)
    units = -(-gkv // pack)
    num_k = t // block_k
    kv_row = _kv_rows(causal, block_q, block_k)
    tables, table_specs = _rope_specs(rope, block_q, lambda qi, ki: qi, block_k, kv_row)

    q_spec = pl.BlockSpec((None, block_q, gq * d), lambda bi, hi, qi, ki: (bi, qi, hi))
    kv_spec = pl.BlockSpec(
        (None, block_k, gkv * d), lambda bi, hi, qi, ki: (bi, kv_row(qi, ki), hi)
    )
    lse_spec = pl.BlockSpec((None, gq, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, 0, qi))
    scratch = [
        ((gq, block_q, lanes), q.dtype), ((units, block_k, lanes), k.dtype),
        ((units, block_k, lanes), v.dtype), ((gq, block_q, 1), jnp.float32),
        ((gq, block_q, 1), jnp.float32), ((gq, block_q, lanes), jnp.float32),
        ((128, block_q), jnp.float32),
    ]
    if pack > 1:  # q, and the output, packed as their lanes lie (``_heads_in``, ``_heads_out``)
        q_units = -(-gq // pack)
        scratch += [((q_units, block_q, lanes), q.dtype), ((q_units, block_q, lanes), jnp.float32)]
    blocks = [((block_q, gq * d), q.dtype)] * 2 + [((block_k, gkv * d), k.dtype)] * 2
    blocks += [((gq, 1, block_q), jnp.float32)] + [((block_q, lanes), jnp.float32)] * len(tables)
    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, d=d, scale=d**-0.5, causal=causal, num_k=num_k, rep=hq // hkv,
            rot=rope.rot if rope else 0,
        ),
        name="odtp_flash_fwd",
        grid=(b, hkv // gkv, t // block_q, num_k),
        in_specs=[q_spec, kv_spec, kv_spec, *table_specs],
        out_specs=[q_spec, lse_spec],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
            jax.ShapeDtypeStruct((b, hq, 1, t), jnp.float32, vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM(*s) for s in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(*blocks, *blocks, *scratch),
        ),
        interpret=interpret,
    )(q, k, v, *tables)
    return out, lse


def _rows(x):
    """[B, T, H, D] -> the same values as rows [B, T, H * D]."""
    return x.reshape(*x.shape[:2], -1)


def flash_attention_lse(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
    block_q: int = 1024, block_k: int = 1024, interpret: bool = False,
):
    """The forward kernel alone, with the softmax's log-sum-exp beside the
    output, for a caller that merges this attention with another under one
    softmax (a serving prefill; no gradient is defined): q [B, T, H, D], k and
    v [B, T, Hkv, D] -> (out [B, T, H, D], lse [B, T, H] float32, of the scaled
    scores), or None where the kernel does not tile the shape."""
    t, d = q.shape[1], q.shape[-1]
    block_q, block_k = _pick_block(t, block_q), _pick_block(t, block_k)
    if block_q == 0 or block_k == 0 or d % 8 != 0:
        return None
    out, lse = _fwd(
        _rows(q), _rows(k), _rows(v), d=d,
        block_q=block_q, block_k=block_k, causal=causal, interpret=interpret,
    )
    return out.reshape(q.shape), lse[:, :, 0].transpose(0, 2, 1)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(*refs, d, scale, causal, num_k, rep, rot):
    # q/do/dq: [block_q, gq * d]; k/v: [block_k, gkv * d] per step;
    # lse/delta: [gq, 1, block_q]
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), rope_q, rope_k, rest = _split(refs, 6, rot)
    dq_ref, q_scr, k_scr, v_scr, do_scr, dq_scr, *units = rest
    q_units, do_units, dq_units = units or (None, None, None)
    gq, block_q, lanes = q_scr.shape
    block_k = k_scr.shape[1]
    pack = lanes // d
    place = lambda h: _rem(_div(h, rep), pack)
    qi, ki = pl.program_id(2), pl.program_id(3)
    on_q = _scale_on_operand(scale)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)
        _heads_in(q_ref, q_units, q_scr, d, place, rope_q, rot, scale if on_q else None)
        _heads_in(do_ref, do_units, do_scr, d, place)

    below, crossed = _tile_classes(qi * block_q, block_q, ki * block_k, block_k)

    @pl.when(jnp.logical_or(not causal, jnp.logical_or(below, crossed)))
    def _keys_in():
        _packed_in(k_ref, k_scr, d, rope_k, rot)
        _packed_in(v_ref, v_scr, d)

    def phases(h, rows, cols, mask):
        """dq of head ``h``'s query rows ``rows`` from key columns ``cols``
        (in its place of the unit; beside it what ``_heads_out`` drops)."""
        n = rows.stop - rows.start
        g = _div(h, rep * pack)
        k_blk = k_scr[g, cols, :]
        s = _dot(q_scr[h, rows, :], k_blk, _NT)
        s = s if on_q else scale * s
        dp = _dot(do_scr[h, rows, :], v_scr[g, cols, :], _NT)
        yield
        s = s if mask is None else jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[h, :, rows].reshape(n, 1))
        ds = (p * (dp - delta_ref[h, :, rows].reshape(n, 1))).astype(k_blk.dtype)
        yield
        dq_scr[h, rows, :] = dq_scr[h, rows, :] + scale * _dot(ds, k_blk, _NN)

    _tiles(
        phases, gq, causal, qi * block_q, block_q, ki * block_k, block_k,
        [(slice(0, block_q), slice(0, block_k), None)],
    )

    @pl.when(ki == num_k - 1)
    def _finish():
        _heads_out(dq_scr, dq_units, dq_ref, d, place, rope_q, rot)


def _dkv_kernel(*refs, d, scale, causal, num_q, rep, rot):
    # grid point: (batch, head group, k-block, q-block). q/do: [block_q,
    # gq * d] per step; k/v/dk/dv: [block_k, gkv * d]; lse/delta: [gq, 1, block_q]
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), rope_q, rope_k, rest = _split(refs, 6, rot)
    dk_ref, dv_ref, q_scr, k_scr, v_scr, do_scr, dk_scr, dv_scr, *units = rest
    q_units, do_units = units or (None, None)
    gq, block_q, lanes = q_scr.shape
    block_k = k_scr.shape[1]
    pack = lanes // d
    place = lambda h: _rem(_div(h, rep), pack)
    ki, qj = pl.program_id(2), pl.program_id(3)
    on_q = _scale_on_operand(scale)

    @pl.when(qj == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)
        _packed_in(k_ref, k_scr, d, rope_k, rot, scale if on_q else None)
        _packed_in(v_ref, v_scr, d)

    below, crossed = _tile_classes(qj * block_q, block_q, ki * block_k, block_k)

    @pl.when(jnp.logical_or(not causal, jnp.logical_or(below, crossed)))
    def _queries_in():
        _heads_in(q_ref, q_units, q_scr, d, place, rope_q, rot)
        _heads_in(do_ref, do_units, do_scr, d, place)

    def phases(h, cols, rows, mask):
        """dk and dv of head ``h``'s group's key rows ``cols`` from its query
        rows ``rows``. The scores are computed transposed, ``[key rows,
        query rows]``: every matmul then has the MXU's own forms (a @ b.T,
        a @ b), and the lane-dense ``lse`` and ``delta`` rows are read as
        they lie. q and dO are zero outside the head's place of its unit,
        so the head's dk and dv land in that place of the packed sums and
        nowhere else."""
        g = _div(h, rep * pack)
        q = q_scr[h, rows, :]
        do = do_scr[h, rows, :]
        s = _dot(k_scr[g, cols, :], q, _NT)  # [cols, rows]
        s = s if on_q else scale * s
        dp = _dot(v_scr[g, cols, :], do, _NT)
        yield
        s = s if mask is None else jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[h, :, rows])
        ds = (p * (dp - delta_ref[h, :, rows])).astype(q.dtype)
        yield
        dv_scr[g, cols, :] = dv_scr[g, cols, :] + _dot(p.astype(do.dtype), do, _NN)
        dk_scr[g, cols, :] = dk_scr[g, cols, :] + scale * _dot(ds, q, _NN)

    # causal: only q blocks at or after this k block contribute
    _tiles(
        phases, gq, causal, qj * block_q, block_q, ki * block_k, block_k,
        [(slice(0, block_k), slice(0, block_q), None)], keys_first=True,
    )

    @pl.when(qj == num_q - 1)
    def _finish():
        _packed_out(dk_scr, dk_ref, d, rope_k, rot)
        _packed_out(dv_scr, dv_ref, d)


def _delta(dout, out, d: int):
    """delta = rowsum(dO * O) a head, f32: rows [B, T, Hq * d] -> [B, Hq, 1,
    T]. The sum over a head's lanes is a matmul with the heads' indicator:
    the rows stay whole (a reshape to ``[.., Hq, d]`` would split them in
    HBM)."""
    b, t, w = out.shape
    heads = (jnp.arange(w)[:, None] // d == jnp.arange(w // d)[None, :]).astype(jnp.float32)
    prod = dout.astype(jnp.float32) * out.astype(jnp.float32)
    return jnp.einsum(
        "btw,wh->bht", prod, heads, precision=jax.lax.Precision.HIGHEST
    ).reshape(b, w // d, 1, t)


def _bwd_impl(
    q, k, v, rope, dout, lse, delta, *, d, block_q, block_k, causal,
    grad_dtype=None, vma=None,
):
    """Backward kernels with delta precomputed, over rows as ``_fwd`` takes
    them; under ``rope`` dq and dk are turned back before they are written.
    ``grad_dtype`` overrides the output dtype and ``vma`` annotates varying
    manual axes (both used by the ring-attention chunk path, which
    accumulates f32 inside shard_map); an unset vma is derived from q (see
    _fwd)."""
    vma = jax.typeof(q).vma if vma is None else vma
    b, t, wq = q.shape
    hq, hkv = wq // d, k.shape[2] // d
    gq, gkv = heads_a_step(hq, hkv, d)
    pack, lanes = lanes_of(d)
    units = -(-gkv // pack)
    num_k, num_q = t // block_k, t // block_q
    rot = rope.rot if rope else 0
    kv_row = _kv_rows(causal, block_q, block_k)
    tables, table_specs = _rope_specs(rope, block_q, lambda qi, ki: qi, block_k, kv_row)
    params = functools.partial(
        pltpu.CompilerParams,
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    )
    q_tile, kv_tile = ((block_q, gq * d), q.dtype), ((block_k, gkv * d), k.dtype)
    stat_tile, table_tiles = ((gq, 1, block_q), jnp.float32), [((block_q, lanes), jnp.float32)] * len(tables)
    scratch = [
        ((gq, block_q, lanes), q.dtype), ((units, block_k, lanes), k.dtype),
        ((units, block_k, lanes), v.dtype), ((gq, block_q, lanes), dout.dtype),
    ]

    q_spec = pl.BlockSpec((None, block_q, gq * d), lambda bi, hi, qi, ki: (bi, qi, hi))
    kv_spec = pl.BlockSpec(
        (None, block_k, gkv * d), lambda bi, hi, qi, ki: (bi, kv_row(qi, ki), hi)
    )
    stat_spec = pl.BlockSpec((None, gq, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, 0, qi))
    packed_q = [] if pack == 1 else [  # q and dO packed as their lanes lie (``_heads_in``)
        ((-(-gq // pack), block_q, lanes), q.dtype), ((-(-gq // pack), block_q, lanes), dout.dtype)
    ]
    dq_scratch = scratch + [((gq, block_q, lanes), jnp.float32)] + packed_q
    dq_scratch += [(packed_q[0][0], jnp.float32)] if packed_q else []
    dq_blocks = [q_tile] * 3 + [kv_tile] * 2 + [stat_tile] * 2 + table_tiles
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, d=d, scale=d**-0.5, causal=causal, num_k=num_k, rep=hq // hkv, rot=rot
        ),
        name="odtp_flash_dq",
        grid=(b, hkv // gkv, num_q, num_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec, *table_specs],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, grad_dtype or q.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM(*s) for s in dq_scratch],
        compiler_params=params(vmem_limit_bytes=_vmem_limit(*dq_blocks, *dq_blocks, *dq_scratch)),
    )(q, k, v, dout, lse, delta, *tables)

    # dk/dv: the sequential grid dim walks the q blocks, streaming one tile of
    # query rows (all the group's heads) per step while dk/dv accumulate in
    # scratch
    if causal:  # clamp skipped below-diagonal q tiles (DMA elision)
        q_row = lambda ki, qj: jnp.maximum(qj, (ki * block_k) // block_q)
    else:
        q_row = lambda ki, qj: qj
    tables, table_specs = _rope_specs(rope, block_q, q_row, block_k, lambda ki, qj: ki)
    q_spec = pl.BlockSpec(
        (None, block_q, gq * d), lambda bi, hi, ki, qj: (bi, q_row(ki, qj), hi)
    )
    kv_spec = pl.BlockSpec((None, block_k, gkv * d), lambda bi, hi, ki, qj: (bi, ki, hi))
    stat_spec = pl.BlockSpec(
        (None, gq, 1, block_q), lambda bi, hi, ki, qj: (bi, hi, 0, q_row(ki, qj))
    )
    dkv_scratch = scratch + [((units, block_k, lanes), jnp.float32)] * 2 + packed_q
    dkv_blocks = [q_tile] * 2 + [kv_tile] * 4 + [stat_tile] * 2 + table_tiles
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, d=d, scale=d**-0.5, causal=causal, num_q=num_q, rep=hq // hkv, rot=rot
        ),
        name="odtp_flash_dkv",
        grid=(b, hkv // gkv, num_k, num_q),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec, *table_specs],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, grad_dtype or k.dtype, vma=vma),
            jax.ShapeDtypeStruct(v.shape, grad_dtype or v.dtype, vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM(*s) for s in dkv_scratch],
        compiler_params=params(vmem_limit_bytes=_vmem_limit(*dkv_blocks, *dkv_blocks, *dkv_scratch)),
    )(q, k, v, dout, lse, delta, *tables)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, cos, sin, rot, d, block_q, block_k, causal):
    return _flash_fwd(q, k, v, cos, sin, rot, d, block_q, block_k, causal)[0]


def _flash_fwd(q, k, v, cos, sin, rot, d, block_q, block_k, causal):
    rope = Rope(cos, sin, rot) if rot else None
    out, lse = _fwd(q, k, v, rope, d=d, block_q=block_q, block_k=block_k, causal=causal)
    # tag the kernel outputs so the remat policies (llama._maybe_remat) can
    # save them -- without these names the backward pass reruns the whole
    # forward kernel just to rebuild its residuals. ``out`` is the kernel's
    # own output, rows [B, T, H * D] as the output projection reads them
    out = checkpoint_name(out, "attn_out")
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, cos, sin, out, lse)


def _bwd(rot, d, block_q, block_k, causal, res, dout):
    q, k, v, cos, sin, out, lse = res
    rope = Rope(cos, sin, rot) if rot else None
    dq, dk, dv = _bwd_impl(
        q, k, v, rope, dout, lse, _delta(dout, out, d),
        d=d, block_q=block_q, block_k=block_k, causal=causal,
    )
    none = lambda x: None if x is None else jnp.zeros_like(x)
    return dq, dk, dv, none(cos), none(sin)


_flash.defvjp(_flash_fwd, _bwd)


def _resolve_blocks(t: int, d: int, block_q: int = 1024, block_k: int = 1024) -> tuple:
    """The blocks ``flash_attention`` runs a sequence of ``t`` rows in, under
    OPENDILOCO_TPU_FLASH_BLOCKS if set; (0, 0) where the kernel does not tile
    the shape and XLA's attention runs instead."""
    env = os.environ.get("OPENDILOCO_TPU_FLASH_BLOCKS")  # tuning: "bq,bk"
    if env:
        try:
            eq, ek = (int(x) for x in env.split(","))
        except ValueError:
            raise ValueError(
                f"OPENDILOCO_TPU_FLASH_BLOCKS={env!r}: expected 'block_q,block_k'"
            ) from None
        if eq % 128 or ek % 128:
            raise ValueError(
                f"OPENDILOCO_TPU_FLASH_BLOCKS={env!r}: blocks must be "
                "multiples of 128 (TPU lane tiling)"
            )
        block_q, block_k = eq, ek
    block_q = _pick_block(t, block_q)
    block_k = _pick_block(t, block_k)
    if block_q == 0 or block_k == 0 or d % 8 != 0:
        return 0, 0
    return block_q, block_k


def plan_of(t: int, d: int, causal: bool = True) -> Optional[CausalPlan]:
    """``causal_plan`` of what ``flash_attention`` does with ``t`` rows of
    heads of ``d``; None where it hands the shape to XLA's attention."""
    block_q, block_k = _resolve_blocks(t, d)
    return causal_plan(t, block_q, block_k, causal) if block_q else None


def rotate_rows(x: jax.Array, rope: Rope, d: int) -> jax.Array:
    """Rows x [B, T, H * d] rotated a head at a time under ``rope``, in XLA:
    what the kernels do to a tile in VMEM (``_turn``), for a shape they do
    not tile and for the tests."""
    b, t, w = x.shape
    xh = x.reshape(b, t, w // d, d)
    x1, x2, rest = jnp.split(xh, (rope.rot // 2, rope.rot), axis=-1)
    swapped = jnp.concatenate((x2, x1, jnp.zeros_like(rest)), axis=-1).astype(jnp.float32)
    cos, sin = rope.cos[:, :, None, :d], rope.sin[:, :, None, :d]
    out = xh.astype(jnp.float32) * cos + swapped * sin
    return out.astype(x.dtype).reshape(b, t, w)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    head_dim: Optional[int] = None,
    rope: Optional[Rope] = None,
    causal: bool = True,
    block_q: int = 1024,
    block_k: int = 1024,
) -> jax.Array:
    """Attention via the Pallas kernels over the projections' own rows: q
    [B, T, Hq * D], k and v [B, T, Hkv * D] with ``head_dim`` D -> [B, T,
    Hq * D]; under ``rope`` (``rope_rows``) q and k come unrotated and the
    kernels rotate them in VMEM. Heads [B, T, H, D] (no ``head_dim``; already
    rotated) go through the same kernels as rows and come back as heads.
    Falls back to XLA for shapes the kernel doesn't tile (T not a multiple of
    128).

    Blocks default large, 1024 x 1024: a grid step has a fixed cost, and VMEM
    per step is only O(block * heads a step * d) + the [bq, bk] f32 score
    tile. What that rests on (PR 42, TPU v5e, seq 2,048, 15/5 heads of 64,
    batch 8, a head a grid step, before the sub-tile walk): at 512 x 512 the
    forward read 2.59 ms a call against 1.83, dq 1.93 against 1.82, dkv 2.41
    against 2.23 (PERF.md section 6, PR 42)."""
    if q.ndim == 4:
        out = flash_attention(
            _rows(q), _rows(k), _rows(v), head_dim=q.shape[3], causal=causal,
            block_q=block_q, block_k=block_k,
        )
        return out.reshape(q.shape)
    d = head_dim
    block_q, block_k = _resolve_blocks(q.shape[1], d, block_q, block_k)
    if block_q == 0:
        from opendiloco_tpu.ops.attention import xla_attention

        if rope is not None:
            q, k = rotate_rows(q, rope, d), rotate_rows(k, rope, d)
        heads = lambda x: x.reshape(*x.shape[:2], -1, d)
        return _rows(xla_attention(heads(q), heads(k), heads(v), causal=causal))
    cos, sin, rot = rope if rope is not None else (None, None, 0)
    return _flash(q, k, v, cos, sin, rot, d, block_q, block_k, causal)


def flash_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh,
    batch_axes: tuple = (),
    tp_axis=None,
    head_dim: Optional[int] = None,
    rope: Optional[Rope] = None,
    causal: bool = True,
) -> jax.Array:
    """SPMD entry for multi-device meshes, over rows or heads as
    ``flash_attention`` takes them.

    Mosaic kernels cannot be automatically partitioned — XLA raises at
    compile the moment a pallas operand has a sharded dimension (found by
    the deviceless multichip AOT compile, round 5; a single-chip mesh
    never hits it). Attention is independent per (batch row, head), so
    the fix is a shard_map manual over exactly the axes the activations
    are sharded on: the batch axes always, and tp on the heads (of rows: the
    last dimension, a shard holding whole heads) when it divides BOTH q and
    kv head counts (shards then keep whole GQA groups, so the kernel's local
    group arithmetic is unchanged). A non-dividing tp head dim is instead
    replicated into the region (tp is in the manual set with no spec entry =
    all-gather), which is the same gather the auto partitioner would emit.

    Do NOT call inside another manual region (the pp pipeline): nested
    shard_map has no jvp lowering — there the pipeline's in_specs gather
    the batch, operands arrive replicated, and the plain kernel compiles.
    """
    if mesh is None or getattr(mesh, "size", 1) <= 1:
        return flash_attention(q, k, v, head_dim=head_dim, rope=rope, causal=causal)
    P = jax.sharding.PartitionSpec
    d = head_dim or q.shape[3]
    hq, hkv = math.prod(q.shape[2:]) // d, math.prod(k.shape[2:]) // d
    head = None
    if tp_axis is not None and mesh.shape[tp_axis] > 1:
        n_tp = mesh.shape[tp_axis]
        if hq % n_tp == 0 and hkv % n_tp == 0:
            head = tp_axis
    batch = tuple(batch_axes) or None
    spec = P(batch, None, head, None) if q.ndim == 4 else P(batch, None, head)
    tables = () if rope is None else (rope.cos, rope.sin)
    rot = rope.rot if rope is not None else 0

    def local(a, b, c, *tables):
        return flash_attention(
            a, b, c, head_dim=head_dim, rope=Rope(*tables, rot) if tables else None,
            causal=causal,
        )

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(spec, spec, spec) + (P(batch, None, None),) * len(tables),
        out_specs=spec,
        # ALL mesh axes manual: a partially-manual pallas call still goes
        # through the auto partitioner for the remaining axes and XLA
        # refuses; axes outside the spec replicate into the region (the
        # same gather auto partitioning would emit)
        axis_names=set(mesh.axis_names),
    )
    return fn(q, k, v, *tables)
