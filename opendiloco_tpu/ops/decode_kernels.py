"""Pallas TPU serving kernels: ragged paged decode attention (over a ``(k,
v)`` ring, and over a latent ring in the absorbed form).

The XLA paths they stand in for (``decode_attention`` dense-masks the
whole ring page per slot) stay as the off-TPU path and the reference:
every kernel is token-bit-exact against them (PagedAttention-style
cache-aware decode, arXiv 2309.06180).

- :func:`paged_decode_attention` is the decode step's whole traffic with
  the ring KV cache. It is handed the cache as the engine holds it
  (``ring_cache``: ``[L, S, Nkv, Dh, T]``, rows minor-most) and cuts its
  ``(heads, Dh, block_t)`` tiles straight from it, the layer index and the
  per-slot ``lens`` vector riding the grid as scalar-prefetch operands:
  nothing of a layer's size is sliced, transposed or copied on the way in.
  Each slot's dead ring blocks are skipped (``pl.when``) AND their DMAs
  elided (the BlockSpec index map clamps to the last live block, an
  unchanged index reuses the resident tile — same trick as the flash
  kernel's causal skip). A grid step is one pair of MXU calls over all the
  KV heads it holds (:func:`decode_plan`): their tiles are one ``[heads *
  Dh, block_t]`` operand (a free view), the queries lie block-diagonally
  over it (head j's ``rep`` rows in columns ``j * Dh : (j + 1) * Dh``, built
  once a slot), so every product is the per-head one and every added term
  an exact zero; one online softmax in f32 over ``[heads * rep, block_t]``
  matches ``decode_attention`` row-for-row, and GQA never materializes a
  ``_repeat_kv``. The step's new K/V row is written by the same call, and
  touches no tile it is not in: the attention reads the tiles as the cache
  holds them, the row's own score (``q . k_new``, a row sum) is selected
  into its column of the scores and its value term added to the
  accumulator; what goes back, through an output aliased to the cache, is
  the one 128-row block of K and of V that holds ring row ``lens % T`` (an
  XLA scatter into rows-minor pages re-lays the whole cache, ISSUE 29), the
  row rolled into it as a column from a slots-as-lanes copy of the rows.
  Measured on a v5e (PERF.md, PR 35): 1.25 us a grid step of 5 heads of 64
  over a 256-row tile where the per-head form took 2.4, against 0.8 us for
  the tiles' DMAs alone. So a grid step is sized to a tile budget in all
  three directions it has (:func:`decode_plan`, PR 50): heads, then 512
  rows a tile where few heads leave it small, then, where a slot's whole
  ring is one tile, several consecutive slots, their bodies run one after
  another over one ``(slots, heads, Dh, block_t)`` tile of K and of V, each
  slot's 128-row block handed back by a copy of its own.
- :func:`mla_decode_attention` is the same plan for latent attention: one
  ring of latent rows and no value twin, every head of a slot against the
  same ``(R + rope, block_t)`` tile, the values taken from the tile's
  first ``R`` rows, so a live row is read once a layer and step. It checks
  against ``latent_decode_step_attention`` to rounding, not to the bit (the
  row reaches its tile through a one-hot product on the MXU).
- :func:`kda_step` is a Kimi-delta layer's decode step against the stacked
  float32 states as the engine holds them: decay, both reads, the rank-one
  update and the write in one visit of a live slot's state, where the XLA
  form (``models.kda.step_state``) passes over a layer's states three times.
Dispatch: by what the code sees. ``ServeEngine`` runs these kernels on a TPU
backend and the XLA paths elsewhere (:func:`resolve_decode_kernel`), so CPU
rigs keep the stock XLA code; no option or environment name chooses. A test
asks the engine for ``"pallas"`` by name and the kernels run in Pallas
interpret mode (slow, but semantically the kernel): that is how the parity
tests pin token-bit-exactness on a CPU rig. Shapes a kernel
cannot tile (head_dim not a multiple of 8, a ring whose rows are not a
multiple of the 128 lanes) fall
back to the XLA path per call, mirroring ``flash_attention``'s fallback
contract.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from opendiloco_tpu.models.ring_cache import index_write_rows, ring_rows
from opendiloco_tpu.ops.attention import (
    BlockSizes,
    _repeat_kv,
    decode_step_attention,
    eva_accumulate,
    eva_attention,
    eva_decode_step_attention,
    latent_decode_step_attention,
    merge_own_row,
    ring_rows_sum as xla_ring_rows_sum,
    sparse_decode_step_attention,
    xla_attention,
)
from opendiloco_tpu.ops.pallas_util import NEG_INF, pick_block

def resolve_decode_kernel(spec: str | None = None) -> str:
    """The decode path ("pallas" | "xla") for ``spec``: None, which every
    caller but a test passes, is the kernels on a TPU backend and the XLA
    paths elsewhere; a name is itself (a test's way to the kernels
    interpreted, beside their XLA reference)."""
    if spec is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if spec not in ("pallas", "xla"):
        raise ValueError(f"unknown decode kernel {spec!r}; expected 'pallas' or 'xla'")
    return spec


def _interpret(interpret: bool | None) -> bool:
    """A forced Pallas path off-TPU runs interpreted — slow, but it is the
    kernel's own dataflow, which is what the CPU parity tests pin."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def _asked_block(t: int, block_t: int | None, interpret: bool) -> int:
    """The ring-page tile the caller or ``ODTP_DECODE_BLOCK_T`` asks for, in
    rows, if the ring can be cut so; else 0. Rows are the tiles' lane
    dimension, so on the chip a tile is a multiple of 128 of them
    (interpreted, the tests cut small rings into small tiles)."""
    want = block_t or int(os.environ.get("ODTP_DECODE_BLOCK_T") or 0)
    return want if want > 0 and t % want == 0 and (interpret or want % 128 == 0) else 0


def _ring_block(
    t: int, block_t: int | None, interpret: bool, preferred: int = 256
) -> int:
    """Ring-page tile size, in rows: explicit arg > ``ODTP_DECODE_BLOCK_T``
    > the shared block heuristic. A ring that no tile of 128 rows divides
    has none: 0, and the caller keeps the XLA path."""
    return _asked_block(t, block_t, interpret) or pick_block(t, preferred)


# ---------------------------------------------------------------------------
# (a) ragged paged decode attention
# ---------------------------------------------------------------------------


# What a grid step moves is decided here, in the three directions it has: as
# many KV heads of a slot as fit this many bytes of one K tile (all 5 of
# SmolLM2-360M's over 256 rows, all 16 of OLMoE's over its 128-row tiles, all 8
# of granite's, 8 of EvaByte's 32 over 256 rows); then, where the heads leave
# the tile under it, 512 rows a tile (ZAYA1's 2 heads of 128, Keye's 4); then,
# where the whole ring of all the heads is one tile, several slots
# (:data:`_SLOTS_TILE_BYTES`). What a grid step costs on the v5e, measured
# (PERF.md, PR 35): about 0.35 us whatever it moves, its K and V tiles at
# 0.7-0.8 TB/s beside that, and what the step computes on top where that is
# not hidden: 1.25 us for 5 heads of 64 over a 256-row tile (320 KB read, 160
# KB written), 0.8 of it the DMAs. K and V tiles double-buffered and the
# written 128-row blocks hold six such tiles in VMEM.
_HEAD_TILE_BYTES = 512 * 1024

# The K tile of a grid step that holds several slots, and the most slots in
# one (their bodies are unrolled one after another). Measured on the v5e at the
# batch cell's shapes (PERF.md, PR 50; us a call of 256 slots x 5 heads of 64
# x 256 rows): 1 slot a step 291.7, 2 263.3, 4 237.8, 8 227.1, 16 222.7: the
# 0.35 us a step goes with the steps, and what is left is the tiles' DMAs at
# 0.55 TB/s whatever their size. 8 slots of that cell are 1.25 MB of K tile:
# with V, both double-buffered, and the written blocks, 6.3 MB of VMEM.
_SLOTS_TILE_BYTES = 1280 * 1024
_MAX_SLOTS_A_STEP = 8

_LANES = 128  # what goes back to the cache: the 128-row block that holds the row


def _heads_per_step(nkv: int, tile_bytes: int) -> int:
    """The most KV heads (a divisor of ``nkv``) whose tiles of
    ``tile_bytes`` each stay under :data:`_HEAD_TILE_BYTES`."""
    fit = max(1, _HEAD_TILE_BYTES // tile_bytes)
    return max(g for g in range(1, nkv + 1) if nkv % g == 0 and g <= fit)


def _slots_per_step(num_slots: int, tile_bytes: int) -> int:
    """The most consecutive slots, :data:`_MAX_SLOTS_A_STEP` at most, whose
    tiles of ``tile_bytes`` each stay under :data:`_SLOTS_TILE_BYTES`: a
    divisor of the slot count, and of the 128 lanes, so that a step's slots
    lie in one block of the slots-as-lanes copy of the new rows."""
    fit = min(max(1, _SLOTS_TILE_BYTES // tile_bytes), _MAX_SLOTS_A_STEP)
    return max(n for n in range(1, fit + 1) if num_slots % n == 0 and _LANES % n == 0)


class DecodePlan(NamedTuple):
    """What a grid step of ``odtp_paged_decode_attn`` does, from what the call
    can see (:func:`decode_plan`)."""

    heads: int  # KV heads of one slot a grid step, under one pair of MXU calls
    block_t: int  # ring rows a tile
    slots: int = 1  # consecutive slots a grid step, one after another

    @property
    def block_diagonal(self) -> bool:
        """Whether the queries of a grid step lie block-diagonally over
        several heads' tiles (with one head they are the head's own)."""
        return self.heads > 1

    def grid(self, num_slots: int, nkv: int, t: int) -> tuple[int, int, int]:
        """The kernel's grid over ``num_slots`` slots of ``nkv`` KV heads and
        ``t`` ring rows: (steps of slots, head groups, tiles)."""
        return (num_slots // self.slots, nkv // self.heads, t // self.block_t)


def decode_plan(
    nkv: int, d: int, t: int, itemsize: int,
    *, num_slots: int = 1, block_t: int | None = None, interpret: bool | None = None,
) -> DecodePlan | None:
    """The kernel's plan for ``nkv`` KV heads of ``d``, whatever the query heads
    over each, over ``num_slots`` rings of ``t`` rows of ``itemsize`` bytes an
    element: a pure function of those (and of the tile the caller or
    ``ODTP_DECODE_BLOCK_T`` asks for), never of a model's name. None where
    the kernel cannot tile the shape and the call keeps the XLA path.

    A grid step is sized to the tile budget in the three directions it has,
    in this order. Heads: as many of a slot's as fit, over the tile asked for
    or 256 rows. Rows: where no tile was asked for and the heads' tile of 512
    rows still fits, 512 rows (a ring that 512 does not divide keeps its
    tile). Slots: where that leaves the whole ring of all the heads as one
    tile, as many consecutive slots as fit (:func:`_slots_per_step`); the
    caller passes ``num_slots`` 1 under ``eva_ring`` and ``chosen``, whose
    rings are never one tile in any configuration, and keeps the one-slot
    step."""
    interp = _interpret(interpret)
    asked = _asked_block(t, block_t, interp)
    bt = asked or pick_block(t, 256)
    if d % 8 != 0 or not bt:
        return None
    # the heads' tiles go to the MXU as one [heads * d, bt] operand: the
    # (heads, d) axes merge freely where a head's rows are whole sublane
    # tiles of the cache's dtype (8 rows of 32 bits); else one head a step
    whole = d % (8 * 4 // itemsize) == 0
    heads = _heads_per_step(nkv, d * bt * itemsize) if whole else 1
    if not asked and heads * d * 512 * itemsize <= _HEAD_TILE_BYTES:
        bt = pick_block(t, 512)
    slots = 1
    if bt == t and heads == nkv:
        slots = _slots_per_step(num_slots, heads * d * bt * itemsize)
    return DecodePlan(heads, bt, slots)


def _head_of(index, size: int, heads: int):
    """``index // size`` for an iota under ``heads * size``, by comparisons
    (no vector division on the chip)."""
    return sum((index >= j * size).astype(jnp.int32) for j in range(1, heads))


def _lanes32(x):
    """A 16-bit array [n, lanes] as 32-bit words [n / 2, lanes] (two rows of
    a lane in a word), so that lane rolls and lane selects move whole words
    on a chip with no 16-bit vector unit; other widths as they are."""
    return pltpu.bitcast(x, jnp.uint32) if x.dtype.itemsize == 2 else x


class _SlotOf:
    """Slot ``j``'s part of a grid step's block or scratch that holds several
    slots' on its leading dimension, read and written as the one-slot step
    reads and writes its own. (An index on every access and not a ``.at[j]``
    view: Mosaic slices no view out of a block whose minor dimensions are
    padded to its tiling, and a slot's rows and columns mostly are.)"""

    def __init__(self, ref, j: int):
        self.ref, self.j = ref, j
        self.shape, self.dtype = ref.shape[1:], ref.dtype

    def _at(self, idx) -> tuple:
        return (self.j, *(idx if isinstance(idx, tuple) else (idx,)))

    def __getitem__(self, idx):
        return self.ref[self._at(idx)]

    def __setitem__(self, idx, value):
        self.ref[self._at(idx)] = value


def _decode_attn_kernel(lens_ref, layer_ref, *rest, slots=1, interpreted=False, **static):
    """A grid step: one slot's (:func:`_decode_slot_step`), or, under a plan
    of several slots a step (a ring of one tile, so the step is a slot's
    whole attention), theirs one after another, each over its own part of the
    step's blocks and scratch. The caches then come back as whole arrays in
    ``pl.ANY`` (a slot's written block lies where its own ``lens`` says, which
    one output block cannot name): each slot's patched 128-row blocks go from
    VMEM scratch to their place by a copy of their own, started as its body
    ends and waited for at the step's end. (Read slower on the chip, PERF.md,
    PR 50: the copies waited for a step later, before the scratch is written
    again.)"""
    if slots == 1:
        return _decode_slot_step(lens_ref, layer_ref, *rest, **static)
    *rest, back_k, back_v, sems = rest
    ko_ref, vo_ref = rest[8:10]
    # the step's and not a slot's: the slots-as-lanes rows, the two caches
    whole = (3, 4, 8, 9)
    first = pl.program_id(0) * slots

    def one(j):
        def hand_back(c, lane0, block):
            scr = (back_k, back_v)[c].at[j]
            scr[:] = block
            place = (ko_ref, vo_ref)[c].at[
                layer_ref[0], first + j, :, :, pl.ds(lane0, block.shape[-1])
            ]
            pltpu.make_async_copy(scr, place, sems.at[c, j]).start()

        _decode_slot_step(
            lens_ref, layer_ref,
            *(r if i in whole else _SlotOf(r, j) for i, r in enumerate(rest)),
            slot=first + j, hand_back=hand_back, **static,
        )

    # unrolled (as a loop the slots read a tenth slower), but on the chip
    # traced once: eight traces of the body cost a serving process a second of
    # set-up (PERF.md, PR 50). Interpreted, Python's own loop: XLA compiles a
    # loop's body apart from the code around it and rounds it otherwise, and
    # the parity tests hold the several-slot step to the one-slot step's bits
    if interpreted:
        for j in range(slots):
            one(j)
    else:
        jax.lax.fori_loop(0, slots, lambda j, carry: (one(j), carry)[1], 0, unroll=True)
    for j in range(slots):  # every slot wrote one block of each cache
        for c, (scr, out_ref) in enumerate(((back_k, ko_ref), (back_v, vo_ref))):
            place = out_ref.at[0, 0, :, :, pl.ds(0, scr.shape[-1])]
            pltpu.make_async_copy(scr.at[j], place, sems.at[c, j]).wait()


def _decode_slot_step(
    lens_ref, layer_ref, *rest,
    scale, block_t, t, num_t, rep, with_stats, eva_ring=None, with_selection=False,
    slot=None, hand_back=None, window=0, live_only=False,
):
    rest = list(rest)
    # under a selection (learned sparse attention): a third prefetched vector,
    # the ring row each slot's new row goes to (-1: the slot holds no sequence
    # and is written nothing), and behind the caches one more operand, the
    # rows of this tile that the slot's indexer chose. ``live_only`` brings the
    # vector alone: a slot at ``lens`` 0 is written nothing
    at_ref = rest.pop(0) if with_selection or live_only else None
    q_ref, kn_ref, vn_ref, knt_ref, vnt_ref, k_ref, v_ref = rest[:7]
    sel_ref = rest[7] if with_selection else None
    o_ref, ko_ref, vo_ref = rest[7 + with_selection : 10 + with_selection]
    # the stats block's index ignores the ring axis, so it stays resident
    # across ti and doubles as the counter: a vector add, since Mosaic
    # cannot store a scalar to VMEM
    rest = rest[10 + with_selection :]
    stats_ref = rest.pop(0) if with_stats else None
    # one of EVA's two rings (``paged_decode_attention``'s ``eva_ring``): the
    # softmax's running maximum and sum go out too, for the caller's merge of
    # the two calls under one softmax; ``pooled`` rows a window of the pooled
    # ring, 0 for the window's ring and for every other configuration
    with_softmax, pooled = eva_ring is not None, eva_ring or 0
    m_ref, l_ref = (rest.pop(0), rest.pop(0)) if with_softmax else (None, None)
    q_scr, snew_scr, m_scr, l_scr, acc_scr = rest
    heads, d, _ = k_ref.shape  # the KV heads of this grid step
    # all of them under one pair of MXU calls: their tiles as one
    # [heads * d, bt] operand, their rep query rows each block-diagonal
    rows, width = acc_scr.shape
    si, ti = pl.program_id(0), pl.program_id(2)
    if slot is not None:  # one of the step's several
        si = slot
    f32 = jnp.float32
    row_head = _head_of(
        jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), rep, heads
    )

    def own_block():  # [rows, width]: head j's rows x columns j*d : (j+1)*d
        # by the columns' bounds, not by ``row_head == col_head``: with two
        # heads a step each is one comparison widened to int32, the compiler
        # folds the equality onto the two masks, and Mosaic has no comparison
        # of masks ("failed to legalize arith.cmpi", first met at 2 KV heads
        # of 128: ZAYA1's)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        first = row_head * d
        return (col >= first) & (col < first + d)

    def side_by_side(ref):  # the heads' new rows [heads, 1, d] as [1, heads * d]
        return jnp.concatenate([ref[j] for j in range(heads)], axis=1)

    @pl.when(ti == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, f32)
        l_scr[:] = jnp.zeros(l_scr.shape, f32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, f32)
        if with_stats:
            stats_ref[:] = jnp.zeros(stats_ref.shape, jnp.int32)
        # head j's rep query rows in columns j*d : (j+1)*d, zeros elsewhere
        tiled = jnp.concatenate([q_ref[:]] * heads, axis=1)
        q_bd = jnp.where(own_block(), tiled, jnp.zeros_like(tiled))
        q_scr[:] = q_bd
        if not pooled:
            # the step's own scores, q . k_new: a row sum, once a slot
            snew_scr[:] = scale * jnp.sum(
                q_bd.astype(f32) * side_by_side(kn_ref).astype(f32),
                axis=1, keepdims=True,
            )

    lens_s = lens_ref[si]
    if window:
        # a ring that wraps under a window: the slot reads positions [first,
        # lens], which lie in the tiles first // block_t .. lens // block_t of
        # the positions (``num_t`` of them at most: the grid's), tile u of the
        # positions being tile u % (t // block_t) of the ring; ``base`` is the
        # position of this grid step's lane 0, past the last live tile for a
        # step that has none (its block is the last live one's: no DMA)
        first = jnp.maximum(lens_s - (window - 1), 0)
        first_tile = jax.lax.div(first, block_t)
        live_tiles = jax.lax.div(lens_s, block_t) - first_tile + 1
        base = (first_tile + ti) * block_t
    elif pooled:
        # EVA's pooled ring, ``pooled`` rows a window: row lens is where the
        # step's row goes and is not read; the rows read are those of the
        # windows before the one it lies in, whole tiles (block_t divides a
        # window's rows), none of them for a slot in its first window
        live_tiles = lens_s // pooled * (pooled // block_t)
    else:
        # valid cache entries are idx <= lens (whole ring once lens >= t), so
        # blocks past min(lens, t-1) hold no live rows for this slot
        last_live = jnp.minimum(lens_s, t - 1) // block_t
    # the step's own row goes to ring row lens % t, in a block that is
    # always live (it is the last live one until the ring wraps); new_at is
    # its lane in this tile, if it lies here
    row_at = jax.lax.rem(lens_s, t) if at_ref is None else at_ref[si]
    if window:  # the row's lane in the tile of the positions that holds ``lens``
        new_at = jnp.where(row_at < 0, -1, lens_s - base)
    else:
        new_at = row_at - ti * block_t

    @pl.when(ti < live_tiles if pooled or window else ti <= last_live)
    def _step():
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, block_t), 1)
        if window:  # every live tile holds a row of [first, lens]
            valid = (base + lane >= first) & (base + lane <= lens_s)
        else:
            valid = (ti * block_t + lane <= lens_s) | (lens_s >= t)
        at_row = lane == new_at  # nowhere, in a tile that does not hold it
        # the tiles as the cache holds them: the row's own score and value are
        # patched into s and acc, never into a tile. What lies at the row's
        # place is finite (zeros, or the row it evicts)
        k_blk = k_ref[:].reshape(width, block_t)
        v_blk = v_ref[:].reshape(width, block_t)
        s = scale * jax.lax.dot_general(
            q_scr[:], k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=f32,
        )  # [rows, block_t]
        if not pooled:  # (a pooled tile that is read is live whole and unpatched)
            s_new = snew_scr[:]
            s = jnp.where(at_row, s_new, s)
            if with_selection:  # of the live rows, those the indexer chose
                valid = valid & (sel_ref[:] > 0)
            s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if with_selection:  # a live tile may hold no chosen row: its maximum stays NEG_INF
            p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            (p if pooled else jnp.where(at_row, 0.0, p)).astype(v_blk.dtype), v_blk,
            (((1,), (1,)), ((), ())), preferred_element_type=f32,
        )  # head j's values in columns j*d : (j+1)*d of its rows

        if not pooled:
            @pl.when((new_at >= 0) & (new_at < block_t))
            def _own_value():
                # p[:, new_at] x v_new, rounded as the MXU's operand is
                p_new = jnp.exp(s_new - m_new).astype(v_blk.dtype)
                if with_selection:  # the step's own row may not be among the chosen
                    own = jnp.sum(
                        jnp.where(at_row[:1], sel_ref[:], 0), axis=1, keepdims=True
                    )
                    p_new = jnp.where(own > 0, p_new, jnp.zeros_like(p_new))
                acc_scr[:] += p_new.astype(f32) * side_by_side(vn_ref).astype(f32)

        if with_stats:
            stats_ref[:] += 1

    # what goes back: the one block of min(block_t, 128) rows that holds the
    # step's row. The rows of all slots arrive a second time transposed,
    # slots as lanes, so this slot's is a column already: rolled from lane
    # si % 128 to the row's lane and selected into the block (Mosaic has no
    # [1, d] -> [d, 1] reshape)
    back = min(block_t, _LANES)
    for b in range(block_t // back):
        at = new_at - b * back
        here_it_lies = (at >= 0) & (at < back)
        if (with_selection or live_only) and b == 0:
            # a slot that is written nothing hands its first block back as it was
            here_it_lies = here_it_lies | ((row_at < 0) & (ti == 0))

        @pl.when(here_it_lies)
        def _write():
            lanes = knt_ref.shape[-1]
            shift = jax.lax.rem(at - jax.lax.rem(si, lanes) + lanes, lanes)
            for c, (new_ref, tile_ref, out_ref) in enumerate(
                ((knt_ref, k_ref, ko_ref), (vnt_ref, v_ref, vo_ref))
            ):
                col = pltpu.roll(_lanes32(new_ref[:]), shift, 1)[:, :back]
                old = _lanes32(
                    tile_ref[:, :, b * back:(b + 1) * back].reshape(width, back)
                )
                here = jax.lax.broadcasted_iota(jnp.int32, old.shape, 1) == at
                patched = jnp.where(here, col, old)
                if patched.dtype != out_ref.dtype:
                    patched = pltpu.bitcast(patched, out_ref.dtype)
                if hand_back is None:
                    out_ref[:] = patched.reshape(heads, d, back)
                else:  # (a ring of one tile: the block's first lane is b * back)
                    hand_back(c, b * back, patched.reshape(heads, d, back))

    @pl.when(ti == num_t - 1)
    def _finish():
        # head j keeps its own d columns of its rep rows: the others zeroed,
        # the heads' columns are added onto each other (zeros, so exact), by
        # whole vregs first where a head is narrower than one
        own = jnp.where(own_block(), acc_scr[:], 0.0)
        chunk = _LANES if _LANES % d == 0 else d
        if width % chunk:
            own = jnp.concatenate(
                [own, jnp.zeros((rows, -width % chunk), f32)], axis=1
            )
        own = sum(
            own[:, c * chunk:(c + 1) * chunk] for c in range(own.shape[1] // chunk)
        )
        while chunk > d:
            chunk //= 2
            own = own[:, :chunk] + own[:, chunk:]
        l = l_scr[:]
        o_ref[:] = (own / jnp.where(l == 0, 1.0, l)).astype(o_ref.dtype)
        if with_softmax:
            m_ref[:] = m_scr[:]
            l_ref[:] = l


def paged_decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    lens: jax.Array,
    layer,
    *,
    block_t: int | None = None,
    interpret: bool | None = None,
    return_stats: bool = False,
    eva_ring: int | None = None,
    chosen: jax.Array | None = None,
    window: int = 0,
    live_only: bool = False,
):
    """One layer's share of a decode step against the ring cache: write
    each slot's new row (k, v [S, Nkv, D]) at ring row ``lens % T`` of
    ``layer``'s pages, then attend q [S, H, D] over them under the per-slot
    ``lens``. Returns (out [S, H, D], cache_k, cache_v): what
    :func:`~opendiloco_tpu.ops.attention.decode_step_attention` gives,
    with the whole cache ``[L, S, Nkv, D, T]`` read and written where it lies (the outputs alias the inputs;
    callers donate them).

    ``return_stats`` additionally returns the measured per-(slot, kv-head)
    count of ring blocks the kernel actually processed — the dead-block
    skip evidence banked by scripts/decode_kernel_bench.py.

    ``eva_ring`` (None for every configuration but EVA's) says which of EVA's
    two rings this call reads, and appends the softmax's running maximum and
    sum over the rows read ([S, H] float32 each; the maximum is ``NEG_INF`` and
    the sum 0 where none was), under which the caller merges the two calls'
    outputs into one softmax. 0 is the window's ring, read as any ring is. n >
    0 is the pooled ring of n rows a window: the row is written at ring row
    ``lens`` and *not* read, and the rows read are those of the windows before
    the one it lies in, [0, lens // n * n) (kernel name
    ``odtp_eva_pooled_attn``). There is no XLA stand-in for either: no plan
    raises. :func:`eva_decode_attention` is the caller of both.

    ``chosen`` (None for every configuration but one with learned sparse
    attention) [S, T] bool is a selection: of each slot's live rows the kernel
    reads the tiles as ever and lets only the chosen rows into the softmax
    (the step's own row too only if it is among them), a ``(1, block_t)`` tile
    of the selection beside each ``(heads, Dh, block_t)`` tile of K and V. A
    slot at ``lens`` 0 is then written nothing (its first block goes back as
    it was): it may be one whose prompt is arriving in chunks. The XLA
    stand-in is ``sparse_decode_step_attention``.

    ``window`` (0 for every configuration but one with sliding grouped-query
    layers): the ring wraps (position p at row p % T, whatever ``lens``) and a
    slot reads the rows of its last ``window`` positions, [max(lens - window + 1,
    0), lens]. The grid's last dimension is then the tiles a window can cross
    (ceil((window - 1) / block_t) + 1, whatever the ring's length), each grid
    step's block the ring tile that holds its part of those positions; a slot
    whose window crosses fewer has its other steps skipped and their DMAs
    elided (the index map holds them to the last live tile), as ``lens`` elides
    dead tiles without a window. ``live_only``: a slot at ``lens`` 0 is written
    nothing, as under ``chosen``. The XLA stand-in of both is
    ``decode_step_attention`` under the same arguments."""
    # Mosaic requires the last two dims of every block to be (8, 128)-
    # aligned OR equal to the array's own dims. The cache's two minor dims
    # are (D, T), so a (d, bt) tile is legal for bt a multiple of 128. The
    # query rows of a grid step and the single new row are few and never
    # 8-aligned, so they must BE array dims: q as [S, Kh / hb, hb * rep, D]
    # (the grid step's heads' rows as one tile), the new rows as [S, Kh, 1, D]
    # ([1, d] tiles, the KV heads of a grid step leading them)
    s_, nkv, d = k.shape
    t = ring_rows(cache_k)
    h = q.shape[1]
    interp = _interpret(interpret)
    if window and (eva_ring is not None or chosen is not None or return_stats):
        raise ValueError("a window goes with neither EVA's rings, a selection nor the stats")
    one_slot_a_step = eva_ring is not None or chosen is not None or window or live_only
    plan = h % nkv == 0 and decode_plan(
        nkv, d, t, cache_k.dtype.itemsize,
        num_slots=1 if one_slot_a_step else s_, block_t=block_t, interpret=interp,
    )
    if eva_ring is not None and (not plan or eva_ring % plan.block_t):
        raise ValueError(
            "the softmax's state and the pooled ring's reading are the kernel's "
            f"alone, and it has no plan for {nkv} KV heads of {d} over {t} rows "
            f"(windows of {eva_ring} pooled rows)"
        )
    if not plan and chosen is not None:
        return sparse_decode_step_attention(q, k, v, chosen, cache_k, cache_v, lens, layer)
    if not plan and (window or live_only):
        return decode_step_attention(
            q, k, v, cache_k, cache_v, lens, layer, window=window, live_only=live_only
        )
    if not plan:
        res = decode_step_attention(q, k, v, cache_k, cache_v, lens, layer)
        return (*res, None) if return_stats else res
    hb, bt, n = plan
    rep = h // nkv
    num_t = t // bt
    if window:  # the tiles of positions a window can cross, not the ring's
        ring_tiles, num_t = num_t, -(-(window - 1) // bt) + 1
    back = min(bt, _LANES)
    # the slots of a grid step: theirs is a leading dimension of its blocks
    # and scratch where they are several, and none where it is the one
    ns = None if n == 1 else n
    rows, width = hb * rep, hb * d
    q4 = q.reshape(s_, nkv // hb, rows, d)
    kn = k.reshape(s_, nkv, 1, d).astype(cache_k.dtype)
    vn = v.reshape(s_, nkv, 1, d).astype(cache_v.dtype)
    # and slots as lanes, [Kh * D, S]: a slot's row as the column it becomes
    knt = jnp.pad(kn.reshape(s_, nkv * d).T, ((0, 0), (0, -s_ % _LANES)))
    vnt = jnp.pad(vn.reshape(s_, nkv * d).T, ((0, 0), (0, -s_ % _LANES)))

    # the prefetched vectors: lens, layer and, under a selection, the row written
    def kv_map(si, gi, ti, lens_ref, layer_ref, *_):
        if window:  # the ring tile of the ti-th tile of positions the window crosses
            lens_s = lens_ref[si]
            tile = jax.lax.div(jnp.maximum(lens_s - (window - 1), 0), bt) + ti
            tile = jnp.minimum(tile, jax.lax.div(lens_s, bt))  # held to the last live one
            return (layer_ref[0], si, gi, 0, jax.lax.rem(tile, ring_tiles))
        if n > 1:  # the whole rings of the step's slots
            return (layer_ref[0], si, gi, 0, 0)
        # clamp dead blocks to the last live one: unchanged index = no DMA
        last = jnp.minimum(lens_ref[si], t - 1) // bt
        return (layer_ref[0], si, gi, 0, jnp.minimum(ti, last))

    def written_map(si, gi, ti, lens_ref, layer_ref, *at_ref):
        # the one block of (slot, head group) that goes back: the row's
        layer = layer_ref[0]
        at = jnp.maximum(at_ref[0][si], 0) if at_ref else jax.lax.rem(lens_ref[si], t)
        return (layer, si, gi, 0, at // back)

    def q_map(si, gi, ti, *_):
        return (si, gi, 0, 0)

    def chosen_map(si, gi, ti, lens_ref, *_):
        return (si, 0, jnp.minimum(ti, jnp.minimum(lens_ref[si], t - 1) // bt))

    queries = pl.BlockSpec((ns, None, rows, d), q_map)
    row = pl.BlockSpec((ns, hb, 1, d), q_map)
    column = pl.BlockSpec(
        (width, _LANES), lambda si, gi, ti, *_: (gi, (si if n == 1 else si * n) // _LANES)
    )
    # the block that goes back: the pipeline's own output where a step is one
    # slot; the kernel's own copies into the whole cache where it is several
    written = (
        pl.BlockSpec((None, None, hb, d, back), written_map) if n == 1
        else pl.BlockSpec(memory_space=pl.ANY)
    )
    out_specs = [queries, written, written]
    out_shape = [
        jax.ShapeDtypeStruct(q4.shape, q.dtype, vma=jax.typeof(q).vma),
        jax.ShapeDtypeStruct(cache_k.shape, cache_k.dtype),
        jax.ShapeDtypeStruct(cache_v.shape, cache_v.dtype),
    ]
    slot = () if n == 1 else (n,)
    scratch = [
        pltpu.VMEM((*slot, rows, width), q.dtype),  # the block-diagonal queries
        pltpu.VMEM((*slot, rows, 1), jnp.float32),  # the step's own scores
        pltpu.VMEM((*slot, rows, 1), jnp.float32),
        pltpu.VMEM((*slot, rows, 1), jnp.float32),
        pltpu.VMEM((*slot, rows, width), jnp.float32),
    ]
    if n > 1:  # each slot's written blocks on their way back, and their copies' semaphores
        scratch += [
            pltpu.VMEM((n, hb, d, back), cache_k.dtype),
            pltpu.VMEM((n, hb, d, back), cache_v.dtype),
            pltpu.SemaphoreType.DMA((2, n)),
        ]
    if return_stats:
        out_specs.append(pl.BlockSpec((ns, hb, 1, 1), q_map))
        out_shape.append(jax.ShapeDtypeStruct((s_, nkv, 1, 1), jnp.int32))
    if eva_ring is not None:  # a grid step's rows, as the queries are laid
        out_specs += [pl.BlockSpec((None, None, rows, 1), q_map)] * 2
        out_shape += [jax.ShapeDtypeStruct((s_, nkv // hb, rows, 1), jnp.float32)] * 2

    prefetched = [lens.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1)]
    selection = []
    if chosen is not None or live_only:
        prefetched.append(jnp.where(lens > 0, jax.lax.rem(lens, t), -1).astype(jnp.int32))
    if chosen is not None:
        selection = [chosen.astype(jnp.int32).reshape(s_, 1, t)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetched),
        grid=(*plan.grid(s_, nkv, t)[:2], num_t),
        in_specs=[
            queries,
            row,
            row,
            column,
            column,
            pl.BlockSpec((None, ns, hb, d, bt), kv_map),
            pl.BlockSpec((None, ns, hb, d, bt), kv_map),
            *([pl.BlockSpec((None, 1, bt), chosen_map)] if selection else []),
        ],
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    res = pl.pallas_call(
        functools.partial(
            _decode_attn_kernel,
            scale=d**-0.5, block_t=bt, t=t, num_t=num_t, rep=rep,
            with_stats=return_stats,
            **({} if eva_ring is None else {"eva_ring": int(eva_ring)}),
            **({"with_selection": True} if selection else {}),
            **({} if n == 1 else {"slots": n, "interpreted": interp}),
            **({"window": int(window)} if window else {}),
            **({"live_only": True} if live_only and not selection else {}),
        ),
        name="odtp_eva_pooled_attn" if eva_ring else "odtp_paged_decode_attn",
        grid_spec=grid_spec,
        out_shape=out_shape,
        # operands count the scalar-prefetch vectors (two, three under a
        # selection): the caches follow five inputs behind them, and come back
        # as outputs 1 and 2
        input_output_aliases={len(prefetched) + 5: 1, len(prefetched) + 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interp,
    )(*prefetched, q4, kn, vn, knt, vnt, cache_k, cache_v, *selection)
    out = (res[0].reshape(s_, h, d), res[1], res[2])
    if return_stats:
        out = (*out, res[3].reshape(s_, nkv))
    if eva_ring is not None:
        out = (*out, res[-2].reshape(s_, h), res[-1].reshape(s_, h))
    return out


def _index_write_kernel(at_ref, key_ref, ring_ref, out_ref):
    at = at_ref[pl.program_id(1)]
    lane = jax.lax.broadcasted_iota(jnp.int32, ring_ref.shape, 1)
    here = (lane == jax.lax.rem(jnp.maximum(at, 0), ring_ref.shape[1])) & (at >= 0)
    f32 = jnp.float32  # the select in 32 bits: exact both ways
    out_ref[:] = jnp.where(here, key_ref[:].astype(f32), ring_ref[:].astype(f32)).astype(out_ref.dtype)


def index_ring_write(
    cache_i: jax.Array, keys: jax.Array, lens: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    """A decode step's index keys of all layers, keys [L, S, Di], into the
    index ring ``cache_i`` [L, S, Di, T] at ring row ``lens % T`` of each slot
    (nothing for a slot at ``lens`` 0): what ``ring_cache.index_write_rows``
    gives, with the ring written where it lies. A grid step a layer and slot
    takes the one 128-row block that holds the row, the key as a column
    selected into it, and hands it back through an output aliased to the ring
    (an XLA scatter or slice update into rows-minor pages re-lays the whole
    ring: ``ring_cache``). A ring that 128 rows do not divide keeps the XLA
    path."""
    L, S, di, t = cache_i.shape
    if t % _LANES or di % 8:
        return index_write_rows(cache_i, keys, lens)
    at = jnp.where(lens > 0, jax.lax.rem(lens, t), -1).astype(jnp.int32)
    block = lambda li, si, at_ref: (li, si, 0, jnp.maximum(at_ref[si], 0) // _LANES)
    return pl.pallas_call(
        _index_write_kernel,
        name="odtp_index_ring_write",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(L, S),
            in_specs=[
                pl.BlockSpec((None, None, di, 1), lambda li, si, at_ref: (li, si, 0, 0)),
                pl.BlockSpec((None, None, di, _LANES), block),
            ],
            out_specs=pl.BlockSpec((None, None, di, _LANES), block),
        ),
        out_shape=jax.ShapeDtypeStruct(cache_i.shape, cache_i.dtype),
        input_output_aliases={2: 0},
        interpret=_interpret(interpret),
    )(at, keys.astype(cache_i.dtype)[..., None], cache_i)


# A whole prompt's causal attention in XLA has float32 scores of query heads x
# rows^2 x 4 bytes. While they are small XLA keeps them in two fusions a layer
# and is the faster form; past about 100 MB it writes them out and passes over
# them several times, and the flash forward kernel, which holds a tile of them
# in VMEM, wins. Measured on the v5e, us a call, XLA beside the kernel (PERF.md
# section 6, PR 55): 8 heads of 128 x 512 rows (8 MB) 10.5 / 24.1; 32/8 x 512
# (32 MB) 37.9 / 91.9; 20 heads of 256 x 768 (45 MB) 79.2 / 109.4; 32 heads of
# 64 x 768 (72 MB) 65.9 / 138.8; 20 of 256 x 1,280 (125 MB) 392.0 / 264.4; 32/8
# x 1,024 (128 MB) 473.1 / 168.2; 16 of 128 x 3,072 (576 MB) 4,523 / 494. The
# line lies between 72 and 125 MB; under it, and under the floor of rows, the
# XLA form stays
_PREFILL_SCORE_BYTES = 96 * 1024 * 1024
_PREFILL_FLOOR_ROWS = 512


def prefill_form(
    rows: int, hq: int, hkv: int, dk: int, dv: int, decode_kernel: str | None = None
) -> str:
    """Which form a whole prompt's causal attention takes in a serving
    prefill, from what the call can see: "flash" (the training forward kernel,
    no scores in memory) where ``decode_kernel`` resolves to the kernels (the
    chip, or a test that asks for them interpreted), a multiple of 128 divides
    the bucket's ``rows``, keys' and values' heads are of one size the kernel
    takes, and the scores XLA would write are worth it; else "xla". The engine
    reports it (``ServeEngine.prefill_forms``)."""
    if resolve_decode_kernel(decode_kernel) != "pallas":
        return "xla"
    if rows < _PREFILL_FLOOR_ROWS or rows % 128 or dk != dv or dk % 8 or hq % hkv:
        return "xla"
    return "flash" if hq * rows * rows * 4 >= _PREFILL_SCORE_BYTES else "xla"


def prefill_block(rows: int) -> int:
    """The block, of queries and of keys alike, a serving prefill of ``rows``
    runs the flash forward kernel in: 512 where it divides the bucket, else the
    largest multiple of 128 up to 1,024 that does (640 / 896 for 1,280 / 1,792,
    where ``pick_block`` would say 256: half the speed). Larger blocks are
    faster (16 heads of 128 over 3,072 rows on the v5e: 494 us a call at 1,024,
    804 at 512, 1,577 at 256) and dearer to trace (433 / 267 / 197 equations),
    and a prefill program is traced once a bucket a process at about 2 ms an
    equation: at 1,024 the hybrid cell's ``setup_s`` read up to 3.8 s over its
    parent's 33, against a bound of 10% (PERF.md section 6, PR 55)."""
    if rows % 512 == 0:
        return 512
    return max((b for b in range(128, 1025, 128) if rows % b == 0), default=0)


def causal_prefill_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, decode_kernel: str | None = "xla"
) -> jax.Array:
    """A whole prompt's causal attention from position 0 (``xla_attention``'s
    signature and result, ``causal=True``): q [1, P, Hq, D], k and v [1, P,
    Hkv, D], rotated and normed, in the form :func:`prefill_form` names. No
    gradient."""
    from opendiloco_tpu.ops.flash_attention import flash_attention_lse

    rows = q.shape[1]
    if prefill_form(rows, q.shape[2], k.shape[2], k.shape[3], v.shape[3], decode_kernel) == "xla":
        return xla_attention(q, k, v, causal=True)
    block = prefill_block(rows)
    return flash_attention_lse(
        q, k, v, causal=True, block_q=block, block_k=block, interpret=_interpret(None)
    )[0]


def eva_prefill_form(window: int, d: int, interpret: bool | None = None) -> str:
    """Which form a serving prefill of EVA attention takes, from what can be
    seen: "flash" on the chip (and where a test asks for the kernel
    interpreted) if the flash kernel tiles a window of heads of ``d``, else
    "xla". The engine reports it (``ServeEngine.eva_forms``)."""
    wanted = bool(interpret) or not _interpret(interpret)
    return "flash" if wanted and pick_block(window, 1024) and d % 8 == 0 else "xla"


def eva_prefill_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, kbar: jax.Array, vbar: jax.Array,
    *, window: int, chunk: int, interpret: bool | None = None,
) -> jax.Array:
    """EVA over a whole prompt (``ops.attention.eva_attention``'s signature
    and result), in the form :func:`eva_prefill_form` names. "flash" holds no
    window's scores in memory: each window's causal attention over its own
    rows is the flash kernel's (the windows as a batch), which hands back its
    softmax's log-sum-exp; the pooled rows of the windows before, a few
    hundred columns, are scored in XLA; the two are merged under one softmax
    (scope ``odtp_eva``). "xla" is ``eva_attention`` itself. No gradient."""
    from opendiloco_tpu.ops.flash_attention import flash_attention_lse

    b, t, h, d = q.shape
    if eva_prefill_form(window, d, interpret) == "xla":
        return eva_attention(q, k, v, kbar, vbar, window=window, chunk=chunk)
    cpw = window // chunk
    pad = -t % window
    if pad:
        rows = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, rows), jnp.pad(k, rows), jnp.pad(v, rows)
    nw = (t + pad) // window
    as_batch = lambda x: x.reshape(b * nw, window, *x.shape[2:])
    own = flash_attention_lse(
        as_batch(q), as_batch(k), as_batch(v), causal=True, interpret=_interpret(interpret)
    )
    o_w = own[0].reshape(b, nw, window, h, d)
    seen = (nw - 1) * cpw  # pooled rows that any query of the prompt reads
    if not seen:
        return o_w.reshape(b, nw * window, h, d)[:, :t]
    with jax.named_scope("odtp_eva"):
        f32 = jnp.float32
        lse_w = own[1].reshape(b, nw, window, h)
        qw = q.reshape(b, nw, window, h, d)
        kb, vb = _repeat_kv(kbar[:, :seen], h), _repeat_kv(vbar[:, :seen], h)
        s = jnp.einsum("bwqhd,bjhd->bwqhj", qw, kb, preferred_element_type=f32) * d**-0.5
        w_of = jax.lax.broadcasted_iota(jnp.int32, (nw, 1, 1, seen), 0)
        j_of = jax.lax.broadcasted_iota(jnp.int32, (nw, 1, 1, seen), 3)
        s = jnp.where(j_of < w_of * cpw, s, NEG_INF)
        m = jnp.maximum(lse_w, jnp.max(s, axis=-1))  # the window's own rows keep it finite
        p = jnp.exp(s - m[..., None])  # exactly 0 where masked
        w_w = jnp.exp(lse_w - m)
        o_p = jnp.einsum("bwqhj,bjhd->bwqhd", p.astype(q.dtype), vb, preferred_element_type=f32)
        out = (o_w.astype(f32) * w_w[..., None] + o_p) / (w_w + jnp.sum(p, axis=-1))[..., None]
    return out.astype(q.dtype).reshape(b, nw * window, h, d)[:, :t]


def eva_plans(
    nkv: int, d: int, window: int, chunk: int, pooled_rows: int, itemsize: int,
    *, interpret: bool | None = None,
) -> tuple[DecodePlan, DecodePlan] | None:
    """The decode kernel's plans for EVA's two rings (the window's, the pooled
    one of ``pooled_rows`` rows), or None where either has none. The pooled
    ring's tile divides a window's pooled rows, since the rows read of it are
    whole windows: the widest the chip tiles, or, interpreted, whatever
    ``ODTP_DECODE_BLOCK_T`` asks for."""
    cpw = window // chunk
    want = next((b for b in (256, 128) if cpw % b == 0), None)
    local = decode_plan(nkv, d, window, itemsize, interpret=interpret)
    pooled = local and decode_plan(nkv, d, pooled_rows, itemsize, block_t=want, interpret=interpret)
    return (local, pooled) if pooled and cpw % pooled.block_t == 0 else None


def eva_decode_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array,
    cache_k: jax.Array, cache_v: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
    stats: jax.Array, lens: jax.Array, layer, *, window: int, chunk: int,
    interpret: bool | None = None,
):
    """One layer's share of a decode step of EVA attention over a slot's two
    rings, what ``ops.attention.eva_decode_step_attention`` gives (its
    signature), with both rings read and written where they lie: the decode
    kernel once over the window's ring under ``lens % window`` (the ring
    restarts: the kernel's own mask, rows [0, lens % window], is the
    window's), once over the pooled ring (``eva_ring`` > 0: the step's
    pooled row written at ``lens // chunk`` and not read, the rows of the
    windows before read), each handing back its softmax's maximum and sum,
    and the two outputs merged under them: one softmax over both. The
    pooling's step and the merge are XLA's, over [S, H, D] (scope
    ``odtp_eva``). Two calls and not one kernel fed both: the rings differ in
    what a step reads of them (its own row or not, a prefix by rows or by
    windows) and in their tile (a window's ring takes the widest, a pooled
    ring a window's 128 rows), and the merge is a few KB. Where either ring
    has no plan it raises (:func:`eva_plans`; the engine asks at construction):
    a step never takes the XLA form unasked."""
    cpw = window // chunk
    s_, nkv, d = k.shape
    h = q.shape[1]
    interp = _interpret(interpret)
    plans = h % nkv == 0 and ring_rows(cache_k) == window and eva_plans(
        nkv, d, window, chunk, ring_rows(pool_k), cache_k.dtype.itemsize, interpret=interp
    )
    if not plans:
        raise ValueError(
            f"the decode kernel has no plan for EVA's rings here ({nkv} KV heads of "
            f"{d} under {h} query heads, a window of {ring_rows(cache_k)} rows for "
            f"{window}, {ring_rows(pool_k)} pooled rows, {cpw} a window): "
            "decode_kernel 'xla' runs ops.attention.eva_decode_step_attention instead"
        )
    lens = lens.astype(jnp.int32)
    with jax.named_scope("odtp_eva"):
        kbar, vbar, new = eva_accumulate(stats[layer], k, v, phi, mu, lens, chunk)
        stats = jax.lax.dynamic_update_index_in_dim(stats, new, layer, 0)
    o_w, cache_k, cache_v, m_w, l_w = paged_decode_attention(
        q, k, v, cache_k, cache_v, jnp.mod(lens, window), layer,
        interpret=interp, eva_ring=0,
    )
    o_p, pool_k, pool_v, m_p, l_p = paged_decode_attention(
        q, kbar, vbar, pool_k, pool_v, lens // chunk, layer,
        block_t=plans[1].block_t, interpret=interp, eva_ring=cpw,
    )
    with jax.named_scope("odtp_eva"):
        m = jnp.maximum(m_w, m_p)
        w_w, w_p = l_w * jnp.exp(m_w - m), l_p * jnp.exp(m_p - m)  # w_p 0: no pooled row yet
        out = (
            o_w.astype(jnp.float32) * w_w[..., None] + o_p.astype(jnp.float32) * w_p[..., None]
        ) / (w_w + w_p)[..., None]
    return out.astype(q.dtype), cache_k, cache_v, pool_k, pool_v, stats


# ---------------------------------------------------------------------------
# (a') decode attention over a latent ring, absorbed form
# ---------------------------------------------------------------------------


def _mla_decode_kernel(
    lens_ref, layer_ref, q_ref, row_ref, new_ref, c_ref, *rest,
    scale, block_t, t, num_t, value_dim, window=0, with_selection=False, live_only=False,
):
    # under a selection (an indexer over latent rows): one more operand behind
    # the cache, the rows of this tile that the slot's indexer chose
    sel_ref = rest[0] if with_selection else None
    o_ref, co_ref, snew_scr, m_scr, l_scr, acc_scr = rest[with_selection:]
    heads, d = q_ref.shape  # every head of the slot: they share its rows
    si, ti = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32

    @pl.when(ti == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, f32)
        l_scr[:] = jnp.zeros(l_scr.shape, f32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, f32)
        # the step's own scores, q . row: a row sum, once a slot
        snew_scr[:] = scale * jnp.sum(
            q_ref[:].astype(f32) * row_ref[:].astype(f32), axis=1, keepdims=True
        )

    lens_s = lens_ref[si]
    last_live = jnp.minimum(lens_s, t - 1) // block_t
    # the step's own row goes to ring row lens % t, in a block that is
    # always live (the last live one until the ring wraps). ``live_only``: a
    # slot at lens 0 holds no sequence that decodes (it may be one whose
    # prompt is arriving in chunks); it is written nothing, reads nothing, and
    # hands the block the output maps to back as it was
    row_at = jax.lax.rem(lens_s, t)
    holds = ti <= last_live
    if live_only:
        row_at = jnp.where(lens_s > 0, row_at, -1)
        holds = holds & (lens_s > 0)
    new_at = row_at - ti * block_t  # its lane in this tile, if it lies here

    @pl.when(holds)
    def _attend():
        # the tile as the ring holds it, one read for scores and values: the
        # row's own score and value are patched into s and acc, never into a
        # tile. What lies at the row's place is finite (zeros, or the row it
        # evicts)
        tile = c_ref[:]  # [d, block_t]
        lane = jax.lax.broadcasted_iota(jnp.int32, (heads, block_t), 1)
        idx = ti * block_t + lane
        if window:
            # a ring that wraps under a window: row r holds the newest position
            # r modulo t, lens - ((lens - r) mod t), and the slot reads the rows
            # of its last ``window`` positions
            ago = jax.lax.rem(lens_s, t) - idx
            ago = jnp.where(ago < 0, ago + t, ago)
            valid = ago < jnp.minimum(window, lens_s + 1)
        else:
            valid = (idx <= lens_s) | (lens_s >= t)
        if with_selection:  # of the live rows, those the indexer chose
            valid = valid & (sel_ref[:] > 0)
        at_row = lane == new_at  # nowhere, in a tile that does not hold it
        s = scale * jax.lax.dot_general(
            q_ref[:], tile, (((1,), (0,)), ((), ())), preferred_element_type=f32,
        )  # [heads, block_t]
        s = jnp.where(valid, jnp.where(at_row, snew_scr[:], s), NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if with_selection or window:  # a live tile may hold no row that is read
            p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        # the row's own weight (0 where it lies in another tile, or is not
        # among the chosen), rounded as the MXU's operand is
        own = jnp.sum(jnp.where(at_row, p, 0.0), axis=1, keepdims=True).astype(tile.dtype)
        acc_scr[:] = (
            acc_scr[:] * corr
            + jax.lax.dot_general(
                jnp.where(at_row, 0.0, p).astype(tile.dtype), tile[:value_dim],
                (((1,), (1,)), ((), ())), preferred_element_type=f32,
            )
            + own.astype(f32) * row_ref[:, :value_dim].astype(f32)
        )

    # what goes back: the one block of min(block_t, 128) rows that holds the
    # step's row, as ``_decode_slot_step`` hands it back. The rows of all slots
    # arrive a second time transposed, slots as lanes, so this slot's is a
    # column already: rolled from lane si % 128 to the row's lane and selected
    # into the block (Mosaic has no [1, d] -> [d, 1] reshape)
    back = co_ref.shape[-1]
    for b in range(block_t // back):
        at = new_at - b * back
        here_it_lies = (at >= 0) & (at < back)
        if live_only and b == 0:
            here_it_lies = here_it_lies | ((row_at < 0) & (ti == 0))

        @pl.when(here_it_lies)
        def _write():
            lanes = new_ref.shape[-1]
            shift = jax.lax.rem(at - jax.lax.rem(si, lanes) + lanes, lanes)
            col = pltpu.roll(_lanes32(new_ref[:]), shift, 1)[:, :back]
            old = _lanes32(c_ref[:, b * back:(b + 1) * back])
            here = jax.lax.broadcasted_iota(jnp.int32, old.shape, 1) == at
            patched = jnp.where(here, col, old)
            if patched.dtype != co_ref.dtype:
                patched = pltpu.bitcast(patched, co_ref.dtype)
            co_ref[:] = patched

    @pl.when(ti == num_t - 1)
    def _finish():
        l = l_scr[:]
        o_ref[:] = (acc_scr[:] / jnp.where(l == 0, 1.0, l)).astype(o_ref.dtype)


def mla_decode_plan(
    d: int, value_dim: int, t: int, *, block_t: int | None = None, interpret: bool | None = None,
) -> int:
    """The ring rows a tile of ``odtp_mla_decode_attn`` over a latent ring of
    ``t`` rows of ``d`` values, the first ``value_dim`` of them the values: 0
    where the kernel cannot tile the shape (and a call keeps the XLA path)."""
    if d % 8 != 0 or value_dim % 8 != 0:
        return 0
    return _ring_block(t, block_t, _interpret(interpret), preferred=512)


def mla_rows_written_back(block_t: int) -> int:
    """The ring rows a slot's step of ``odtp_mla_decode_attn`` hands back through
    the aliased output, of a tile of ``block_t``: the 128 that hold the step's
    row, or the tile where it is smaller (0 for no tile: the XLA form)."""
    return min(block_t, _LANES)


def mla_decode_attention(
    q: jax.Array,
    row: jax.Array,
    cache: jax.Array,
    lens: jax.Array,
    layer,
    *,
    scale: float,
    value_dim: int,
    block_t: int | None = None,
    interpret: bool | None = None,
    chosen: jax.Array | None = None,
    window: int = 0,
    live_only: bool = False,
):
    """One layer's share of a decode step of latent attention in the absorbed
    form, against the one latent ring ``cache`` [L, S, 1, Dl, T]: write each
    slot's new latent row [S, Dl] at ring row ``lens % T`` of ``layer``'s
    pages, then attend every head's absorbed query q [S, H, Dl] over the
    slot's live rows -> (o_lat [S, H, value_dim], cache): what
    :func:`~opendiloco_tpu.ops.attention.latent_decode_step_attention` gives,
    with the cache read and written where it lies (the output aliases the
    input; callers donate it).

    :func:`paged_decode_attention`'s plan with one operand: a grid step takes
    a ``(Dl, block_t)`` tile of one slot's page, cut from the whole cache
    (layer and ``lens`` by scalar prefetch; dead blocks skipped and their
    DMAs elided), scores all H heads against it and takes the values from
    its first ``value_dim`` rows, so each live row is read once a layer and
    step, not once for keys and once for values, and not once a head. The
    tiles are attended as the ring holds them, the new row's own score and
    value patched into the softmax, and of the tile that holds ring row
    ``lens % T`` only the block of :func:`mla_rows_written_back` rows around
    it goes back through the aliased output, the row in it as a column. A
    shape it cannot tile keeps the XLA path per call (:func:`mla_decode_plan`
    says beforehand).

    ``chosen`` [S, T] bool (an indexer over latent rows): one more operand,
    the selection a tile at a time; the kernel reads the tiles as ever and lets
    only the chosen rows into the softmax, as :func:`paged_decode_attention`
    does under its own. ``window``: the ring wraps and a slot reads the rows
    of its last ``window`` positions (a sliding layer's ring of a few tiles,
    every one visited). ``live_only``: a slot at ``lens`` 0 is written nothing
    (it may be one whose prompt is arriving in chunks)."""
    s_, h, d = q.shape
    t = ring_rows(cache)
    interp = _interpret(interpret)
    bt = mla_decode_plan(d, value_dim, t, block_t=block_t, interpret=interp)
    if not bt:
        return latent_decode_step_attention(
            q, row, cache, lens, layer, scale=scale, value_dim=value_dim,
            chosen=chosen, window=window, live_only=live_only,
        )
    num_t = t // bt
    back = mla_rows_written_back(bt)
    row = row.astype(cache.dtype)
    # and slots as lanes, so that a slot's new row is a column
    new = jnp.pad(row.T, ((0, 0), (0, -s_ % _LANES)))

    def page_map(si, ti, lens_ref, layer_ref):
        # clamp dead blocks to the last live one: unchanged index = no DMA
        last = jnp.minimum(lens_ref[si], t - 1) // bt
        return (layer_ref[0], si, 0, 0, jnp.minimum(ti, last))

    def written_map(si, ti, lens_ref, layer_ref):
        # the one block of the slot that goes back: the row's
        return (layer_ref[0], si, 0, 0, jax.lax.rem(lens_ref[si], t) // back)

    def slot_map(si, ti, lr, yr):
        return (si, 0, 0)

    def chosen_map(si, ti, lens_ref, layer_ref):
        return (si, 0, jnp.minimum(ti, jnp.minimum(lens_ref[si], t - 1) // bt))

    selection = [] if chosen is None else [chosen.astype(jnp.int32).reshape(s_, 1, t)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_, num_t),
        in_specs=[
            pl.BlockSpec((None, h, d), slot_map),
            pl.BlockSpec((None, 1, d), slot_map),
            pl.BlockSpec((d, _LANES), lambda si, ti, lr, yr: (0, si // _LANES)),
            pl.BlockSpec((None, None, None, d, bt), page_map),
            *([pl.BlockSpec((None, 1, bt), chosen_map)] if selection else []),
        ],
        out_specs=[
            pl.BlockSpec((None, h, value_dim), slot_map),
            pl.BlockSpec((None, None, None, d, back), written_map),
        ],
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),  # the step's own scores
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, value_dim), jnp.float32),
        ],
    )
    out, cache = pl.pallas_call(
        functools.partial(
            _mla_decode_kernel,
            scale=float(scale), block_t=bt, t=t, num_t=num_t, value_dim=value_dim,
            **({"window": int(window)} if window else {}),
            **({"with_selection": True} if selection else {}),
            **({"live_only": True} if live_only else {}),
        ),
        name="odtp_mla_decode_attn",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((s_, h, value_dim), q.dtype, vma=jax.typeof(q).vma),
            jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        ],
        # operands count the two scalar-prefetch vectors: the cache is input
        # 5, and comes back as output 1
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interp,
    )(
        lens.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
        q, row.reshape(s_, 1, d), new, cache, *selection,
    )
    return out, cache


# ---------------------------------------------------------------------------
# (c) a decode step over chosen blocks: the tiles that hold none stay unread
# ---------------------------------------------------------------------------

# ring rows a tile of :func:`block_decode_attention`: the lanes' 128, which is
# two blocks of 64 rows, the finest a tile of a rows-minor page can be cut
BLOCK_TILE = 128


def block_tile_plan(d: int, t: int, sizes: BlockSizes, interpret: bool | None = None) -> int:
    """The tile of :func:`block_decode_attention` over rings of ``t`` rows of
    heads of ``d`` under ``sizes``: whole blocks, a divisor of the ring, whole
    lanes on the chip; 0 where the kernel cannot tile the shape."""
    bt = _asked_block(t, None, _interpret(interpret)) or (BLOCK_TILE if t % BLOCK_TILE == 0 else 0)
    if not bt or d % 8 or bt % sizes.block_size or bt // sizes.block_size > 31:
        return 0
    return bt


def _rows_sum_kernel(first_ref, tiles_ref, layer_ref, a_ref, b_ref, o_ref, *, count, lanes):
    si = pl.program_id(0)
    first = first_ref[si]
    total = jnp.zeros(o_ref.shape, jnp.float32)
    for i, ref in enumerate((a_ref, b_ref)):  # the tile that holds ``first`` and the next
        tile = tiles_ref[2 * si + i]
        at = tile * lanes + jax.lax.broadcasted_iota(jnp.int32, ref.shape, 2)
        wanted = (at >= first) & (at < first + count)
        if i:  # at the ring's end the next tile is the same one again
            wanted = wanted & (tile != tiles_ref[2 * si])
        total += jnp.sum(jnp.where(wanted, ref[:].astype(jnp.float32), 0.0), axis=2, keepdims=True)
    o_ref[:] = total


def ring_rows_sum(
    cache_k: jax.Array, layer, first: jax.Array, count: int, *, interpret: bool | None = None
) -> jax.Array:
    """``ops.attention.ring_rows_sum`` with the ring read where it lies: a grid
    step a slot takes the 128-row tile that holds row ``first`` and the one
    behind it (``count`` rows cross one tile's edge at most) and sums the rows
    wanted. A ring that 128 rows do not divide, or a ``count`` past a tile,
    keeps the XLA path."""
    L, S, kh, d, t = cache_k.shape
    if t % _LANES or count > _LANES or d % 8:
        return xla_ring_rows_sum(cache_k, layer, first, count)
    first = first.astype(jnp.int32)
    tile = first // _LANES
    tiles = jnp.stack((tile, jnp.minimum(tile + 1, t // _LANES - 1)), axis=1).reshape(-1)
    block = lambda i: pl.BlockSpec(
        (None, None, kh, d, _LANES),
        lambda si, first_ref, tiles_ref, layer_ref: (layer_ref[0], si, 0, 0, tiles_ref[2 * si + i]),
    )
    out = pl.pallas_call(
        functools.partial(_rows_sum_kernel, count=count, lanes=_LANES),
        name="odtp_ring_rows_sum",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(S,), in_specs=[block(0), block(1)],
            out_specs=pl.BlockSpec((None, kh, d, 1), lambda si, *_: (si, 0, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((S, kh, d, 1), jnp.float32),
        interpret=_interpret(interpret),
    )(first, tiles, jnp.asarray(layer, jnp.int32).reshape(1), cache_k, cache_k)
    return out[..., 0]


def block_most_tiles(t: int, tile: int, sizes: BlockSizes) -> int:
    """The most tiles of ``tile`` rows a slot and KV head reads of a ring of
    ``t`` rows: ``topk`` blocks each in a tile of its own, or every tile of a
    sequence still under ``dense_len``."""
    return min(t // tile, max(sizes.topk, -(-sizes.dense_len // tile)))


def _tile_bits(chosen: jax.Array, lens: jax.Array, block: int, per: int) -> jax.Array:
    """Chosen blocks [..., S, Kh, blocks] -> which blocks of each tile of ``per``
    blocks are chosen and hold a row before ``lens`` [S], a bit a block [...,
    S, Kh, tiles] int32 (0: the tile stays unread)."""
    nb = chosen.shape[-1]
    chosen = chosen & (jnp.arange(nb) * block < lens[:, None, None])
    lead = [(0, 0)] * (chosen.ndim - 1)
    by_tile = jnp.pad(chosen, (*lead, (0, -nb % per))).reshape(*chosen.shape[:-1], -1, per)
    return jnp.sum(by_tile.astype(jnp.int32) << jnp.arange(per, dtype=jnp.int32), axis=-1)


def block_tiles_held(chosen: jax.Array, lens: jax.Array, sizes: BlockSizes, t: int) -> jax.Array:
    """The ring tiles that hold a chosen block with a row before ``lens``,
    summed over ``chosen`` [..., S, Kh, blocks] -> [1] int32: what a decode
    step over chosen blocks moves of rings of ``t`` rows, in tiles of
    :func:`block_tile_plan` rows (a block a tile where the kernel has none)."""
    per = (block_tile_plan(8, t, sizes) or sizes.block_size) // sizes.block_size
    held = _tile_bits(chosen, lens, sizes.block_size, per) > 0
    return jnp.sum(held).astype(jnp.int32).reshape(1)


def block_tile_lists(chosen: jax.Array, lens: jax.Array, block: int, tile: int, most: int):
    """Chosen blocks [S, Kh, blocks] (bool, up to each slot's own) -> what the
    kernel's grid walks, a slot and KV head: the ring tiles of ``tile`` rows
    that hold a chosen block with a row before ``lens``, in order [S, Kh,
    ``most``] (the places behind the last hold it again: an unchanged index
    moves nothing), how many they are [S, Kh], and which of each tile's blocks
    are chosen, a bit a block [S, Kh, ``most``]."""
    bits = _tile_bits(chosen, lens, block, tile // block)
    nt = bits.shape[-1]
    held = bits > 0
    counts = jnp.sum(held.astype(jnp.int32), axis=-1)
    order = jnp.sort(jnp.where(held, jnp.arange(nt), nt), axis=-1)[..., :most]
    if order.shape[-1] < most:
        order = jnp.pad(order, ((0, 0), (0, 0), (0, most - order.shape[-1])), constant_values=nt)
    last = jnp.take_along_axis(order, jnp.maximum(jnp.minimum(counts, most) - 1, 0)[..., None], -1)
    tiles = jnp.where(order < nt, order, jnp.where(last < nt, last, 0)).astype(jnp.int32)
    return tiles, jnp.minimum(counts, most), jnp.take_along_axis(bits, tiles, axis=-1)


def _block_decode_kernel(
    lens_ref, layer_ref, tiles_ref, counts_ref, bits_ref, q_ref, k_ref, v_ref,
    o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr, *, scale, block, block_t, most, nkv,
):
    si, gi, ti = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    flat = si * nkv + gi
    f32 = jnp.float32

    @pl.when(ti == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, f32)
        l_scr[:] = jnp.zeros(l_scr.shape, f32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, f32)

    @pl.when(ti < counts_ref[flat])
    def _step():
        at = flat * most + ti
        rows = q_ref.shape[0]
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, block_t), 1)
        of_block = _head_of(lane, block, block_t // block)  # the lane's block in the tile
        picked = (jax.lax.shift_right_logical(
            jnp.full((rows, block_t), bits_ref[at], jnp.int32), of_block) & 1) > 0
        valid = picked & (tiles_ref[at] * block_t + lane < lens_ref[si])
        s = scale * jax.lax.dot_general(
            q_ref[:], k_ref[:], (((1,), (0,)), ((), ())), preferred_element_type=f32
        )  # [rep, block_t]
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[:], (((1,), (1,)), ((), ())), preferred_element_type=f32
        )

    @pl.when(ti == most - 1)
    def _finish():
        l = l_scr[:]
        o_ref[:] = acc_scr[:] / jnp.where(l == 0, 1.0, l)
        m_ref[:] = m_scr[:]
        l_ref[:] = l


def block_decode_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, chosen: jax.Array,
    cache_k: jax.Array, cache_v: jax.Array, lens: jax.Array, layer, sizes: BlockSizes,
    *, interpret: bool | None = None,
):
    """One layer's share of a decode step under a selection by blocks: what
    ``ops.attention.block_decode_step_attention`` gives, reading of ``layer``'s
    pages ``[L, S, Kh, D, T]`` only the tiles of :func:`block_tile_plan` rows
    that hold a chosen block. A grid step is one tile of one slot and KV head
    under the head's ``rep`` queries; the tiles' indices, their number and each
    tile's chosen blocks ride the grid as scalar-prefetch vectors
    (:func:`block_tile_lists`), so a tile that holds no chosen block is neither
    visited nor moved (the steps behind a slot's last tile are skipped, their
    index unchanged: no DMA, as ``lens`` elides dead tiles in
    :func:`paged_decode_attention`). The rings are read as the step found them:
    the step's own row (k, v [S, Kh, D]) is merged in under the one softmax
    (``merge_own_row``) and written behind the layers. -> out [S, H, D]. A
    shape the kernel cannot tile raises: the caller asks ``block_tile_plan``
    first (the engine at construction)."""
    s_, h, d = q.shape
    nkv, t = k.shape[1], ring_rows(cache_k)
    interp = _interpret(interpret)
    bt = block_tile_plan(d, t, sizes, interp)
    if not bt or h % nkv:
        raise ValueError(
            f"no tile for a decode step over chosen blocks of {sizes.block_size} rows: "
            f"{nkv} KV heads of {d} under {h} query heads over {t} rows"
        )
    rep = h // nkv
    most = block_most_tiles(t, bt, sizes)
    tiles, counts, bits = block_tile_lists(chosen, lens, sizes.block_size, bt, most)

    def kv_map(si, gi, ti, lens_ref, layer_ref, tiles_ref, *_):
        return (layer_ref[0], si, gi, 0, tiles_ref[(si * nkv + gi) * most + ti])

    head = lambda si, gi, ti, *_: (si, gi, 0, 0)
    stat = jax.ShapeDtypeStruct((s_, nkv, rep, 1), jnp.float32)
    out, m, l = pl.pallas_call(
        functools.partial(
            _block_decode_kernel, scale=d**-0.5, block=sizes.block_size, block_t=bt,
            most=most, nkv=nkv,
        ),
        name="odtp_block_decode_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(s_, nkv, most),
            in_specs=[
                pl.BlockSpec((None, None, rep, d), head),
                pl.BlockSpec((None, None, None, d, bt), kv_map),
                pl.BlockSpec((None, None, None, d, bt), kv_map),
            ],
            out_specs=[
                pl.BlockSpec((None, None, rep, d), head),
                pl.BlockSpec((None, None, rep, 1), head),
                pl.BlockSpec((None, None, rep, 1), head),
            ],
            scratch_shapes=[
                pltpu.VMEM((rep, 1), jnp.float32), pltpu.VMEM((rep, 1), jnp.float32),
                pltpu.VMEM((rep, d), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((s_, nkv, rep, d), jnp.float32), stat, stat],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interp,
    )(
        lens.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1), tiles.reshape(-1),
        counts.reshape(-1), bits.reshape(-1), q.reshape(s_, nkv, rep, d), cache_k, cache_v,
    )
    return merge_own_row(out.reshape(s_, h, d), m.reshape(s_, h), l.reshape(s_, h), q, k, v)


# ---------------------------------------------------------------------------
# (d) a prefill chunk's attention over a slot's pages: the scores stay in VMEM
# ---------------------------------------------------------------------------

# The XLA forms (``ops.attention.tiled_sparse_attention``,
# ``tiled_block_attention``) walk the ring a tile at a time under the whole
# chunk's queries: a float32 score tile of query heads x chunk x tile, written
# to memory and passed over for the mask, the maximum, the exponential, the sum
# and the cast. Past :data:`_PREFILL_SCORE_BYTES` (what a whole prompt's scores
# were measured at, above) that tile is what a visit costs: 134 MB for 32 heads
# x 2,048 queries x 512 rows, some 0.8 ms a visit for 87 us of MXU work. The
# kernel holds a block of queries of one KV head's group and one K and V tile a
# grid step, and the score tile of one head at a time in VMEM.
# Measured on the v5e (PERF.md section 6, PR 62), ms a layer's call, 2,048
# queries at row 16,384 of a slot: 32 / 2 heads of 128 under a selection by
# blocks (MiniCPM-SALA) the XLA form 25.8, the kernel at 512 queries x 512 rows
# a grid step 8.3, 1,024 x 512 9.2, 256 x 512 9.1, 512 x 1,024 5.8, 1,024 x
# 1,024 5.6; 48 / 8 heads under the causal mask alone (Laguna, row 6,144) 13.9 /
# 5.0 / 5.6 / 5.6 / 3.4 / 3.2.
_CHUNK_QUERIES = 512  # queries a grid step
_CHUNK_ROWS = 1024  # ring rows a grid step, where the ring is whole tiles of them


def _chunk_queries(c: int) -> int:
    return _CHUNK_QUERIES if c % _CHUNK_QUERIES == 0 else c


def _chunk_rows(t: int, tile: int) -> int:
    """Ring rows a grid step of :func:`chunk_attention` holds: whole tiles of
    the XLA form's ``tile``, :data:`_CHUNK_ROWS` where they cut the ring."""
    return _CHUNK_ROWS if _CHUNK_ROWS % tile == 0 and t % _CHUNK_ROWS == 0 else tile


def chunk_form(
    c: int, hq: int, hkv: int, d: int, t: int, tile: int, decode_kernel: str | None = None
) -> str:
    """Which form a prefill chunk's grouped-query attention over a slot's
    pages takes, from what the call can see: "tiles-pallas"
    (:func:`chunk_attention`) where ``decode_kernel`` resolves to the kernels
    (the chip, or a test that asks for them interpreted), the heads are whole
    lanes, the ring of ``t`` rows is whole tiles of ``tile`` and the float32
    scores of a tile under the chunk's ``c`` queries, which the XLA form
    writes to memory, are worth it; else "tiled-xla". The engine reports it
    (``ServeEngine.block_forms``, ``kind_forms``)."""
    if resolve_decode_kernel(decode_kernel) != "pallas" or hq % hkv or t % tile:
        return "tiled-xla"
    bq = _chunk_queries(c)  # on the chip whole int8 sublanes, and a block VMEM holds
    if not _interpret(None) and (d % 128 or tile % 128 or bq % 32 or bq > 1024):
        return "tiled-xla"
    return "tiles-pallas" if hq * c * tile * 4 >= _PREFILL_SCORE_BYTES else "tiled-xla"


def chunk_tiles_held(visit: jax.Array) -> jax.Array:
    """Which tile a grid step holds, from the tiles a row of the grid visits
    (``visit`` [..., tiles] bool) -> int32 of its shape: a visited tile itself,
    a tile stepped over the next one visited (fetched ahead), the steps behind
    the last the last (an unchanged index moves nothing), -1 where the row
    visits none. A step is taken where it holds its own tile."""
    nk = visit.shape[-1]
    ki = jnp.arange(nk, dtype=jnp.int32)
    ahead = jax.lax.cummin(jnp.where(visit, ki, nk), axis=visit.ndim - 1, reverse=True)
    last = jnp.max(jnp.where(visit, ki, -1), axis=-1, keepdims=True)
    return jnp.where(ahead < nk, ahead, last).astype(jnp.int32)


def _softmax_start(m_scr, l_scr, acc_scr):
    m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)


def _softmax_visit(r, s, reads, v_ref, m_scr, l_scr, acc_scr):
    """A tile into head ``r``'s online softmax: its scores ``s`` [bq, bk]
    float32, of which a query reads the entries of ``reads``, and the tile's
    values ``v_ref`` [dv, bk], into the running maximum, sum and accumulator."""
    s = jnp.where(reads, s, NEG_INF)
    m_prev = m_scr[r]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(reads, jnp.exp(s - m_new), 0.0)
    keep = jnp.exp(m_prev - m_new)
    m_scr[r] = m_new
    l_scr[r] = l_scr[r] * keep + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[r] = acc_scr[r] * keep + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[:], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _softmax_finish(heads, o_ref, l_scr, acc_scr):
    def head(r, carry):
        l = l_scr[r]
        o_ref[r] = (acc_scr[r] / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, heads, head, 0)


def _chunk_attn_kernel(held_ref, q_ref, at_ref, k_ref, v_ref, *rest, scale, step, rep):
    picked_ref = rest[0] if len(rest) == 5 else None
    o_ref, m_scr, l_scr, acc_scr = rest[-4:]
    g, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)
    bq, bk = q_ref.shape[1], k_ref.shape[1]
    f32 = jnp.float32

    @pl.when(ki == 0)
    def _init():
        _softmax_start(m_scr, l_scr, acc_scr)

    @pl.when(held_ref[step(g, qi, ki)] == ki)
    def _visit():
        row = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        reads = row <= at_ref[:]  # the rows up to a query's own
        if picked_ref is not None:
            reads = reads & (picked_ref[:].astype(jnp.int32) > 0)

        def head(r, carry):
            s = scale * jax.lax.dot_general(
                q_ref[r], k_ref[:], (((1,), (0,)), ((), ())), preferred_element_type=f32
            )  # [bq, bk]
            _softmax_visit(r, s, reads, v_ref, m_scr, l_scr, acc_scr)
            return carry

        jax.lax.fori_loop(0, rep, head, 0)

    @pl.when(ki == nk - 1)
    def _finish():
        _softmax_finish(rep, o_ref, l_scr, acc_scr)


def chunk_attention(
    q: jax.Array, pages_k: jax.Array, pages_v: jax.Array, at: jax.Array, live_rows, tile: int,
    chosen: jax.Array | None = None, block: int = 1, *, interpret: bool | None = None,
):
    """A prefill chunk's attention over one slot's pages with the scores in
    VMEM: what ``ops.attention.tiled_sparse_attention`` and
    ``tiled_block_attention`` compute, by their equations (float32 scores from
    the operands as they are, masked entries at ``NEG_INF``, the running
    maximum, sum and accumulator in float32, the probabilities cast to the
    values' dtype, a query that reads no row zero). q [C, H, D] at positions
    ``at`` [C], pages_k and pages_v [Kh, D, T] (the chunk's own rows in them)
    -> (out [C, H, D], the tiles of ``tile`` rows visited [] int32, counted as
    the XLA form counts them: the tiles before ``live_rows`` (traced) in which
    some query chose something).

    A query reads the rows up to its own; under ``chosen`` [G, C, T / block]
    bool (G the KV heads, or 1: one choice for all) those of them whose block
    of ``block`` rows it chose (a selection by blocks; ``block`` 1: a selection
    by rows). The kernel takes the choice a row, int8 [G, C, T], expanded here.

    A grid step is one tile of K and of V of one KV head under a block of
    queries of its ``rep`` heads, which a loop walks (one trace of the body);
    the ring's tiles are the grid's last dimension. A step is neither taken
    nor its tiles fetched (:func:`chunk_tiles_held`, a scalar-prefetch table
    made here) where the tile starts at or past ``live_rows``, where it starts
    behind the block's last query, and where no query of the block chose
    anything in it."""
    from opendiloco_tpu.ops.flash_attention import _vmem_limit

    c, h, d = q.shape
    kh, _, t = pages_k.shape
    rep, bq, bk = h // kh, _chunk_queries(c), _chunk_rows(t, tile)
    nq, nk = c // bq, t // bk
    at = at.astype(jnp.int32)
    live = jnp.asarray(live_rows, jnp.int32)
    walked = jnp.arange(t // tile) < (live + tile - 1) // tile  # the XLA form's tiles
    last = jnp.max(at.reshape(nq, bq), axis=1)  # a block's last query
    first = jnp.arange(nk, dtype=jnp.int32) * bk
    visit = ((first < live)[None] & (first[None] <= last[:, None]))[None]  # [1, nq, nk]
    picked = []
    if chosen is not None:
        by_tile = lambda tiles, *lead: chosen.reshape(chosen.shape[0], *lead, tiles, -1)
        visit = visit & jnp.any(by_tile(nk, nq, bq), axis=(2, 4))
        walked = walked & jnp.any(by_tile(t // tile, c), axis=(0, 1, 3))
        picked = [jnp.repeat(chosen.astype(jnp.int8), block, axis=-1)]  # [G, C, T]
    group = (lambda g: g) if visit.shape[0] > 1 else (lambda g: 0)  # one choice for all: one row
    held = chunk_tiles_held(visit).reshape(-1)

    def step(g, qi, ki):  # its place in ``held``
        return (group(g) * nq + qi) * nk + ki

    def tile_of(g, qi, ki, held_ref):
        return jnp.maximum(held_ref[step(g, qi, ki)], 0)

    queries = pl.BlockSpec((None, rep, bq, d), lambda g, qi, ki, held_ref: (g, 0, qi, 0))
    page = pl.BlockSpec((None, d, bk), lambda g, qi, ki, held_ref: (g, 0, tile_of(g, qi, ki, held_ref)))
    choice = pl.BlockSpec(
        (None, bq, bk), lambda g, qi, ki, held_ref: (group(g), qi, tile_of(g, qi, ki, held_ref))
    )
    blocks = [((rep, bq, d), q.dtype), ((bq, 1), jnp.int32), ((d, bk), pages_k.dtype),
              ((d, bk), pages_v.dtype), ((bq, bk), jnp.int8), ((rep, bq, d), q.dtype)]
    scratch = [((rep, bq, 1), jnp.float32), ((rep, bq, 1), jnp.float32), ((rep, bq, d), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_chunk_attn_kernel, scale=d**-0.5, step=step, rep=rep),
        name="odtp_chunk_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(kh, nq, nk),
            in_specs=[
                queries, pl.BlockSpec((bq, 1), lambda g, qi, ki, held_ref: (qi, 0)), page, page,
                *[choice] * len(picked),
            ],
            out_specs=queries,
            scratch_shapes=[pltpu.VMEM(shape, dtype) for shape, dtype in scratch],
        ),
        out_shape=jax.ShapeDtypeStruct((kh, rep, c, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(*blocks, *blocks, *scratch, ((bq, bk), jnp.float32)),
        ),
        interpret=_interpret(interpret),
    )(held, jnp.moveaxis(q.reshape(c, kh, rep, d), 0, 2), at[:, None], pages_k, pages_v, *picked)
    return jnp.moveaxis(out, 2, 0).reshape(c, h, d), jnp.sum(walked).astype(jnp.int32)


# ---------------------------------------------------------------------------
# (d') a prefill chunk's latent attention over a slot's page, absorbed form
# ---------------------------------------------------------------------------

# Every head reads the one page of latent rows, so this is the kernel above
# with one KV head and all the heads its group: but no step holds the group (128
# heads' absorbed queries of a chunk of 512 are 75 MB, their accumulator 134),
# so the grid has a dimension of head blocks before the query blocks and the
# ring's tiles, and the page is fetched once a head block and query block.
_LATENT_HEADS = 8  # heads a grid step


def _latent_heads(h: int) -> int:
    return _LATENT_HEADS if h % _LATENT_HEADS == 0 else h


def latent_chunk_form(
    c: int, h: int, dl: int, value_dim: int, t: int, tile: int, decode_kernel: str | None = None
) -> str:
    """Which form a prefill chunk's latent attention in the absorbed form over
    a slot's page takes, from what the call can see, as :func:`chunk_form`
    chooses: "absorbed-pallas" (:func:`latent_chunk_attention`) where
    ``decode_kernel`` resolves to the kernels, the ring of ``t`` rows is whole
    tiles of ``tile``, the blocks are ones the chip tiles, and the float32
    scores of a tile under the chunk's ``c`` queries of ``h`` heads, which the
    XLA form writes to memory beside a running sum as large, reach
    :data:`_PREFILL_SCORE_BYTES`; else "absorbed-xla"
    (``ops.attention.tiled_latent_attention``). The engine reports it
    (``ServeEngine.latent_forms``)."""
    if resolve_decode_kernel(decode_kernel) != "pallas" or t % tile:
        return "absorbed-xla"
    bq = _chunk_queries(c)  # on the chip whole int8 sublanes, and a block VMEM holds
    if not _interpret(None) and (dl % 16 or value_dim % 128 or tile % 128 or bq % 32 or bq > 512):
        return "absorbed-xla"
    return "absorbed-pallas" if h * c * tile * 4 >= _PREFILL_SCORE_BYTES else "absorbed-xla"


def _latent_chunk_kernel(held_ref, q_ref, page_ref, reads_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, value_dim):
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        _softmax_start(m_scr, l_scr, acc_scr)

    @pl.when(held_ref[qi * nk + ki] == ki)
    def _visit():
        reads = reads_ref[:].astype(jnp.int32) > 0  # [bq, bk]: one set a query for all heads

        def head(r, carry):
            s = scale * jax.lax.dot_general(
                q_ref[r], page_ref[:], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )  # [bq, bk]: all Dl values of a row, the contraction as it is
            # the values: the same tile's first ``value_dim`` rows
            _softmax_visit(r, s, reads, page_ref.at[:value_dim], m_scr, l_scr, acc_scr)
            return carry

        jax.lax.fori_loop(0, q_ref.shape[0], head, 0)

    @pl.when(ki == nk - 1)
    def _finish():
        _softmax_finish(q_ref.shape[0], o_ref, l_scr, acc_scr)


def latent_chunk_attention(
    q: jax.Array, page: jax.Array, reads: jax.Array, live_rows, tile: int,
    *, scale: float, value_dim: int, interpret: bool | None = None,
) -> jax.Array:
    """A prefill chunk's latent attention in the absorbed form over one slot's
    page with the scores in VMEM: what ``ops.attention.tiled_latent_attention``
    computes, which has this signature, by its equations (float32 scores from
    the operands as they are, times ``scale``; entries a query does not read at
    ``NEG_INF``; the running maximum, sum and accumulator in float32; the
    probabilities cast to the page's dtype; the values the same tile's first
    ``value_dim`` rows; a query that reads no row zero). q [C, H, Dl], ``page``
    [Dl, T], ``reads`` [C, T] bool (one set a query for all its heads; the
    kernel takes it int8 a row) -> o_lat [C, H, value_dim].

    A grid step is one tile of ``tile`` ring rows, fetched once for scores and
    values, under a block of queries of :data:`_LATENT_HEADS` heads, which a
    loop walks (one trace of the body); the head blocks are the grid's first
    dimension, the ring's tiles its last. ``Dl`` need be no whole number of
    lanes (576 = 512 + 64): the scores are one product over all of it, which
    Mosaic pads as ``odtp_mla_decode_attn``'s. A step is neither taken nor its
    tile fetched (:func:`chunk_tiles_held`) where the tile starts at or past
    ``live_rows`` (traced) and where no query of the block reads anything in
    it: the tiles behind the block's last row among them."""
    from opendiloco_tpu.ops.flash_attention import _vmem_limit

    c, h, dl = q.shape
    t = page.shape[-1]
    bh, bq, bk = _latent_heads(h), _chunk_queries(c), tile
    nq, nk = c // bq, t // bk
    first = jnp.arange(nk, dtype=jnp.int32) * bk
    visit = (first < jnp.asarray(live_rows, jnp.int32))[None] & jnp.any(
        reads.reshape(nq, bq, nk, bk), axis=(1, 3)
    )
    held = chunk_tiles_held(visit).reshape(-1)

    def tile_of(hi, qi, ki, held_ref):
        return jnp.maximum(held_ref[qi * nk + ki], 0)

    queries = lambda width: pl.BlockSpec((bh, bq, width), lambda hi, qi, ki, held_ref: (hi, qi, 0))
    blocks = [((bh, bq, dl), q.dtype), ((dl, bk), page.dtype), ((bq, bk), jnp.int8),
              ((bh, bq, value_dim), q.dtype)]
    scratch = [((bh, bq, 1), jnp.float32), ((bh, bq, 1), jnp.float32), ((bh, bq, value_dim), jnp.float32)]
    out = pl.pallas_call(
        functools.partial(_latent_chunk_kernel, scale=float(scale), value_dim=value_dim),
        name="odtp_latent_chunk_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(h // bh, nq, nk),
            in_specs=[
                queries(dl),
                pl.BlockSpec((dl, bk), lambda hi, qi, ki, held_ref: (0, tile_of(hi, qi, ki, held_ref))),
                pl.BlockSpec((bq, bk), lambda hi, qi, ki, held_ref: (qi, tile_of(hi, qi, ki, held_ref))),
            ],
            out_specs=queries(value_dim),
            scratch_shapes=[pltpu.VMEM(shape, dtype) for shape, dtype in scratch],
        ),
        out_shape=jax.ShapeDtypeStruct((h, c, value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(*blocks, *blocks, *scratch, ((bq, bk), jnp.float32)),
        ),
        interpret=_interpret(interpret),
    )(held, jnp.swapaxes(q, 0, 1), page, reads.astype(jnp.int8))
    return jnp.swapaxes(out, 0, 1)


# ---------------------------------------------------------------------------
# (f) the Kimi-delta mixer's decode step: a slot's state visited once
# ---------------------------------------------------------------------------

# The states of a grid step: as many of a slot's heads as stay under this (16
# heads of [128, 128] float32), the block in and the block out both
# double-buffered: 4 MB of VMEM. Measured on the v5e at the solar2 cell's shapes
# (PERF.md section 6, PR 65; ``scripts/kda_step_bench.py``), us a layer of 126
# live slots x 64 heads, 1.06 GB there and back: 8 heads a step 1,762.5 (600
# GB/s), 16 1,625.1 (650), 32 1,626.5 (650), beside the XLA form's 2,383.4.
_KDA_STATE_BYTES = 1024 * 1024

# what a grid step does with its slot (the kernel's third prefetched vector)
_KDA_LIVE, _KDA_KEEP, _KDA_PASS = 1, 0, 2


def kda_step_form(decode_kernel: str | None, head_dim: int) -> str:
    """Which form a kda layer's decode step takes, from what the call can see:
    "pallas" (:func:`kda_step`) where ``decode_kernel`` resolves to the kernels
    and a head's state [D, D] is whole tiles of 128 lanes; else "xla"
    (``models.kda.step_state``, the tests' reference). The engine reports it
    (``ServeEngine.kda_forms``)."""
    pallas = resolve_decode_kernel(decode_kernel) == "pallas"
    return "pallas" if pallas and head_dim % _LANES == 0 else "xla"


def _kda_heads(h: int, d: int) -> int:
    """Heads a grid step of :func:`kda_step`: the most (a divisor of ``h``)
    whose float32 states stay under :data:`_KDA_STATE_BYTES`, and whose three
    columns a head (decay, key, query) are lanes of one tile."""
    fit = max(1, min(_KDA_STATE_BYTES // (d * d * 4), _LANES // 3))
    return max(n for n in range(1, h + 1) if h % n == 0 and n <= fit)


def _kda_step_kernel(layer_ref, src_ref, what_ref, x_ref, s_ref, o_ref, out_ref, rows_scr, cols_scr):
    what = what_ref[pl.program_id(1)]
    heads = s_ref.shape[0]

    @pl.when(what == _KDA_LIVE)
    def _step():
        # decay, key and query vary along a state's rows: their rows [heads, D]
        # side by side, turned once a step, are a column [D, 1] a head each
        rows_scr[0:heads] = jnp.exp(x_ref[0])
        rows_scr[heads : 2 * heads] = x_ref[1]
        rows_scr[2 * heads : 3 * heads] = x_ref[2]
        cols_scr[:] = rows_scr[:].T
        kq = jnp.sum(x_ref[1] * x_ref[2], axis=-1, keepdims=True)  # [heads, 1]
        for j in range(heads):
            a, k, q = (cols_scr[:, i * heads + j : i * heads + j + 1] for i in range(3))
            decayed = a * s_ref[j]  # S' = Diag(a) S, [D (key), D (value)]
            r_k = jnp.sum(decayed * k, axis=0, keepdims=True)  # S'^T k, [1, D]
            r_q = jnp.sum(decayed * q, axis=0, keepdims=True)
            u = x_ref[4, j : j + 1] * (x_ref[3, j : j + 1] - r_k)  # beta (v - S'^T k)
            o_ref[j : j + 1] = r_q + kq[j : j + 1] * u  # S_new^T q
            out_ref[j] = decayed + k * u

    @pl.when(what != _KDA_LIVE)
    def _dead():
        o_ref[:] = jnp.zeros(o_ref.shape, o_ref.dtype)

    # a dead slot's step is mapped to the block of the live slot before it,
    # which stays where it is (no fetch, nothing written back: its state keeps
    # its bytes in memory); before the first live slot it is mapped to that
    # slot's block (or, where none is live, slot 0's), which goes out as it came
    @pl.when(what == _KDA_PASS)
    def _pass():
        out_ref[:] = s_ref[:]


def kda_step(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    states: jax.Array, layer, live: jax.Array, *, interpret: bool | None = None,
):
    """One kda layer's share of a decode step against the stacked states
    ``[Lk, S, H, D, D]`` float32, read and written where they lie (the output
    aliases the input; callers donate it): what ``models.kda.step_state``
    computes and the write of the layer's slice into the stack, by the
    module's equations in float32, in **one read and one write of each live
    slot's state**. q, k, v and g (the log of the decay a key channel) [S, H,
    D], beta [S, H], ``layer`` the layer's index in the stack (traced: a
    scalar-prefetch operand that the index maps read), ``live`` [S] bool ->
    (o [S, H, D] float32, the states).

    A grid step holds :func:`_kda_heads` heads of a slot in VMEM: ``S' =
    Diag(a) S``, ``r_k = S'^T k`` and ``r_q = S'^T q`` as sums over the rows of
    one pass over the block, ``u = beta (v - r_k)``, ``S_new = S' + k u^T``, ``o
    = r_q + (k . q) u``. A slot that holds no sequence (it may be one whose
    prompt is arriving in chunks) is neither computed nor fetched nor written:
    its grid steps are mapped to the block the step before them holds (the
    nearest live slot's before it, resident and already computed), so its
    bytes stay what they were through the alias; ``o`` is zero there."""
    _, s_, h, d, _ = states.shape
    f32 = jnp.float32
    hb = _kda_heads(h, d)
    groups = h // hb
    # a slot's five rows a head: log decay, key, query, value, beta (over D)
    x = jnp.stack([g, k, q, v, jnp.broadcast_to(beta[..., None], g.shape)], axis=1)
    x = x.astype(f32).reshape(s_, 5, groups, hb, d)
    at = jnp.arange(s_, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(live, at, -1))  # the nearest live slot up to and with each
    src = jnp.where(before >= 0, before, jnp.argmax(live).astype(jnp.int32))
    what = jnp.where(live, _KDA_LIVE, jnp.where(before >= 0, _KDA_KEEP, _KDA_PASS)).astype(jnp.int32)
    block = pl.BlockSpec(
        (None, None, hb, d, d),
        lambda gi, si, layer_ref, src_ref, what_ref: (layer_ref[0], src_ref[si], gi, 0, 0),
    )
    o, states = pl.pallas_call(
        _kda_step_kernel,
        name="odtp_kda_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(groups, s_),
            in_specs=[
                pl.BlockSpec((None, 5, None, hb, d), lambda gi, si, *_: (si, 0, gi, 0, 0)),
                block,
            ],
            out_specs=[pl.BlockSpec((None, None, hb, d), lambda gi, si, *_: (si, gi, 0, 0)), block],
            scratch_shapes=[pltpu.VMEM((_LANES, d), f32), pltpu.VMEM((d, _LANES), f32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((s_, groups, hb, d), f32),
            jax.ShapeDtypeStruct(states.shape, states.dtype),
        ],
        # operands count the three prefetched vectors: the states follow the rows
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        interpret=_interpret(interpret),
    )(jnp.asarray(layer, jnp.int32).reshape(1), src, what, x, states)
    return o.reshape(s_, h, d), states
