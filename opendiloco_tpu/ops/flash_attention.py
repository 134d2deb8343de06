"""Pallas TPU flash attention (forward + backward), causal, GQA-aware.

TPU-native replacement for the reference's optional FlashAttention-2 CUDA
kernels (README.md:41-47, train_fsdp.py:107). FlashAttention-2-style online
softmax: never materializes the [T, T] score matrix; scores and softmax
statistics accumulate in float32 on the MXU/VPU while q/k/v stream through
VMEM tiles.

Layout: grid (batch, q-head, q-block, k-block) with the k-block dimension
sequential ("arbitrary") -- K/V stream through VMEM one [block_k, d] tile
per step while the online-softmax state (m, l, acc) persists in VMEM
scratch across k-steps. Per-step VMEM is O(block_q*d + block_k*d),
independent of T, so sequence length is bounded by HBM, not VMEM. GQA is
handled in the BlockSpec index maps (q-head h reads kv-head h // rep) --
KV is never materialized at q-head width.

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
accumulating over the rep q-heads of each kv head).
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


def _tiles(phases, causal, q_lo, block_q, k_lo, block_k, whole, keys_first=False):
    """One grid step of a kernel whose update of one item is the generator
    ``phases``: the ``whole`` items, unmasked, for a tile wholly on or below
    the diagonal (and for every tile of full attention); for a tile the
    diagonal crosses, the walk where the blocks are equal, else the whole
    tile under its mask; nothing for a tile above the diagonal."""
    below, crossed = _tile_classes(q_lo, block_q, k_lo, block_k)
    pl.when(jnp.logical_or(not causal, below))(lambda: _run(phases, whole))
    if not causal:
        return

    @pl.when(crossed)
    def _diagonal():
        if block_q == block_k:
            return _run(phases, _diagonal_items(block_q, keys_first))
        first, second = (block_k, block_q) if keys_first else (block_q, block_k)
        mask = _lower_triangle(first, second, q_lo, k_lo, keys_first)
        _run(phases, [(slice(0, first), slice(0, second), mask)])


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, ((contract[:1], contract[1:]), ((), ())),
        preferred_element_type=jnp.float32,
    )


_NT = (1, 1)  # a @ b.T
_NN = (1, 0)  # a @ b


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, num_k: int
):
    # q_ref/o_ref: [block_q, d]; k_ref/v_ref: [block_k, d] (one tile per step)
    block_q, d = q_ref.shape
    block_k = k_ref.shape[0]
    qi, ki = pl.program_id(2), pl.program_id(3)
    on_q = _scale_on_operand(scale)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros((block_q, 1), jnp.float32)
        acc_scr[:] = jnp.zeros((block_q, d), jnp.float32)

    def phases(rows, cols, mask):
        """One online-softmax update of query rows ``rows`` against key
        columns ``cols``. Matmul inputs stay in bf16 (f32 inputs run the MXU
        at ~1/8 rate on v5e); accumulation and softmax statistics are f32."""
        q = q_ref[rows, :]
        q = q * scale if on_q else q
        s = _dot(q, k_ref[cols, :], _NT)  # [rows, cols]
        s = s if on_q else scale * s
        s = s if mask is None else jnp.where(mask, s, _NEG_INF)
        yield
        m_prev = m_scr[rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        m_scr[rows, :] = m_new
        corr = jnp.exp(m_prev - m_new)
        yield
        p = jnp.exp(s - m_new)
        yield
        l_scr[rows, :] = l_scr[rows, :] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[rows, :] = acc_scr[rows, :] * corr + _dot(
            p.astype(v_ref.dtype), v_ref[cols, :], _NN
        )

    rows = block_q if block_q % _FWD_ROWS else _FWD_ROWS
    _tiles(
        phases, causal, qi * block_q, block_q, ki * block_k, block_k,
        [(slice(i, i + rows), slice(0, block_k), None) for i in range(0, block_q, rows)],
    )

    @pl.when(ki == num_k - 1)
    def _finish():
        l = l_scr[:]
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[:] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[:] = (m_scr[:] + jnp.log(l_safe)).reshape(1, block_q)


def _fwd(q, k, v, *, block_q: int, block_k: int, causal: bool, vma=None,
         interpret: bool = False):
    """q: [B, Hq, T, D]; k/v: [B, Hkv, T, D] -> (out [B, Hq, T, D], lse [B, Hq, 1, T]).

    ``vma``: varying-manual-axes annotation for the outputs, required when
    called inside a shard_map manual region (the ring-attention chunks).
    When unset it is derived from q so the kernel types correctly in ANY
    manual region (e.g. flash_attention_sharded's batch/tp shard_map).
    """
    vma = jax.typeof(q).vma if vma is None else vma
    b, hq, t, d = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    scale = d**-0.5
    num_k = t // block_k

    if causal:
        # clamp skipped above-diagonal steps to the last needed tile: an
        # unchanged block index re-uses the resident copy (no DMA)
        def kv_map(bi, hi, qi, ki):
            last = (qi * block_q + block_q - 1) // block_k
            return (bi, hi // rep, jnp.minimum(ki, last), 0)
    else:
        def kv_map(bi, hi, qi, ki):
            return (bi, hi // rep, ki, 0)

    grid = (b, hq, t // block_q, num_k)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, num_k=num_k),
        name="odtp_flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec(
                (None, None, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
            ),
            pl.BlockSpec((None, None, block_k, d), kv_map),
            pl.BlockSpec((None, None, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec(
                (None, None, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
            ),
            pl.BlockSpec(
                (None, None, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, 0, qi)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype, vma=vma),
            jax.ShapeDtypeStruct((b, hq, 1, t), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out, lse


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
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        block_q=block_q, block_k=block_k, causal=causal, interpret=interpret,
    )
    return out.transpose(0, 2, 1, 3), lse[:, :, 0].transpose(0, 2, 1)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, scale, causal, num_k
):
    # q/do/dq: [block_q, d]; k/v: [block_k, d] per step; lse/delta: [1, block_q]
    block_q, d = q_ref.shape
    block_k = k_ref.shape[0]
    qi, ki = pl.program_id(2), pl.program_id(3)
    on_q = _scale_on_operand(scale)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros((block_q, d), jnp.float32)

    def phases(rows, cols, mask):
        """dq of query rows ``rows`` from key columns ``cols``."""
        n = rows.stop - rows.start
        q = q_ref[rows, :]
        q = q * scale if on_q else q
        k_blk = k_ref[cols, :]
        s = _dot(q, k_blk, _NT)
        s = s if on_q else scale * s
        dp = _dot(do_ref[rows, :], v_ref[cols, :], _NT)
        yield
        s = s if mask is None else jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[:, rows].reshape(n, 1))
        ds = (p * (dp - delta_ref[:, rows].reshape(n, 1))).astype(k_blk.dtype)
        yield
        dq_scr[rows, :] = dq_scr[rows, :] + scale * _dot(ds, k_blk, _NN)

    _tiles(
        phases, causal, qi * block_q, block_q, ki * block_k, block_k,
        [(slice(0, block_q), slice(0, block_k), None)],
    )

    @pl.when(ki == num_k - 1)
    def _finish():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, scale, causal, rep, num_q
):
    # grid point: (batch, kv-head, k-block, rep*q-block). q/do: [1, block_q, d]
    # per step; k/v/dk/dv: [block_k, d]; lse/delta: [1, 1, block_q]
    block_k, d = k_ref.shape
    block_q = q_ref.shape[1]
    ki, step = pl.program_id(2), pl.program_id(3)
    qj = step % num_q  # q-block index within a head
    on_q = _scale_on_operand(scale)

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros((block_k, d), jnp.float32)
        dv_scr[:] = jnp.zeros((block_k, d), jnp.float32)

    def phases(cols, rows, mask):
        """dk and dv of key rows ``cols`` from query rows ``rows``. The
        scores are computed transposed, ``[key rows, query rows]``: every
        matmul then has the MXU's own forms (a @ b.T, a @ b), and the
        lane-dense ``lse`` and ``delta`` rows are read as they lie."""
        k_blk = k_ref[cols, :]
        k_blk = k_blk * scale if on_q else k_blk
        q = q_ref[0, rows, :]
        do = do_ref[0, rows, :]
        s = _dot(k_blk, q, _NT)  # [cols, rows]
        s = s if on_q else scale * s
        dp = _dot(v_ref[cols, :], do, _NT)
        yield
        s = s if mask is None else jnp.where(mask, s, _NEG_INF)
        p = jnp.exp(s - lse_ref[0, :, rows])
        ds = (p * (dp - delta_ref[0, :, rows])).astype(q.dtype)
        yield
        dv_scr[cols, :] = dv_scr[cols, :] + _dot(p.astype(do.dtype), do, _NN)
        dk_scr[cols, :] = dk_scr[cols, :] + scale * _dot(ds, q, _NN)

    # causal: only q blocks at or after this k block contribute
    _tiles(
        phases, causal, qj * block_q, block_q, ki * block_k, block_k,
        [(slice(0, block_k), slice(0, block_q), None)], keys_first=True,
    )

    @pl.when(step == rep * num_q - 1)
    def _finish():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _delta(dout, out):
    """delta = rowsum(dO * O), f32: [B, Hq, T, D] -> [B, Hq, 1, T]."""
    b, hq, t, _ = out.shape
    return jnp.sum(
        dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    ).reshape(b, hq, 1, t)


def _bwd(block_q, block_k, causal, res, dout):
    q, k, v, out, lse = res
    return _bwd_impl(
        q, k, v, dout, lse, _delta(dout, out),
        block_q=block_q, block_k=block_k, causal=causal,
    )


def _bwd_impl(
    q, k, v, dout, lse, delta, *, block_q, block_k, causal, grad_dtype=None,
    vma=None,
):
    """Backward kernels with delta precomputed. ``grad_dtype`` overrides the
    output dtype and ``vma`` annotates varying manual axes (both used by the
    ring-attention chunk path, which accumulates f32 inside shard_map);
    an unset vma is derived from q (see _fwd)."""
    vma = jax.typeof(q).vma if vma is None else vma
    b, hq, t, d = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    scale = d**-0.5
    num_k = t // block_k
    num_q = t // block_q

    if causal:
        def kv_map(bi, hi, qi, ki):
            last = (qi * block_q + block_q - 1) // block_k
            return (bi, hi // rep, jnp.minimum(ki, last), 0)
    else:
        def kv_map(bi, hi, qi, ki):
            return (bi, hi // rep, ki, 0)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal, num_k=num_k),
        name="odtp_flash_dq",
        grid=(b, hq, num_q, num_k),
        in_specs=[
            pl.BlockSpec(
                (None, None, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
            ),
            pl.BlockSpec((None, None, block_k, d), kv_map),
            pl.BlockSpec((None, None, block_k, d), kv_map),
            pl.BlockSpec(
                (None, None, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
            ),
            pl.BlockSpec(
                (None, None, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, 0, qi)
            ),
            pl.BlockSpec(
                (None, None, 1, block_q), lambda bi, hi, qi, ki: (bi, hi, 0, qi)
            ),
        ],
        out_specs=pl.BlockSpec(
            (None, None, block_q, d), lambda bi, hi, qi, ki: (bi, hi, qi, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, grad_dtype or q.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(q, k, v, dout, lse, delta)

    # dk/dv: group q by kv head: [b, hkv, rep, t, d]; the sequential grid
    # dim walks (rep, q-block) in row-major order, streaming one q tile per
    # step while dk/dv accumulate in scratch
    q_g = q.reshape(b, hkv, rep, t, d)
    do_g = dout.reshape(b, hkv, rep, t, d)
    lse_g = lse.reshape(b, hkv, rep, 1, t)
    delta_g = delta.reshape(b, hkv, rep, 1, t)

    def _qj(ki, st):
        qj = st % num_q
        if causal:  # clamp skipped below-diagonal q tiles (DMA elision)
            qj = jnp.maximum(qj, (ki * block_k) // block_q)
        return qj

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, rep=rep, num_q=num_q
        ),
        name="odtp_flash_dkv",
        grid=(b, hkv, num_k, rep * num_q),
        in_specs=[
            pl.BlockSpec(
                (None, None, 1, block_q, d),
                lambda bi, hi, ki, st: (bi, hi, st // num_q, _qj(ki, st), 0),
            ),
            pl.BlockSpec(
                (None, None, block_k, d), lambda bi, hi, ki, st: (bi, hi, ki, 0)
            ),
            pl.BlockSpec(
                (None, None, block_k, d), lambda bi, hi, ki, st: (bi, hi, ki, 0)
            ),
            pl.BlockSpec(
                (None, None, 1, block_q, d),
                lambda bi, hi, ki, st: (bi, hi, st // num_q, _qj(ki, st), 0),
            ),
            pl.BlockSpec(
                (None, None, 1, 1, block_q),
                lambda bi, hi, ki, st: (bi, hi, st // num_q, 0, _qj(ki, st)),
            ),
            pl.BlockSpec(
                (None, None, 1, 1, block_q),
                lambda bi, hi, ki, st: (bi, hi, st // num_q, 0, _qj(ki, st)),
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (None, None, block_k, d), lambda bi, hi, ki, st: (bi, hi, ki, 0)
            ),
            pl.BlockSpec(
                (None, None, block_k, d), lambda bi, hi, ki, st: (bi, hi, ki, 0)
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, grad_dtype or k.dtype, vma=vma),
            jax.ShapeDtypeStruct(v.shape, grad_dtype or v.dtype, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
    )(q_g, k, v, do_g, lse_g, delta_g)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, block_q, block_k, causal):
    out, _ = _fwd(q, k, v, block_q=block_q, block_k=block_k, causal=causal)
    return out


def _flash_fwd(q, k, v, block_q, block_k, causal):
    out, lse = _fwd(q, k, v, block_q=block_q, block_k=block_k, causal=causal)
    # tag the kernel outputs so the remat policies (llama._maybe_remat) can
    # save them -- without these names the backward pass reruns the whole
    # forward kernel just to rebuild its residuals. ``out`` is tagged as
    # [B, T, H * D], the form the output projection reads: kept in the
    # kernel's layout, a head of 64 fills half of the chip's 128 lanes and
    # a saved copy takes twice its bytes
    b, h, t, d = out.shape
    kept = checkpoint_name(out.transpose(0, 2, 1, 3).reshape(b, t, h * d), "attn_out")
    out = kept.reshape(b, t, h, d).transpose(0, 2, 1, 3)
    lse = checkpoint_name(lse, "attn_lse")
    return out, (q, k, v, out, lse)


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


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 1024,
    block_k: int = 1024,
) -> jax.Array:
    """[B, T, H, D] attention via the Pallas kernel; falls back to XLA for
    shapes the kernel doesn't tile (T not a multiple of 128).

    Blocks default large, 1024 x 1024: a grid step has a fixed cost, and VMEM
    per step is only O(block*d) + the [bq, bk] f32 score tile. What that
    rests on for these kernels (PR 42, TPU v5e, seq 2,048, 15/5 heads of 64,
    batch 8, before the sub-tile walk): at 512 x 512 the forward read 2.59 ms
    a call against 1.83, dq 1.93 against 1.82, dkv 2.41 against 2.23. No
    other block shape was measured on this chip for this shape, and none
    since the walk (PERF.md section 6, PR 42)."""
    block_q, block_k = _resolve_blocks(q.shape[1], q.shape[3], block_q, block_k)
    if block_q == 0:
        from opendiloco_tpu.ops.attention import xla_attention

        return xla_attention(q, k, v, causal=causal)
    # kernel layout is [B, H, T, D]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash(qt, kt, vt, block_q, block_k, causal)
    return out.transpose(0, 2, 1, 3)


def flash_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh,
    batch_axes: tuple = (),
    tp_axis=None,
    causal: bool = True,
) -> jax.Array:
    """SPMD entry for multi-device meshes.

    Mosaic kernels cannot be automatically partitioned — XLA raises at
    compile the moment a pallas operand has a sharded dimension (found by
    the deviceless multichip AOT compile, round 5; a single-chip mesh
    never hits it). Attention is independent per (batch row, head), so
    the fix is a shard_map manual over exactly the axes the activations
    are sharded on: the batch axes always, and tp on the head dims when
    it divides BOTH q and kv head counts (shards then keep whole GQA
    groups, so the kernel's local group arithmetic is unchanged). A
    non-dividing tp head dim is instead replicated into the region (tp
    is in the manual set with no spec entry = all-gather), which is the
    same gather the auto partitioner would emit.

    Do NOT call inside another manual region (the pp pipeline): nested
    shard_map has no jvp lowering — there the pipeline's in_specs gather
    the batch, operands arrive replicated, and the plain kernel compiles.
    """
    if mesh is None or getattr(mesh, "size", 1) <= 1:
        return flash_attention(q, k, v, causal=causal)
    P = jax.sharding.PartitionSpec
    hq, hkv = q.shape[2], k.shape[2]
    head = None
    if tp_axis is not None and mesh.shape[tp_axis] > 1:
        n_tp = mesh.shape[tp_axis]
        if hq % n_tp == 0 and hkv % n_tp == 0:
            head = tp_axis
    spec = P(tuple(batch_axes) or None, None, head, None)
    fn = jax.shard_map(
        lambda a, b, c: flash_attention(a, b, c, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        # ALL mesh axes manual: a partially-manual pallas call still goes
        # through the auto partitioner for the remaining axes and XLA
        # refuses; axes outside the spec replicate into the region (the
        # same gather auto partitioning would emit)
        axis_names=set(mesh.axis_names),
    )
    return fn(q, k, v)
