"""Attention kernels for TPU.

The reference selects between torch SDPA and FlashAttention-2 CUDA kernels via
``attn_implementation`` (open_diloco/train_fsdp.py:107,173; README.md:41-47).
Here the equivalent menu is:

- ``xla``: plain jnp attention; XLA fuses it well on TPU and keeps the
  matmuls on the MXU. Softmax accumulates in float32.
- ``pallas``: a Pallas flash-attention kernel (ops/flash_attention.py) that
  tiles over the sequence and never materializes the [T, T] score matrix.
- ``ring``: ring attention over a sequence-parallel mesh axis
  (ops/ring_attention.py) for long-context training; each device holds a
  sequence shard and K/V blocks rotate around the ring via ppermute.

All entry points share one signature over [batch, seq, heads, head_dim]
arrays with grouped-query support (num_q_heads % num_kv_heads == 0).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from opendiloco_tpu.models.ring_cache import (
    layer_pages,
    ring_rows,
    rows_first,
    write_live_row,
    write_row,
)
from opendiloco_tpu.ops.pallas_util import NEG_INF


def _repeat_kv(k: jax.Array, num_q_heads: int) -> jax.Array:
    """Broadcast KV heads up to the query head count (GQA), over
    [batch | slot, rows, kv heads, head_dim]."""
    b, t, nkv, d = k.shape
    if nkv == num_q_heads:
        return k
    assert num_q_heads % nkv == 0, (num_q_heads, nkv)
    rep = num_q_heads // nkv
    return jnp.broadcast_to(k[:, :, :, None, :], (b, t, nkv, rep, d)).reshape(
        b, t, num_q_heads, d
    )


def xla_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
) -> jax.Array:
    """Reference jnp attention: [B, T, H, D] -> [B, T, H, D].

    Scores/softmax in float32 regardless of input dtype; output in q.dtype.
    """
    b, tq, h, d = q.shape
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    tk = k.shape[1]
    scale = d**-0.5
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    if causal:
        q_pos = jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        k_pos = jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        # when tq < tk (e.g. decode), align the query block to the suffix
        mask = q_pos + (tk - tq) >= k_pos
        scores = jnp.where(mask[None, None], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lens: jax.Array,
    chosen: jax.Array | None = None,
) -> jax.Array:
    """Single-token decode attention over a slot-paged ring KV cache.

    q [S, H, D] is the current token per slot; k/v are one layer's cache
    pages as ``ring_cache`` stores them ([S, Kh, D, T], read here as rows
    through the module: a copy, this is the reference and the path off the
    TPU); lens [S] int32 is each slot's token count BEFORE this
    step (== the current token's absolute position; its K/V has already
    been written at ring index ``lens % T``). Valid cache entries are
    indices <= lens until the sequence outgrows the page, after which the
    whole ring is live (sliding-window attention over the last T tokens).
    The same mask covers tier-restored slots: a page-in rewrites exactly
    ``ring_cache.ring_live_rows`` rows at row 0, so validity is still fully
    determined by ``lens``.

    ``chosen`` [S, T] bool (learned sparse attention): of the valid entries
    only these enter the softmax.

    Math matches :func:`xla_attention` row-for-row — f32 scores/softmax,
    probabilities cast back to q.dtype — so incremental decode reproduces
    the training-mode forward (pinned by tests/test_serve.py).
    """
    k, v = rows_first(k), rows_first(v)  # [S, T, Kh, D]
    s, t, nkv, d = k.shape
    h = q.shape[1]
    k = _repeat_kv(k, h)
    v = _repeat_kv(v, h)
    scale = d**-0.5
    scores = jnp.einsum("shd,sthd->sht", q, k, preferred_element_type=jnp.float32)
    scores = scores * scale
    idx = jax.lax.broadcasted_iota(jnp.int32, (s, t), 1)
    valid = (idx <= lens[:, None]) | (lens[:, None] >= t)
    if chosen is not None:
        valid = valid & chosen
    scores = jnp.where(valid[:, None, :], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("sht,sthd->shd", probs, v)


def decode_step_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    lens: jax.Array,
    layer,
    *,
    window: int = 0,
    live_only: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One layer's share of a decode step in XLA: the step's rows (k, v [S,
    Kh, D]) written at ring row ``lens % T`` of ``layer``'s pages, then
    :func:`decode_attention` over them -> (out, cache_k, cache_v). Under
    ``window`` the ring wraps and a slot reads the rows of its last ``window``
    positions (:func:`ring_window_rows`); with ``live_only`` a slot at ``lens``
    0 is written nothing (``ring_cache.write_live_row``: it may be a slot whose
    prompt is arriving in chunks). The
    reference of ``decode_kernels.paged_decode_attention``, which has this
    signature, and its per-call fallback."""
    write = write_live_row if live_only else write_row
    cache_k, cache_v = write(cache_k, cache_v, layer, k, v, lens)
    chosen = ring_window_rows(lens, ring_rows(cache_k), window) if window else None
    out = decode_attention(q, *layer_pages(cache_k, cache_v, layer), lens, chosen)
    return out, cache_k, cache_v


def latent_decode_step_attention(
    q: jax.Array,
    row: jax.Array,
    cache: jax.Array,
    lens: jax.Array,
    layer,
    *,
    scale: float,
    value_dim: int,
    chosen: jax.Array | None = None,
    window: int = 0,
    live_only: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One layer's share of a decode step of latent attention, absorbed
    form, in XLA: each slot's new latent row [S, Dl] written at ring row
    ``lens % T`` of ``layer``'s pages of the one latent ring ``cache`` [L, S,
    1, Dl, T], then every head's absorbed query q [S, H, Dl]
    (``llama.latent_absorb``) against the slot's live rows -- the scores over
    all Dl values of a row, times ``scale``, the weighted sum over its first
    ``value_dim`` (the normed latent; the rest is the shared rotated key)
    -> (o_lat [S, H, value_dim], cache). Masks as :func:`decode_attention`;
    under ``chosen`` [S, T] bool (an indexer's selection) only those of the
    live rows enter the softmax, and under ``window`` the ring wraps and a slot
    reads the rows of its last ``window`` positions (:func:`ring_window_rows`);
    with ``live_only`` a slot at ``lens`` 0 is written nothing
    (``ring_cache.write_live_row``: it may be a slot whose prompt is arriving
    in chunks). The reference of
    ``decode_kernels.mla_decode_attention``, which has this signature, and its
    per-call fallback."""
    write = write_live_row if live_only else write_row
    cache, _ = write(cache, None, layer, row[:, None], None, lens)
    pages = cache[layer][:, 0]  # [S, Dl, T]
    s, _, t = pages.shape
    scores = jnp.einsum("shd,sdt->sht", q, pages, preferred_element_type=jnp.float32)
    scores = scores * scale
    idx = jax.lax.broadcasted_iota(jnp.int32, (s, t), 1)
    if window:
        valid = ring_window_rows(lens, t, window)
    else:
        valid = (idx <= lens[:, None]) | (lens[:, None] >= t)
    if chosen is not None:
        valid = valid & chosen
    scores = jnp.where(valid[:, None, :], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("sht,sdt->shd", probs, pages[:, :value_dim]), cache


def ring_window_rows(at: jax.Array, t: int, window: int) -> jax.Array:
    """Which rows of a ring of ``t`` rows that wraps (position p at row p % t)
    a query at position ``at`` [N] reads under a window: the rows of positions
    s with 0 <= at - s < ``window``, s >= 0 -> bool [N, t]. The ring holds
    positions up to ``at``: row r holds the newest position <= ``at`` that is
    r modulo t, ``at - ((at - r) mod t)``."""
    at = at.astype(jnp.int32)[:, None]
    back = jnp.mod(at - jax.lax.broadcasted_iota(jnp.int32, (at.shape[0], t), 1), t)
    return back < jnp.minimum(window, at + 1)


def window_attention(q: jax.Array, k: jax.Array, v: jax.Array, window: int) -> jax.Array:
    """Causal attention under a window over a whole sequence from position 0:
    q [B, T, H, D], k [B, T, H, D], v [B, T, H, Dv]; query t reads rows s with
    0 <= t - s < ``window`` -> [B, T, H, Dv]. Scores and softmax in float32 as
    :func:`xla_attention`'s."""
    t, d = q.shape[1], q.shape[-1]
    k, v = _repeat_kv(k, q.shape[2]), _repeat_kv(v, q.shape[2])  # grouped-query: Kh < H
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * d**-0.5
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None]
    scores = jnp.where((back >= 0) & (back < window), scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def band_block(chunk: int, ring: int, window: int, block: int = 512) -> int:
    """The query block :func:`banded_chunk_attention` cuts a chunk of ``chunk``
    queries into over a ring of ``ring`` rows: ``block`` where it divides the
    chunk, else the chunk whole; 0 where that cuts neither the ring evenly nor
    leaves it room for a chunk beside the blocks before it that a window reaches
    (the caller then keeps the tiled form under a mask)."""
    b = block if chunk % block == 0 else chunk
    before = -(-(window - 1) // b) * b
    return b if ring % b == 0 and chunk + before <= ring else 0


def banded_chunk_attention(
    q: jax.Array, pages_k: jax.Array, pages_v: jax.Array, plen, window: int, block: int,
) -> jax.Array:
    """A prefill chunk's attention under a window over one slot's pages of a
    ring that wraps, visiting the band alone: q [C, H, D] at positions ``plen +
    i`` (``plen`` traced, a multiple of ``block``), pages_k and pages_v [Kh, D,
    T] (one layer's pages of the slot, position p at row p % T, the chunk's own
    rows in them), query t reading rows s with 0 <= t - s < ``window`` -> [C,
    H, D]. The chunk goes ``block`` queries at a time (:func:`band_block`), and
    a block reads its own ``block`` rows and the ceil((window - 1) / block)
    blocks of rows before them, cut from the ring as whole aligned tiles (the
    ring is whole blocks), under one softmax: the scores held at once are one
    block's [H, block, rows read], never [H, C, T], and no tile outside the
    band is touched. Scores and softmax in float32 as :func:`xla_attention`'s;
    a query head reads its KV head's rows in place."""
    c, h, d = q.shape
    kh, _, t = pages_k.shape
    f32 = jnp.float32
    before = -(-(window - 1) // block)  # blocks of rows before a block's own
    span, tiles = (before + 1) * block, t // block
    qb = jnp.moveaxis(q.reshape(c // block, block, kh, h // kh, d), 1, 3)  # [.., Kh, rep, block, D]
    first = jnp.asarray(plen, jnp.int32) // block  # the chunk's first block, in blocks of positions
    # t - s for query row r and key column c of a block's span: static
    back = before * block + jnp.arange(block)[:, None] - jnp.arange(span)[None]
    band = (back >= 0) & (back < window)

    def one(xs):
        j, qj = xs  # [Kh, rep, block, D]
        at = first + j - before  # the span's first block of positions (may lie before 0)
        cut = lambda pages: jnp.concatenate([
            jax.lax.dynamic_slice_in_dim(pages, jnp.mod(at + m, tiles) * block, block, 2)
            for m in range(before + 1)], axis=2)  # [Kh, D, span]
        kt, vt = cut(pages_k), cut(pages_v)
        s = jnp.einsum("grqd,gdk->grqk", qj, kt, preferred_element_type=f32) * d**-0.5
        seen = band & ((at * block + jnp.arange(span)) >= 0)[None]
        s = jnp.where(seen, s, jnp.finfo(f32).min)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("grqk,gdk->grqd", p, vt, preferred_element_type=f32).astype(q.dtype)

    out = jax.lax.map(one, (jnp.arange(c // block, dtype=jnp.int32), qb))  # [.., Kh, rep, block, D]
    return jnp.moveaxis(out, 3, 1).reshape(c, h, d)


def tiled_latent_attention(
    q: jax.Array, page: jax.Array, reads: jax.Array, live_rows, tile: int,
    *, scale: float, value_dim: int,
) -> jax.Array:
    """A prefill chunk's latent attention in the absorbed form over one slot's
    page of latent rows, ``tile`` rows at a time under an online softmax: q [C,
    H, Dl] (``llama.latent_absorb``), ``page`` [Dl, T] (one layer's page of
    the slot, the chunk's own rows in it), ``reads`` [C, T] bool, the rows each
    query reads (an indexer's selection, or a window's rows of a ring that
    wraps), one set a query for all its heads -> o_lat [C, H, value_dim], the
    weighted sum over a row's first ``value_dim`` values. No key or value is
    rebuilt and no [C, T] block of scores a head is held; tiles from
    ``live_rows`` (traced) on hold nothing read and are not visited. A query
    that reads no row (a bucket's padding) comes out zero. The reference of
    ``decode_kernels.latent_chunk_attention``, which keeps the score tile in
    VMEM, and the form off the TPU, over a sliding layer's ring and under
    ``decode_kernels.latent_chunk_form``'s line."""
    c, h, dl = q.shape
    t = page.shape[-1]
    f32, neg = jnp.float32, jnp.finfo(jnp.float32).min

    def visit(i, carry):
        m, l, acc = carry
        at = i * tile
        rows = jax.lax.dynamic_slice_in_dim(page, at, tile, 1)  # [Dl, tile]
        rt = jax.lax.dynamic_slice_in_dim(reads, at, tile, 1)[:, None]  # [C, 1, tile]
        s = jnp.einsum("chd,dt->cht", q, rows, preferred_element_type=f32) * scale
        s = jnp.where(rt, s, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(rt, jnp.exp(s - m_new[..., None]), 0.0)
        keep = jnp.exp(m - m_new)
        l = l * keep + jnp.sum(p, axis=-1)
        acc = acc * keep[..., None] + jnp.einsum(
            "cht,dt->chd", p.astype(q.dtype), rows[:value_dim], preferred_element_type=f32
        )
        return m_new, l, acc

    init = (jnp.full((c, h), neg, f32), jnp.zeros((c, h), f32), jnp.zeros((c, h, value_dim), f32))
    tiles = (jnp.asarray(live_rows, jnp.int32) + tile - 1) // tile
    _, l, acc = jax.lax.fori_loop(0, jnp.minimum(tiles, t // tile), visit, init)
    return (acc / jnp.where(l > 0, l, 1.0)[..., None]).astype(q.dtype)


# ---------------------------------------------------------------------------
# EVA attention (arXiv 2302.04542, in the form EvaByte publishes): a query
# reads the rows of its own window exactly and, under the same softmax, one
# pooled key and value per chunk of every earlier window. The plain forms:
# training and prefill over a whole sequence, a decode step over a slot's two
# rings (``ring_cache``), and the pooling both share. The decode kernels'
# form is ``decode_kernels.eva_decode_attention``.
# ---------------------------------------------------------------------------

_MASKED = -1e30  # finite: a chunk with no live position keeps m - m = 0


def eva_pool(
    k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array, chunk: int, length=None
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Keys and values [B, T, Kh, D] pooled by chunks of ``chunk`` positions
    -> (kbar, vbar [B, J, Kh, D] in their dtypes, J = ceil(T / chunk); stats
    [B, J, Kh, 2 D + 2] float32).

    Chunk j's positions m weigh a_m = softmax_m(phi_h . k_m) (no further
    scale); kbar_j = sum_m a_m k_m + mu_h, vbar_j = sum_m a_m v_m, the
    weighted sums in float32 on the vector unit (sixteen rows a chunk, no
    matmul rounds a weight). Positions from ``length`` (traced; None: T) on are no part of any
    chunk: a bucket's padding, or the rows this call pads T up to whole
    chunks with. ``stats`` is each chunk's pooling unnormalised, as an online
    softmax holds it: sum_m e_m k_m, sum_m e_m v_m with e_m = exp(s_m - max),
    then the max and sum_m e_m; a decode step continues the chunk a prompt
    ends in from there (:func:`eva_accumulate`). A chunk with no live
    position pools to (mu, 0)."""
    b, t, kh, d = k.shape
    f32 = jnp.float32
    pad = -t % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    j = (t + pad) // chunk
    # phi . k on the MXU: the heads' keys side by side against phi laid
    # block-diagonally ([Kh D, Kh]: head h's vector in rows h D .. of column
    # h), accumulated in float32: exact products for operands the compute
    # dtype holds, and no reduction over a head's 128 lanes on the vector unit
    # (which took a fifth of a 4,096 prefill on the chip)
    phi_bd = (jnp.eye(kh, dtype=phi.dtype)[:, None, :] * phi[:, :, None]).reshape(kh * d, kh)
    s = jnp.matmul(
        k.reshape(b, j * chunk, kh * d), phi_bd.astype(k.dtype), preferred_element_type=f32
    ).reshape(b, j, chunk, kh)
    kc = k.reshape(b, j, chunk, kh, d).astype(f32)
    vc = v.reshape(b, j, chunk, kh, d).astype(f32)
    pos = jnp.arange(j * chunk, dtype=jnp.int32).reshape(j, chunk)
    live = (pos < (t if length is None else length))[None, :, :, None]
    s = jnp.where(live, s, _MASKED)  # [B, J, c, Kh]
    m = jnp.max(s, axis=2)
    e = jnp.where(live, jnp.exp(s - m[:, :, None]), 0.0)
    l = jnp.sum(e, axis=2)  # [B, J, Kh]
    acc_k = jnp.sum(e[..., None] * kc, axis=2)
    acc_v = jnp.sum(e[..., None] * vc, axis=2)
    over = jnp.where(l > 0, l, 1.0)[..., None]
    stats = jnp.concatenate((acc_k, acc_v, m[..., None], l[..., None]), axis=-1)
    return (
        (acc_k / over + mu.astype(f32)).astype(k.dtype), (acc_v / over).astype(v.dtype), stats
    )


def eva_accumulate(
    stats: jax.Array, k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array,
    lens: jax.Array, chunk: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step of the pooling: each slot's new key and value (k, v [S,
    Kh, D]) at position ``lens`` [S] enter the chunk that position lies in,
    whose pooling so far is ``stats`` [S, Kh, 2 D + 2] (:func:`eva_pool`'s, by
    slot) -> (kbar, vbar [S, Kh, D]: the chunk pooled over its positions up to
    this one, the chunk's own pooled row once ``lens % chunk == chunk - 1``;
    the new stats). A position that starts a chunk starts from nothing,
    whatever ``stats`` holds."""
    d = k.shape[-1]
    f32 = jnp.float32
    kf, vf = k.astype(f32), v.astype(f32)
    fresh = (jnp.mod(lens, chunk) == 0)[:, None]
    stats = jnp.where(fresh[..., None], 0.0, stats)  # whatever it held is dropped
    m = jnp.where(fresh, _MASKED, stats[..., 2 * d])
    s = jnp.sum(kf * phi.astype(f32), axis=-1)  # [S, Kh]
    m_new = jnp.maximum(m, s)
    keep, p = jnp.exp(m - m_new), jnp.exp(s - m_new)
    l = stats[..., 2 * d + 1] * keep + p
    acc_k = stats[..., :d] * keep[..., None] + p[..., None] * kf
    acc_v = stats[..., d : 2 * d] * keep[..., None] + p[..., None] * vf
    stats = jnp.concatenate((acc_k, acc_v, m_new[..., None], l[..., None]), axis=-1)
    over = l[..., None]
    return (
        (acc_k / over + mu.astype(f32)).astype(k.dtype), (acc_v / over).astype(v.dtype), stats
    )


def eva_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, kbar: jax.Array, vbar: jax.Array,
    *, window: int, chunk: int,
) -> jax.Array:
    """EVA over a whole sequence (training, prefill): q [B, T, H, D], k and v
    [B, T, Kh, D] from position 0, kbar and vbar [B, J, Kh, D] their chunks
    pooled (:func:`eva_pool`) -> [B, T, H, D].

    The query at t, in window w = t // window, reads the keys m <= t of its
    own window and the pooled rows of the chunks of the windows before it, j <
    w * (window // chunk): none of its own window's chunks, complete or not,
    and no row of the window before. One softmax in float32 over both, the
    probabilities cast back to q's dtype as :func:`xla_attention` does. The
    scores a head holds at once are a window's block and the pooled rows
    before the last window, never [T, T]."""
    b, t, h, d = q.shape
    cpw = window // chunk
    pad = -t % window
    if pad:
        rows = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, rows), jnp.pad(k, rows), jnp.pad(v, rows)
    nw = (t + pad) // window
    qw = q.reshape(b, nw, window, h, d)
    kw = _repeat_kv(k, h).reshape(b, nw, window, h, d)
    vw = _repeat_kv(v, h).reshape(b, nw, window, h, d)
    scale = d**-0.5
    neg = jnp.finfo(jnp.float32).min
    scores = jnp.einsum("bwqhd,bwkhd->bhwqk", qw, kw, preferred_element_type=jnp.float32)
    q_pos = jax.lax.broadcasted_iota(jnp.int32, (window, window), 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (window, window), 1)
    scores = jnp.where(q_pos >= k_pos, scores * scale, neg)
    seen = (nw - 1) * cpw  # pooled rows that any query of the sequence reads
    if seen:
        kb, vb = _repeat_kv(kbar[:, :seen], h), _repeat_kv(vbar[:, :seen], h)
        pooled = jnp.einsum("bwqhd,bjhd->bhwqj", qw, kb, preferred_element_type=jnp.float32)
        w_of = jax.lax.broadcasted_iota(jnp.int32, (nw, 1, seen), 0)
        j_of = jax.lax.broadcasted_iota(jnp.int32, (nw, 1, seen), 2)
        pooled = jnp.where(j_of < w_of * cpw, pooled * scale, neg)
        scores = jnp.concatenate((scores, pooled), axis=-1)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhwqk,bwkhd->bwqhd", probs[..., :window], vw)
    if seen:
        out = out + jnp.einsum("bhwqj,bjhd->bwqhd", probs[..., window:], vb)
    return out.reshape(b, nw * window, h, d)[:, :t]


def eva_decode_step_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array,
    cache_k: jax.Array, cache_v: jax.Array, pool_k: jax.Array, pool_v: jax.Array,
    stats: jax.Array, lens: jax.Array, layer, *, window: int, chunk: int,
):
    """One layer's share of a decode step of EVA in XLA, over a slot's two
    rings (``ring_cache``): q [S, H, D] and the step's rows k, v [S, Kh, D] at
    position ``lens`` [S] -> (out [S, H, D], cache_k, cache_v, pool_k, pool_v,
    stats).

    The row is written at ring row ``lens % window`` of ``layer``'s pages and
    the rows [0, lens % window] are read: the ring restarts at a window's edge
    and does not slide. The row enters its chunk's pooling (``stats`` [L, S,
    Kh, 2 D + 2], :func:`eva_accumulate`) and the chunk as pooled so far is
    written at pooled row ``lens // chunk``: it is the chunk's own pooled row
    at the chunk's last position, and is not read before its window has
    ended. Of the pooled ring the first (lens // window) * (window // chunk)
    rows are read, under the same softmax. The reference of
    ``decode_kernels.eva_decode_attention``, which has this signature, and its
    per-call fallback."""
    cpw = window // chunk
    at = jnp.mod(lens, window)
    cache_k, cache_v = write_row(cache_k, cache_v, layer, k, v, at)
    kbar, vbar, new = eva_accumulate(stats[layer], k, v, phi, mu, lens, chunk)
    stats = jax.lax.dynamic_update_index_in_dim(stats, new, layer, 0)
    pool_k, pool_v = write_row(pool_k, pool_v, layer, kbar, vbar, lens // chunk)
    lk, lv = (rows_first(x) for x in layer_pages(cache_k, cache_v, layer))  # [S, T, Kh, D]
    pk, pv = (rows_first(x) for x in layer_pages(pool_k, pool_v, layer))  # [S, Tp, Kh, D]
    s, t = lk.shape[:2]
    tp, h, d = pk.shape[1], q.shape[1], q.shape[-1]
    neg = jnp.finfo(jnp.float32).min

    def scores_of(keys, valid):
        sc = jnp.einsum(
            "shd,sthd->sht", q, _repeat_kv(keys, h), preferred_element_type=jnp.float32
        )
        return jnp.where(valid[:, None, :], sc * d**-0.5, neg)

    local = jax.lax.broadcasted_iota(jnp.int32, (s, t), 1) <= at[:, None]
    before = jax.lax.broadcasted_iota(jnp.int32, (s, tp), 1) < (lens // window * cpw)[:, None]
    scores = jnp.concatenate((scores_of(lk, local), scores_of(pk, before)), axis=-1)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("sht,sthd->shd", probs[..., :t], _repeat_kv(lv, h))
    out = out + jnp.einsum("sht,sthd->shd", probs[..., t:], _repeat_kv(pv, h))
    return out, cache_k, cache_v, pool_k, pool_v, stats


# ---------------------------------------------------------------------------
# Learned sparse attention (DeepSeek sparse attention's lightning indexer, in
# the form Keye-VL-2.0 publishes under ``sa_config``): beside K and V a token
# keeps one index key; a query's ``index_n_heads`` index queries score every
# live row, I = sum_j w_j relu(q_j . k), the ``index_topk`` largest are the
# rows its attention reads, one set for all its heads. The plain forms:
# scoring, the exact selection, attention under the selection over a whole
# sequence (training, a whole-prompt prefill), over a slot's ring in row
# tiles (a prefill chunk) and a decode step's over a slot's rings
# (``ring_cache``: the K and V rings as every configuration's, rows minor-most;
# the index ring too, and never written inside a scan over the layers: the
# new keys enter the scores beside it). The decode kernel's form is
# ``decode_kernels.paged_decode_attention`` under its ``chosen`` operand.
# ---------------------------------------------------------------------------

# index queries scored in one product while its float32 result stays under
# this many bytes; beyond it head by head, one [.., Q, Tk] block at a time
_INDEX_AT_ONCE_BYTES = 64 * 1024 * 1024


def index_scores(qi: jax.Array, wi: jax.Array, keys_t: jax.Array) -> jax.Array:
    """The indexer's scores: index queries qi [B, Q, Hi, Di] under the
    queries' head weights wi [B, Q, Hi] against index keys ``keys_t`` [B, Di,
    Tk] (rows minor-most, as the index ring holds them) -> [B, Q, Tk]
    float32, I[q, s] = sum_j w[q, j] relu(qi[q, j] . k[s]). Products
    accumulated in float32, the ReLU and the weighted sum over heads in
    float32. The positive constants Di^-1/2 and Hi^-1/2 of the published form
    change no order and are left out."""
    f32 = jnp.float32
    b, q, hi, _ = qi.shape
    tk = keys_t.shape[-1]
    if b * q * hi * tk * 4 <= _INDEX_AT_ONCE_BYTES:
        # the queries' heads as rows of one product a batch entry
        s = jnp.einsum(
            "bmd,bdt->bmt", qi.reshape(b, q * hi, -1), keys_t, preferred_element_type=f32
        ).reshape(b, q, hi, tk)
        return jnp.sum(jax.nn.relu(s) * wi.astype(f32)[..., None], axis=2)

    def head(acc, xs):
        qj, wj = xs  # [B, Q, Di], [B, Q]
        s = jnp.einsum("bqd,bdt->bqt", qj, keys_t, preferred_element_type=f32)
        return acc + jax.nn.relu(s) * wj.astype(f32)[..., None], None

    acc, _ = jax.lax.scan(
        head, jnp.zeros((b, q, tk), f32), (jnp.moveaxis(qi, 2, 0), jnp.moveaxis(wi, 2, 0))
    )
    return acc


def _ordered_bits(x: jax.Array) -> jax.Array:
    """float32 -> uint32 whose unsigned order is the floats' order, -0.0 (a
    negative head weight times a ReLU's zero) taken for the +0.0 it equals."""
    x = x.astype(jnp.float32)
    i = jax.lax.bitcast_convert_type(jnp.where(x == 0, jnp.float32(0), x), jnp.int32)
    i = i ^ ((i >> 31) & jnp.int32(0x7FFFFFFF))
    return jax.lax.bitcast_convert_type(i, jnp.uint32) ^ jnp.uint32(0x80000000)


def select_rows(scores: jax.Array, valid: jax.Array, k: int) -> jax.Array:
    """The selection, exactly: of each query's ``valid`` rows (scores, valid
    [..., Tk]) the min(k, their number) of largest score, ties to the lower
    index -> bool [..., Tk]. No sort: the k-th largest score is found bit by
    bit (32 counts over the rows: the largest value that at least k rows
    reach), every row above it is chosen, and of the rows equal to it the
    first as many as are still missing (a running count over the rows, made
    only where some query has such a tie). With fewer than k valid rows every
    valid row is chosen."""
    u = jnp.where(valid, jnp.maximum(_ordered_bits(scores), jnp.uint32(1)), jnp.uint32(0))

    def bit(b, prefix):
        cand = prefix | (jnp.uint32(1) << jnp.asarray(31 - b, jnp.uint32))
        reach = jnp.sum((u >= cand[..., None]).astype(jnp.int32), axis=-1)
        return jnp.where(reach >= k, cand, prefix)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = u > kth[..., None]
    equal = (u == kth[..., None]) & valid
    missing = k - jnp.sum(above.astype(jnp.int32), axis=-1)

    def first_of_the_ties():  # more rows equal the k-th score than are missing
        first = jnp.cumsum(equal.astype(jnp.int32), axis=-1) <= missing[..., None]
        return above | (equal & first)

    tied = jnp.any(jnp.sum(equal.astype(jnp.int32), axis=-1) > missing)
    return jax.lax.cond(tied, first_of_the_ties, lambda: above | equal)


def sparse_attention(q: jax.Array, k: jax.Array, v: jax.Array, chosen: jax.Array) -> jax.Array:
    """Attention under a selection over a whole sequence: q [B, Tq, H, D], k
    and v [B, Tk, Kh, D], ``chosen`` [B, Tq, Tk] bool, one set of rows a query
    for all its heads -> [B, Tq, H, D]. Scores and softmax in float32 as
    :func:`xla_attention`'s; a query head reads its KV head's rows in place
    (no repeated K or V)."""
    b, tq, h, d = q.shape
    kh = k.shape[2]
    qg = q.reshape(b, tq, kh, h // kh, d)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k, preferred_element_type=jnp.float32)
    scores = jnp.where(chosen[:, None, None], scores * d**-0.5, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v).reshape(b, tq, h, v.shape[-1])


def causal_selection(qi, wi, ki, topk: int) -> jax.Array:
    """The rows each position of a whole sequence from position 0 reads: index
    queries qi [B, T, Hi, Di] and weights wi [B, T, Hi] over the sequence's
    own index keys ki [B, T, Di], s <= t -> bool [B, T, T]. A sequence no
    longer than ``topk`` keeps every causal row and scores nothing."""
    b, t = ki.shape[:2]
    causal = jnp.tril(jnp.ones((t, t), bool))[None]
    if t <= topk:
        return jnp.broadcast_to(causal, (b, t, t))
    return select_rows(index_scores(qi, wi, jnp.swapaxes(ki, 1, 2)), causal, topk)


def tiled_sparse_attention(
    q: jax.Array, pages_k: jax.Array, pages_v: jax.Array, chosen: jax.Array, live_rows, tile: int,
) -> jax.Array:
    """A prefill chunk's attention under its selection over one slot's pages,
    ``tile`` rows at a time under an online softmax, so that no [C, T] score
    block a head is ever held: q [C, H, D], pages_k and pages_v [Kh, D, T]
    (one layer's pages of the slot, the chunk's own rows in them), ``chosen``
    [C, T] bool -> [C, H, D]. Tiles from ``live_rows`` (traced) on hold nothing
    chosen and are not visited. A query with no chosen row (a bucket's
    padding) comes out zero. The reference of
    ``decode_kernels.chunk_attention``, which keeps the score tile in VMEM,
    and the form off the TPU and under ``decode_kernels.chunk_form``'s line."""
    c, h, d = q.shape
    kh, _, t = pages_k.shape
    f32, neg = jnp.float32, jnp.finfo(jnp.float32).min
    qg = jnp.moveaxis(q.reshape(c, kh, h // kh, d), 0, 2)  # [Kh, rep, C, D]

    def visit(i, carry):
        m, l, acc = carry
        at = i * tile
        kt = jax.lax.dynamic_slice_in_dim(pages_k, at, tile, 2)  # [Kh, D, tile]
        vt = jax.lax.dynamic_slice_in_dim(pages_v, at, tile, 2)
        ct = jax.lax.dynamic_slice_in_dim(chosen, at, tile, 1)[None, None]
        s = jnp.einsum("grcd,gdt->grct", qg, kt, preferred_element_type=f32) * d**-0.5
        s = jnp.where(ct, s, neg)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(ct, jnp.exp(s - m_new[..., None]), 0.0)
        keep = jnp.exp(m - m_new)
        l = l * keep + jnp.sum(p, axis=-1)
        acc = acc * keep[..., None] + jnp.einsum(
            "grct,gdt->grcd", p.astype(q.dtype), vt, preferred_element_type=f32
        )
        return m_new, l, acc

    shape = (kh, h // kh, c)
    init = (jnp.full(shape, neg, f32), jnp.zeros(shape, f32), jnp.zeros((*shape, d), f32))
    tiles = (jnp.asarray(live_rows, jnp.int32) + tile - 1) // tile
    _, l, acc = jax.lax.fori_loop(0, jnp.minimum(tiles, t // tile), visit, init)
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return jnp.moveaxis(out, 2, 0).reshape(c, h, d).astype(q.dtype)


def decode_selection(qi, wi, ki, keys_t, lens, topk: int) -> jax.Array:
    """The rows a decode step's queries read: each slot's index queries qi [S,
    Hi, Di] under their weights wi [S, Hi] over the slot's index rows
    ``keys_t`` [S, Di, T] **as they were before the step** and the step's own
    key ki [S, Di], which stands at ring row ``lens % T`` whatever the ring
    holds there (it is written behind the layers); min(lens + 1, T) rows are
    live -> bool [S, T], the ``topk`` largest, exactly."""
    s, t = keys_t.shape[0], keys_t.shape[-1]
    scores = index_scores(qi[:, None], wi[:, None], keys_t)[:, 0]
    # the one key a slot by products and a sum in float32: exact products, and
    # no matmul of a single column
    f32 = jnp.float32
    own = jnp.sum(qi.astype(f32) * ki.astype(f32)[:, None], axis=-1)  # [S, Hi]
    own = jnp.sum(jax.nn.relu(own) * wi.astype(f32), axis=-1)
    idx = jax.lax.broadcasted_iota(jnp.int32, (s, t), 1)
    scores = jnp.where(idx == jnp.mod(lens, t)[:, None], own[:, None], scores)
    live = (idx <= lens[:, None]) | (lens[:, None] >= t)
    return select_rows(scores, live, topk)


def chunk_selection(qi, wi, ki, keys_t, plen, topk: int) -> jax.Array:
    """The rows a prefill chunk's queries read: the chunk's index queries qi
    [C, Hi, Di] under their weights wi [C, Hi], at positions ``plen + i``, over
    the slot's index rows ``keys_t`` [Di, T] as they were before the chunk
    (rows [0, plen) are the prompt's) and the chunk's own keys ki [C, Di],
    which stand at rows [plen, plen + C) whatever the ring holds there; query
    i sees rows [0, plen + i] -> bool [C, T], the ``topk`` largest, exactly."""
    c, t = qi.shape[0], keys_t.shape[-1]
    scores = index_scores(qi[None], wi[None], keys_t[None])[0]
    own = index_scores(qi[None], wi[None], ki.T[None])[0]  # [C, C]
    scores = jax.lax.dynamic_update_slice(scores, own, (jnp.int32(0), jnp.asarray(plen, jnp.int32)))
    seen = jnp.arange(t)[None] <= (plen + jnp.arange(c))[:, None]
    return select_rows(scores, seen, topk)


def sparse_decode_step_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, chosen: jax.Array,
    cache_k: jax.Array, cache_v: jax.Array, lens: jax.Array, layer,
):
    """One layer's share of a decode step of learned sparse attention in XLA:
    the step's rows (k, v [S, Kh, D]) written at ring row ``lens % T`` of
    ``layer``'s pages (nothing for a slot at ``lens`` 0), then q [S, H, D] over
    the slot's rows under the selection ``chosen`` [S, T] (which the ``lens``
    masks already bound) -> (out, cache_k, cache_v). The reference of
    ``decode_kernels.paged_decode_attention`` under its ``chosen`` operand,
    which has this signature, and its per-call fallback."""
    cache_k, cache_v = write_live_row(cache_k, cache_v, layer, k, v, lens)
    out = decode_attention(q, *layer_pages(cache_k, cache_v, layer), lens, chosen)
    return out, cache_k, cache_v


# ---------------------------------------------------------------------------
# Attention under a selection by blocks (MiniCPM4's trainable sparse attention,
# arXiv 2506.07900, as a ``minicpm_sala`` stack's ``minicpm4`` layers run it):
# beside K and V a KV head keeps one *pooled key* per window of ``kernel`` rows
# every ``stride`` rows (their mean); a query scores the windows that have
# closed before it under a softmax per head, the heads of a KV group add up,
# a block of ``block`` rows takes the largest score of the windows that overlap
# it, and the query's attention reads ``topk`` blocks: the first
# ``init_blocks``, the ``window // block`` that end with its own, and the
# best-scored of the others, one choice for all the heads of a group. While a
# sequence holds fewer than ``dense_len`` rows every row is read. The plain
# forms: pooling, scores, the exact choice, attention under it over a whole
# sequence (training, a whole prompt), over a slot's ring in row tiles (a
# prompt's chunk; a tile no query chose is stepped over) and a decode step's
# over the chosen blocks alone, gathered (the XLA stand-in and reference of
# ``decode_kernels.block_decode_attention``).
# ---------------------------------------------------------------------------


# query heads of a KV group whose scores over the pooled keys are held at once
# (a chunk of 2,048 queries over 2,176 pooled keys: 71 MB of float32 a head group)
_SCORE_HEADS = 4


class BlockSizes(NamedTuple):
    """The sizes of a selection by blocks (``LlamaConfig.sparse_config``)."""

    kernel_size: int  # rows a pooled key is the mean of
    kernel_stride: int  # rows between two windows' starts
    block_size: int  # rows a block
    topk: int  # blocks a query reads, the forced ones among them
    init_blocks: int  # leading blocks every query reads
    window_size: int  # rows before a query, as whole blocks ending with its own, always read
    dense_len: int  # rows a sequence holds before the selection starts

    @property
    def pooled_a_block(self) -> int:
        return self.block_size // self.kernel_stride

    def pooled_rows(self, rows: int) -> int:
        """Rows of a pooled ring beside a ring of ``rows`` rows."""
        return rows // self.kernel_stride

    def blocks(self, rows: int) -> int:
        return -(-rows // self.block_size)

    def gathered(self, rows: int) -> int:
        """Blocks a decode step's gather holds: ``topk``, or a sequence's
        blocks while it reads every row (under ``dense_len``)."""
        return min(self.blocks(rows), max(self.topk, self.blocks(self.dense_len)))


def pool_pages(pages: jax.Array, sizes: BlockSizes, first=0, windows: int | None = None) -> jax.Array:
    """Rows-minor keys [Kh, D, T] -> the pooled keys of ``windows`` windows
    from the one that starts at row ``first`` (traced) on (None: every window
    that lies within the T rows from row 0) [Kh, D, windows] float32: window i
    the mean of rows [first + stride i, first + stride i + kernel). One product
    with the windows' 0 / 1 matrix: the rows stay on the lanes, as a ring
    holds them."""
    stride, kernel = sizes.kernel_stride, sizes.kernel_size
    if windows is None:
        windows = max((pages.shape[-1] - kernel) // stride + 1, 0)
    row = jnp.arange(pages.shape[-1])[:, None]
    start = first + stride * jnp.arange(windows)[None]
    inside = ((row >= start) & (row < start + kernel)).astype(pages.dtype)
    return jnp.einsum("gdt,tw->gdw", pages, inside, preferred_element_type=jnp.float32) / kernel


def block_scores(q: jax.Array, pooled_t: jax.Array, at: jax.Array, sizes: BlockSizes) -> jax.Array:
    """Queries q [C, H, D] at positions ``at`` [C] over a KV head's pooled keys
    ``pooled_t`` [Kh, D, J] (rows minor-most, as the pooled ring holds them;
    window j at row j) -> the groups' scores [Kh, C, J] float32: per head a
    softmax over the windows the query sees (``stride j + kernel - 1 <= at``),
    added up over the heads of a group; zeros where it sees none."""
    c, h, d = q.shape
    kh, _, j = pooled_t.shape
    f32 = jnp.float32
    qg = jnp.moveaxis(q.reshape(c, kh, h // kh, d), 0, 2)  # [Kh, rep, C, D]
    closes = sizes.kernel_stride * jnp.arange(j) + sizes.kernel_size - 1
    seen = closes[None] <= at[:, None]  # [C, J]

    hb = math.gcd(h // kh, _SCORE_HEADS)

    def group(xs):
        qh, keys = xs  # [rep, C, D], [D, J]

        def heads(total, qs):  # a few heads' softmaxes at a time, added up
            s = jnp.einsum("rcd,dj->rcj", qs, keys, preferred_element_type=f32) * d**-0.5
            s = jnp.where(seen, s, jnp.finfo(f32).min)
            p = jnp.where(seen, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
            norm = jnp.sum(p, axis=-1, keepdims=True)
            return total + jnp.sum(p / jnp.where(norm > 0, norm, 1.0), axis=0), None

        return jax.lax.scan(heads, jnp.zeros((c, j), f32), qh.reshape(-1, hb, c, d))[0]

    return jax.lax.map(group, (qg, pooled_t))


def block_maxima(scores: jax.Array, sizes: BlockSizes, blocks: int) -> jax.Array:
    """Window scores [..., J] -> block scores [..., blocks]: a block takes the
    largest score of the windows that overlap it, ``j stride < (b + 1) block``
    and ``j stride + kernel > b block``."""
    r = sizes.pooled_a_block
    e = -(-(sizes.kernel_size - sizes.kernel_stride) // sizes.kernel_stride)  # windows that start before it
    j = scores.shape[-1]
    lead = [(0, 0)] * (scores.ndim - 1)
    padded = jnp.pad(scores, (*lead, (e, max(0, r * blocks + e - (j + e)))))
    return functools.reduce(
        jnp.maximum,
        [padded[..., i : i + r * blocks : r] for i in range(r + e)],
    )


def choose_blocks(scores: jax.Array, at: jax.Array, dense, sizes: BlockSizes) -> jax.Array:
    """Block scores [Kh, C, blocks] of queries at positions ``at`` [C] -> the
    blocks each reads, bool [Kh, C, blocks]: the first ``init_blocks``, the
    ``window_size // block_size`` that end with the query's own, and by
    largest score (ties to the lower block) as many of the others up to its
    own as make ``topk`` in all; every block up to its own where those are
    fewer, and for a query that ``dense`` ([C] bool) says reads every row."""
    b = jnp.arange(scores.shape[-1])
    own = (at // sizes.block_size)[:, None]
    causal = b[None] <= own  # [C, blocks]
    forced = (b[None] < sizes.init_blocks) | (b[None] > own - sizes.window_size // sizes.block_size)
    ranked = jnp.where(forced[None], jnp.float32(1e30), scores)
    chosen = select_rows(ranked, jnp.broadcast_to(causal[None], scores.shape), sizes.topk)
    return jnp.where(jnp.asarray(dense)[None, :, None], causal[None], chosen)


def block_selection(q, pooled_t, at, dense, sizes: BlockSizes, blocks: int) -> jax.Array:
    """:func:`block_scores`, :func:`block_maxima` and :func:`choose_blocks` in
    one: q [C, H, D] at ``at`` [C] over pooled_t [Kh, D, J] -> bool [Kh, C,
    blocks]."""
    scores = block_maxima(block_scores(q, pooled_t, at, sizes), sizes, blocks)
    return choose_blocks(scores, at, dense, sizes)


def causal_block_selection(q: jax.Array, k: jax.Array, sizes: BlockSizes, dense) -> jax.Array:
    """The blocks each position of whole sequences from position 0 reads: q
    [B, T, H, D] over the sequence's own keys k [B, T, Kh, D] -> bool [B, Kh,
    T, blocks]; ``dense`` [T] bool: the positions that read every row (the
    caller keeps ``dense_len``)."""
    t = k.shape[1]
    at = jnp.arange(t)

    def one(qb, kb):
        pooled = pool_pages(jnp.moveaxis(kb, 0, -1), sizes).astype(kb.dtype)  # [Kh, D, J]
        return block_selection(qb, pooled, at, dense, sizes, sizes.blocks(t))

    return jax.vmap(one)(q, k)


def block_sparse_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, chosen: jax.Array, block: int
) -> jax.Array:
    """Attention under a selection by blocks over whole sequences: q [B, T, H,
    D], k and v [B, T, Kh, D], ``chosen`` [B, Kh, T, blocks] bool, one choice
    for the heads of a group: a query reads the rows up to its own of its
    chosen blocks -> [B, T, H, D]. Scores and softmax in float32."""
    b, t, h, d = q.shape
    kh = k.shape[2]
    rows = jnp.repeat(chosen, block, axis=-1)[..., :t] & jnp.tril(jnp.ones((t, t), bool))
    qg = q.reshape(b, t, kh, h // kh, d)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k, preferred_element_type=jnp.float32)
    scores = jnp.where(rows[:, :, None], scores * d**-0.5, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v).reshape(b, t, h, v.shape[-1])


def tiled_block_attention(
    q: jax.Array, pages_k: jax.Array, pages_v: jax.Array, chosen: jax.Array, at: jax.Array,
    live_rows, tile: int, block: int,
):
    """A prefill chunk's attention under its selection by blocks over one
    slot's pages, ``tile`` rows at a time under an online softmax: q [C, H, D]
    at positions ``at`` [C], pages_k and pages_v [Kh, D, T] (the chunk's own
    rows in them), ``chosen`` [Kh, C, blocks] bool -> (out [C, H, D], the tiles
    visited). Tiles from ``live_rows`` (traced) on are not visited, and of the
    others one in which no query of the chunk chose a block is stepped over.
    The reference of ``decode_kernels.chunk_attention`` under its ``chosen``
    operand, and the form off the TPU."""
    c, h, d = q.shape
    kh, _, t = pages_k.shape
    f32, neg = jnp.float32, jnp.finfo(jnp.float32).min
    qg = jnp.moveaxis(q.reshape(c, kh, h // kh, d), 0, 2)  # [Kh, rep, C, D]
    per = tile // block

    def visit(i, carry):
        m, l, acc, visited = carry
        first = i * tile
        cb = jax.lax.dynamic_slice_in_dim(chosen, i * per, per, 2)  # [Kh, C, per]

        def attend(_):
            kt = jax.lax.dynamic_slice_in_dim(pages_k, first, tile, 2)  # [Kh, D, tile]
            vt = jax.lax.dynamic_slice_in_dim(pages_v, first, tile, 2)
            ct = jnp.repeat(cb, block, axis=-1) & (first + jnp.arange(tile)[None] <= at[:, None])
            ct = ct[:, None]  # [Kh, 1, C, tile]
            s = jnp.einsum("grcd,gdt->grct", qg, kt, preferred_element_type=f32) * d**-0.5
            s = jnp.where(ct, s, neg)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.where(ct, jnp.exp(s - m_new[..., None]), 0.0)
            keep = jnp.exp(m - m_new)
            return m_new, l * keep + jnp.sum(p, axis=-1), acc * keep[..., None] + jnp.einsum(
                "grct,gdt->grcd", p.astype(q.dtype), vt, preferred_element_type=f32
            ), visited + 1

        return jax.lax.cond(jnp.any(cb), attend, lambda _: carry, None)

    shape = (kh, h // kh, c)
    init = (jnp.full(shape, neg, f32), jnp.zeros(shape, f32), jnp.zeros((*shape, d), f32),
            jnp.int32(0))
    tiles = (jnp.asarray(live_rows, jnp.int32) + tile - 1) // tile
    _, l, acc, visited = jax.lax.fori_loop(0, jnp.minimum(tiles, t // tile), visit, init)
    out = acc / jnp.where(l > 0, l, 1.0)[..., None]
    return jnp.moveaxis(out, 2, 0).reshape(c, h, d).astype(q.dtype), visited


def ring_rows_sum(cache_k: jax.Array, layer, first: jax.Array, count: int) -> jax.Array:
    """The sum over ring rows [first, first + count) of ``layer``'s pages, a
    slot each (``first`` [S], within the ring) -> [S, Kh, D] float32, in XLA (a
    gather of the slots' rows): the reference of
    ``decode_kernels.ring_rows_sum`` and the path off the TPU (on the chip the
    gather makes the compiler re-lay the whole ring rows major-most)."""
    zero, li = jnp.int32(0), jnp.asarray(layer, jnp.int32)

    def rows(slot, start):  # [Kh, D, count] of one slot's page, where it lies
        shape = (1, 1, *cache_k.shape[2:4], count)
        return jax.lax.dynamic_slice(cache_k, (li, slot, zero, zero, start), shape)[0, 0]

    past = jax.vmap(rows)(jnp.arange(cache_k.shape[1], dtype=jnp.int32), first)
    return jnp.sum(past.astype(jnp.float32), axis=-1)


def closing_pooled_key(
    cache_k: jax.Array, layer, k: jax.Array, lens: jax.Array, sizes: BlockSizes, rows_sum=ring_rows_sum,
):
    """The pooled key a decode step closes, where it closes one: each slot's
    step's key k [S, Kh, D] at position ``lens`` [S] ends the window of rows
    [lens - kernel + 1, lens] when ``lens + 1`` is a multiple of the stride
    past a first whole window -> (the window's mean [S, Kh, D] float32 from
    ``layer``'s ring rows before ``lens`` (``rows_sum``: :func:`ring_rows_sum`
    or the kernel of its name) and the step's own key, which the ring need not
    hold yet; the window j it is; whether the slot closes one)."""
    kernel, stride = sizes.kernel_size, sizes.kernel_stride
    closes = (lens >= kernel - 1) & (jnp.mod(lens + 1, stride) == 0)
    first = jnp.clip(lens - (kernel - 1), 0, ring_rows(cache_k) - kernel)
    total = rows_sum(cache_k, layer, first, kernel - 1) + k.astype(jnp.float32)
    return total / kernel, (lens - (kernel - 1)) // stride, closes


def merge_own_row(out, m, l, q, k, v):
    """A decode step's attention over the ring's rows *before* the step's own
    (``out`` [S, H, D] float32 under the softmax's maximum ``m`` and sum ``l``
    [S, H]; ``l`` 0 where no row was read) merged with the step's own row (k, v
    [S, Kh, D]) under the one softmax -> [S, H, D] in q's dtype."""
    s_, h, d = q.shape
    kh = k.shape[1]
    f32 = jnp.float32
    qg = q.reshape(s_, kh, h // kh, d).astype(f32)
    own = (jnp.sum(qg * k.astype(f32)[:, :, None], axis=-1) * d**-0.5).reshape(s_, h)
    top = jnp.maximum(m, own)
    w_ring, w_own = l * jnp.exp(m - top), jnp.exp(own - top)
    vg = jnp.repeat(v.astype(f32), h // kh, axis=1)  # [S, H, D]
    merged = (out * w_ring[..., None] + vg * w_own[..., None]) / (w_ring + w_own)[..., None]
    return merged.astype(q.dtype)


def block_decode_step_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, chosen: jax.Array,
    cache_k: jax.Array, cache_v: jax.Array, lens: jax.Array, layer, sizes: BlockSizes,
):
    """One layer's share of a decode step under a selection by blocks, in XLA:
    q [S, H, D] over the rows before ``lens`` of the blocks ``chosen`` [S, Kh,
    blocks] of ``layer``'s pages, gathered (``sizes.gathered`` blocks a slot and
    KV head), and the step's own row (k, v [S, Kh, D]: the rings are written
    behind the layers and need not hold it) -> out [S, H, D]. The reference of
    ``decode_kernels.block_decode_attention`` and the path off the TPU."""
    s_, h, d = q.shape
    kh, t = k.shape[1], ring_rows(cache_k)
    bs, nb = sizes.block_size, chosen.shape[-1]
    n = min(nb, sizes.gathered(t))
    f32, neg = jnp.float32, jnp.finfo(jnp.float32).min
    idx = jnp.sort(jnp.where(chosen, jnp.arange(nb), nb), axis=-1)[..., :n]  # [S, Kh, n]
    held = idx < nb
    idx = jnp.minimum(idx, nb - 1)

    def gather(cache):  # [S, Kh, D, n * bs]
        pages = cache[layer][..., : nb * bs] if t >= nb * bs else jnp.pad(
            cache[layer], ((0, 0),) * 3 + ((0, nb * bs - t),))
        pages = pages.reshape(s_, kh, d, nb, bs)
        got = jnp.take_along_axis(pages, idx[:, :, None, :, None], axis=3)
        return got.reshape(s_, kh, d, n * bs)

    row = (idx[..., None] * bs + jnp.arange(bs)).reshape(s_, kh, n * bs)
    ok = jnp.repeat(held, bs, axis=-1) & (row < lens[:, None, None])
    qg = q.reshape(s_, kh, h // kh, d)
    s = jnp.einsum("sgrd,sgdt->sgrt", qg, gather(cache_k), preferred_element_type=f32) * d**-0.5
    s = jnp.where(ok[:, :, None], s, neg)
    m = jnp.max(s, axis=-1)
    p = jnp.where(ok[:, :, None], jnp.exp(s - m[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("sgrt,sgdt->sgrd", p.astype(q.dtype), gather(cache_v), preferred_element_type=f32)
    out = out / jnp.where(l > 0, l, 1.0)[..., None]
    m = jnp.where(l > 0, m, NEG_INF)
    return merge_own_row(
        out.reshape(s_, h, d), m.reshape(s_, h), l.reshape(s_, h), q, k, v
    )
