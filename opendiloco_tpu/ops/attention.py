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

import jax
import jax.numpy as jnp

from opendiloco_tpu.models.ring_cache import layer_pages, rows_first, write_row


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
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One layer's share of a decode step in XLA: the step's rows (k, v [S,
    Kh, D]) written at ring row ``lens % T`` of ``layer``'s pages, then
    :func:`decode_attention` over them -> (out, cache_k, cache_v). The
    reference of ``decode_kernels.paged_decode_attention``, which has this
    signature, and its per-call fallback."""
    cache_k, cache_v = write_row(cache_k, cache_v, layer, k, v, lens)
    out = decode_attention(q, *layer_pages(cache_k, cache_v, layer), lens)
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
) -> tuple[jax.Array, jax.Array]:
    """One layer's share of a decode step of latent attention, absorbed
    form, in XLA: each slot's new latent row [S, Dl] written at ring row
    ``lens % T`` of ``layer``'s pages of the one latent ring ``cache`` [L, S,
    1, Dl, T], then every head's absorbed query q [S, H, Dl]
    (``llama.latent_absorb``) against the slot's live rows -- the scores over
    all Dl values of a row, times ``scale``, the weighted sum over its first
    ``value_dim`` (the normed latent; the rest is the shared rotated key)
    -> (o_lat [S, H, value_dim], cache). Masks as :func:`decode_attention`.
    The reference of ``decode_kernels.mla_decode_attention``, which has this
    signature, and its per-call fallback."""
    cache, _ = write_row(cache, None, layer, row[:, None], None, lens)
    pages = cache[layer][:, 0]  # [S, Dl, T]
    s, _, t = pages.shape
    scores = jnp.einsum("shd,sdt->sht", q, pages, preferred_element_type=jnp.float32)
    scores = scores * scale
    idx = jax.lax.broadcasted_iota(jnp.int32, (s, t), 1)
    valid = (idx <= lens[:, None]) | (lens[:, None] >= t)
    scores = jnp.where(valid[:, None, :], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("sht,sdt->shd", probs, pages[:, :value_dim]), cache


def tail_attention(
    q: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    tail_k: jax.Array,
    tail_v: jax.Array,
    lens: jax.Array,
) -> jax.Array:
    """Multi-token tail attention over a ring KV cache plus in-register
    tail K/V — the continued prefill's attention (prefix reuse).

    q [S, K, H, D] are the tail's tokens per slot at absolute positions
    ``lens + i``; cache_{k,v} hold one layer's ring pages ([S, Kh, D, T],
    ``ring_cache``'s order, read as rows through the module) as of BEFORE
    the tail (positions <= lens - 1); tail_{k,v} [S, K, Kh, D] are the
    tail's own K/V, kept out of the ring until the caller inserts them.

    The masking reproduces the sequential one-token loop exactly,
    including ring wrap: tail query i attends tail tokens <= i plus the
    ring entries the sequential path would still hold at its step — a
    ring slot is dropped for query i when the write of tail token j <= i
    would have overwritten it (that is, when ``(lens + j) % T`` lands on
    it with ``lens + j >= T``), which is precisely the sliding-window
    eviction the per-step ring write performs. Softmax terms for masked
    entries are exact zeros, so extra masked slots never perturb the
    live reductions (same invariant the prefill bucket-padding relies
    on).
    """
    cache_k, cache_v = rows_first(cache_k), rows_first(cache_v)
    s, t, nkv, d = cache_k.shape
    kq = q.shape[1]
    kt = tail_k.shape[1]
    h = q.shape[2]
    ck = _repeat_kv(cache_k, h)
    cv = _repeat_kv(cache_v, h)
    tk = _repeat_kv(tail_k, h)
    tv = _repeat_kv(tail_v, h)
    scale = d**-0.5

    # ring scores [S, H, Kq, T]
    ring_scores = jnp.einsum(
        "sqhd,sthd->shqt", q, ck, preferred_element_type=jnp.float32
    ) * scale
    idx = jax.lax.broadcasted_iota(jnp.int32, (s, t), 1)
    lens_ = lens[:, None].astype(jnp.int32)
    base = (idx < lens_) | (lens_ >= t)  # live pre-tail entries
    # disp = the i whose tail ring write lands on this slot ((lens+i) % T)
    disp = jnp.mod(idx - lens_, t)
    j = jnp.arange(kq, dtype=jnp.int32)[None, :, None]  # [1, Kq, 1]
    evicted = (disp[:, None, :] <= j) & (
        (lens_[:, None, :] + disp[:, None, :]) >= t
    )
    ring_valid = base[:, None, :] & ~evicted  # [S, Kq, T]
    neg = jnp.finfo(jnp.float32).min
    ring_scores = jnp.where(ring_valid[:, None], ring_scores, neg)

    # tail scores [S, H, Kq, Kt], causal within the tail
    tail_scores = jnp.einsum(
        "sqhd,skhd->shqk", q, tk, preferred_element_type=jnp.float32
    ) * scale
    qi = jax.lax.broadcasted_iota(jnp.int32, (kq, kt), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (kq, kt), 1)
    tail_scores = jnp.where((ki <= qi)[None, None], tail_scores, neg)

    scores = jnp.concatenate([ring_scores, tail_scores], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("shqt,sthd->sqhd", probs[..., :t], cv)
    out = out + jnp.einsum("shqk,skhd->sqhd", probs[..., t:], tv)
    return out


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
