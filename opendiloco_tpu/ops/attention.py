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


def spec_tail_attention(
    q: jax.Array,
    cache_k: jax.Array,
    cache_v: jax.Array,
    tail_k: jax.Array,
    tail_v: jax.Array,
    lens: jax.Array,
    *,
    q_start: int = 0,
) -> jax.Array:
    """Multi-token tail attention over a ring KV cache plus in-register
    tail K/V — the verify/draft primitive for speculative decode.

    q [S, Kq, H, D] are unverified tail tokens per slot at absolute
    positions ``lens + q_start + i``; cache_{k,v} hold one layer's ring
    pages ([S, Kh, D, T], ``ring_cache``'s order, read as rows through the
    module) as of BEFORE the tail (positions <= lens - 1); tail_{k,v}
    [S, K, Kh, D] are the tail's own K/V, kept out of the ring until
    acceptance. ``q_start`` offsets the queries within the tail (the
    draft proposes one token at a time against a growing tail buffer;
    the verify pass runs the whole tail at q_start=0).

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
    j = q_start + jnp.arange(kq, dtype=jnp.int32)[None, :, None]  # [1, Kq, 1]
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
    qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (kq, kt), 0)
    ki = jax.lax.broadcasted_iota(jnp.int32, (kq, kt), 1)
    tail_scores = jnp.where((ki <= qi)[None, None], tail_scores, neg)

    scores = jnp.concatenate([ring_scores, tail_scores], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("shqt,sthd->sqhd", probs[..., :t], cv)
    out = out + jnp.einsum("shqk,skhd->sqhd", probs[..., t:], tv)
    return out
