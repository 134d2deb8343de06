"""The serving KV cache: slot-paged ring pages, and everything that knows
how they are stored.

Storage is a ``k`` and a ``v`` array of ``[L, S, T, Nkv, Dh]``: one
fixed-size ring page of T rows per layer and batch slot (the degenerate
paged layout -- page size == slot context). Two facts live here and nowhere
else:

- **the order of those axes.** The forwards scan the leading layer axis and
  hand one layer's pages ``[S, T, Nkv, Dh]`` to the functions below; the
  engine and the kernel wrappers hold pages without indexing them.
- **the ring arithmetic.** Token ``p`` of a sequence lives at row ``p % T``.
  Until a sequence outgrows its page, rows ``[0, len)`` hold it and rows
  beyond are a previous tenant's: stale, and masked by every reader
  (``ops.attention``'s ``lens`` masks) until the sequence's own writes reach
  them. Once ``len >= T`` the whole ring is live and attention slides over
  the last T tokens.

Plain functions over the ``(k, v)`` pair; every writer returns the new pair
and callers jit them with both donated, so an update is in place at HBM.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init_kv_cache(
    cfg,
    num_slots: int,
    max_context: int,
    dtype: jnp.dtype = jnp.bfloat16,
) -> dict:
    """Zeroed {"k","v"} pages for ``cfg`` (its layers, KV heads and head
    size): ``num_slots`` rings of ``max_context`` rows a layer."""
    L, Nkv, Dh = cfg.num_hidden_layers, cfg.kv_heads, cfg.head_dim
    shape = (L, num_slots, max_context, Nkv, Dh)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_insert(
    cache_k: jax.Array,
    cache_v: jax.Array,
    ks: jax.Array,
    vs: jax.Array,
    slot: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Write a sequence's K/V [L, P, Nkv, Dh] into ``slot`` (traced scalar)
    at ring rows [0, P): a prefilled prompt, or one slot's pages coming back
    from the host tier (:func:`fetch_pages` is the way out). Rows beyond P
    keep the previous tenant's bytes, stale and masked."""
    P, T = ks.shape[1], cache_k.shape[2]
    if P > T:
        raise ValueError(f"prefill length {P} exceeds slot context {T}")
    zero = jnp.int32(0)
    start = (zero, jnp.asarray(slot, jnp.int32), zero, zero, zero)

    def put(cache, x):
        x = x[:, None].astype(cache.dtype)
        return jax.lax.dynamic_update_slice(cache, x, start)

    return put(cache_k, ks), put(cache_v, vs)


def step_writer(cache_k: jax.Array, lens: jax.Array):
    """The decode step's row write, for a scan over layers: ``write(pages_k,
    pages_v, k, v)`` puts each slot's new K/V (k, v [S, 1, Nkv, Dh]) at ring
    row ``lens % T`` of one layer's pages and returns them. The row index is
    computed here, once, outside the scan."""
    S, T = cache_k.shape[1], cache_k.shape[2]
    rows = jnp.arange(S)
    write_idx = jnp.mod(lens, T)

    def write(pages_k, pages_v, k, v):
        pages_k = pages_k.at[rows, write_idx].set(k[:, 0].astype(pages_k.dtype))
        pages_v = pages_v.at[rows, write_idx].set(v[:, 0].astype(pages_v.dtype))
        return pages_k, pages_v

    return write


def spec_cache_insert(
    cache_k: jax.Array,
    cache_v: jax.Array,
    tail_ks: jax.Array,
    tail_vs: jax.Array,
    lens: jax.Array,
    accept: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Positioned ring insert of the ACCEPTED tail prefix: per slot, tail
    tokens i <= accept[s] of tail_{ks,vs} [L, S, K, Nkv, Dh] land at ring
    row ``(lens + i) % T``; rejected positions write their current cache
    value back, so rejected tail tokens never reach the ring. Requires
    K <= T so a tail never collides with itself."""
    S, T, K = cache_k.shape[1], cache_k.shape[2], tail_ks.shape[2]
    if K > T:
        raise ValueError(f"tail width {K} exceeds ring context {T}")
    rows = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[:, None], (S, K))
    pos = jnp.mod(lens[:, None] + jnp.arange(K, dtype=jnp.int32)[None], T)
    keep = (jnp.arange(K, dtype=jnp.int32)[None] <= accept[:, None])[
        None, :, :, None, None
    ]

    def put(cache, tail):  # cache[:, rows, pos] is [L, S, K, Nkv, Dh]
        new = jnp.where(keep, tail.astype(cache.dtype), cache[:, rows, pos])
        return cache.at[:, rows, pos].set(new)

    return put(cache_k, tail_ks), put(cache_v, tail_vs)


def prefix_copy(
    cache_k: jax.Array,
    cache_v: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    plen: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Copy the first ``plen`` rows of slot ``src`` into slot ``dst``
    (shared-prefix KV reuse). Rows >= plen keep dst's previous bytes --
    stale and masked, same as any slot reuse."""
    keep = (jnp.arange(cache_k.shape[2]) < plen)[:, None, None]

    def copy(cache):
        page = jnp.where(
            keep, jnp.take(cache, src, axis=1), jnp.take(cache, dst, axis=1)
        )
        return cache.at[:, dst].set(page)

    return copy(cache_k), copy(cache_v)


def suffix_insert(
    cache_k: jax.Array,
    cache_v: jax.Array,
    ks: jax.Array,
    vs: jax.Array,
    slot: jax.Array,
    start: jax.Array,
    count: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Write a continued prefill's suffix K/V [L, P', Nkv, Dh] into ``slot``
    at rows [start, start + count) -- the positioned counterpart of
    :func:`cache_insert` (a prompt always fits its page, so no ring wrap
    here; padding rows beyond ``count`` are dropped)."""
    T, P = cache_k.shape[2], ks.shape[1]
    disp = jnp.arange(T, dtype=jnp.int32) - jnp.asarray(start, jnp.int32)
    valid = ((disp >= 0) & (disp < count))[:, None, None]
    gidx = jnp.clip(disp, 0, P - 1)

    def put(cache, x):
        page = jnp.take(cache, slot, axis=1)  # [L, T, Nkv, Dh]
        page = jnp.where(valid, x[:, gidx].astype(cache.dtype), page)
        return cache.at[:, slot].set(page)

    return put(cache_k, ks), put(cache_v, vs)


def slot_cache(
    cache_k: jax.Array, cache_v: jax.Array, slot: jax.Array
) -> tuple[jax.Array, jax.Array]:
    """One slot's pages as a cache of a single slot, [L, 1, T, Nkv, Dh] each:
    what a forward over that slot alone (the continued prefill) reads."""
    return (
        jnp.take(cache_k, slot, axis=1)[:, None],
        jnp.take(cache_v, slot, axis=1)[:, None],
    )


def fetch_pages(
    cache_k: jax.Array, cache_v: jax.Array, slot: jax.Array, rows: int
) -> tuple[jax.Array, jax.Array]:
    """One slot's leading ``rows`` (static) ring rows, [L, rows, Nkv, Dh]
    each, by value: the host tier's page-out. :func:`cache_insert` takes
    them back."""
    def cut(cache):
        page = jnp.take(cache, slot, axis=1)
        return jax.lax.dynamic_slice_in_dim(page, 0, rows, axis=1)

    return cut(cache_k), cut(cache_v)


def layer_pages(
    cache_k: jax.Array, cache_v: jax.Array, layer: int
) -> tuple[jax.Array, jax.Array]:
    """One layer's pages, as a scan over the caches hands them out."""
    return cache_k[layer], cache_v[layer]


def ring_live_rows(cache_len: int, t: int) -> int:
    """Rows of a T-row page that hold a sequence of ``cache_len`` cached
    tokens -- the host side of the ``lens`` masks, and the host tier's
    page-transfer contract: a page-out takes exactly these rows and a
    restore writes them back at row 0, which keeps the ring's layout in
    both regimes (rows [0, cache_len) before the page wraps, all of it
    after), so the masks are exact over a restored page."""
    if cache_len < 0:
        raise ValueError(f"cache_len must be >= 0, got {cache_len}")
    return min(int(cache_len), int(t))


def kernel_view(pages: jax.Array) -> jax.Array:
    """One layer's pages [S, T, Nkv, Dh] (or a tail's K/V [S, K, Nkv, Dh])
    as the decode kernels read them: [S, Nkv, T, Dh], so that a (rows, Dh)
    tile's two minor dimensions are array dimensions of their own. A copy of
    the layer's pages while the storage keeps rows before heads."""
    return pages.transpose(0, 2, 1, 3)
