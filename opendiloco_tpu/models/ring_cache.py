"""The serving KV cache: slot-paged ring pages, and everything that knows
how they are stored; beside it, for a hybrid stack, the Mamba-2 layers'
per-slot recurrent state (``init_ssm_state``, ``state_insert``), and for CCA
what each layer's projection keeps of a slot's last token
(``init_cca_state``, ``cca_state_insert``).

Storage is a ``k`` and a ``v`` array of ``[L, S, Nkv, Dh, T]``: one
fixed-size ring page of T rows per layer and batch slot (the degenerate
paged layout -- page size == slot context), **rows minor-most**. That is
the order the chip keeps whatever the program says (a head of 64 is under
the 128 lanes, so the device puts T minor anyway and every reader that
wanted another order paid a copy of the layer's pages) and the order the
decode kernel's ``(Dh, block_t)`` tiles are cut from, so the decode step
reads the pages where the engine holds them and writes one row into them.
Two facts live here and nowhere else:

- **the order of those axes.** Everything outside this module exchanges
  K/V as rows, ``[L, rows, Nkv, Dh]`` (a prefill's output, a page on the
  host tier); the functions below convert at the
  module's edge (a prompt's K/V is megabytes, the cache gigabytes). The
  forwards hand one layer's pages ``[S, Nkv, Dh, T]`` to the attention
  readers (:func:`layer_pages`, or a scan over the leading axis); the
  engine and the scheduler hold pages without indexing them.
- **the ring arithmetic.** Token ``p`` of a sequence lives at row ``p % T``.
  Until a sequence outgrows its page, rows ``[0, len)`` hold it and rows
  beyond are a previous tenant's: stale, and masked by every reader
  (``ops.attention``'s ``lens`` masks) until the sequence's own writes reach
  them. Once ``len >= T`` the whole ring is live and attention slides over
  the last T tokens.

EVA attention (``cfg.eva``) keeps **two rings of two lifetimes** a slot, and
neither is the slot's context. The ``(k, v)`` ring above is ``window_size``
rows and *restarts*: token ``p`` lives at row ``p % window_size`` and a reader
takes rows ``[0, p % window_size]``, the tokens of p's own window up to p; it
never slides over the window before. Beside it a **pooled ring**
(:func:`init_eva_state`: ``pool_k``, ``pool_v``, same order of axes) holds
one pooled key and value per ``chunk_size`` positions, chunk ``j`` at row ``j``
for the sequence's whole life, of which a reader at ``p`` takes the first
``(p // window_size) * (window_size // chunk_size)``: the chunks of the windows
before p's own. Both lengths derive from the one position. A decode step
writes row ``p // chunk_size`` at *every* position (the chunk as pooled so
far, from the per-slot ``stats`` of the pooling under way): it is the chunk's
own pooled row at the chunk's last position, and no reader reaches it before
its window has ended. ``max_context`` bounds the positions and sizes the
pooled ring alone. :func:`eva_insert` hands a prefill's current-window rows
(:func:`eva_window_rows`), its pooled rows and the stats of the chunk the
prompt ends in to a slot.

Learned sparse attention (``cfg.sparse``) keeps **three rings of one
lifetime** a slot: K, V and, beside them, each token's index key, written with
the step's row and inserted with a prompt's or a chunk's rows. Token ``p``
lives at row ``p % T`` of all three and the ``lens`` masks are the ring's
above. **K and V keep this module's order, rows minor-most**: that is what the
chip showed (PERF.md, PR 49). A decode step's attention reads a slot's live
rows in order under the selection's mask, 1.13 to 1.19 ms a layer of 12 slots
from either order, where gathering the 2,048 chosen rows of 1 KB from
row-contiguous pages took 1.49 ms (49,152 transfers of 1 KB a layer cost more
than seven times the bytes in order): nothing reads scattered rows, so nothing
wants a row contiguous, and the compiler itself re-laid row-contiguous pages
rows minor-most wherever a program's heads are a matmul's batch (3.1 GB of
copies a ring in the chunk program, compiled for a described v5e). So the
decode kernel reads and writes these pages as it does every other
configuration's, under one more operand, the selection. The **index ring**
(:func:`init_index_cache`) is ``[L, S, Di, T]``, rows minor-most too: a key of
64 values is under the 128 lanes (this docstring's first paragraph), and the
scoring is one ``[Hi, Di] x [Di, T]`` product a slot with the rows on the
lanes. **No program writes it inside its scan over the layers**: compiled for
a described v5e, a row written there (a slice update a slot, in any order of
the axes, padded to 128 lanes or not) made the compiler keep the ring in
another order inside the scan than at the program's edge and copy the whole
ring in and out, 415 MB each way a decode step, and twice a layer in the chunk
program. So the layers read the ring as the program found it, the step's (or
the chunk's) own keys enter the scores beside it (``ops.attention``), and the
new keys of all layers are written once, behind the scan: a chunk's as one
block of whole lanes (:func:`index_chunk_insert`), a decode step's as a column
a slot and layer, by a kernel that takes the 128-row block that holds it
through an aliased output as the decode kernel does for K and V
(``decode_kernels.index_ring_write``; :func:`index_write_rows` off the TPU).
:func:`layer_rows_insert` writes a chunk's K and V rows into one layer's pages
of one slot, :func:`slot_layer_pages` hands a chunk's attention that slot's
pages.

Plain functions over the ``(k, v)`` pair; every writer returns the new pair
and callers jit them with both donated, so an update is in place at HBM.

Latent attention keeps **one** array: a token's row is its latent
(``kv_lora_rank`` normed values, then the rotated key part all heads share),
from which keys and values are never rebuilt in decode, so the ring is
``[L, S, 1, R + rope, T]`` in ``k``'s place and ``v`` is None. The row is one
"head" of that size to everything here; rows cross the module's edge as
``[L, rows, R + rope]``. :func:`init_kv_cache`, :func:`cache_insert` and
:func:`write_row` take and return the pair with None in ``v``'s place; the
other functions serve what is refused for such a configuration
(``models.traits``: the table's ``latent``) and are not written for it.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from opendiloco_tpu.models import mamba


class RingPair(NamedTuple):
    """The K ring and the V ring of one kind of layer, where a stack keeps
    ``(k, v)`` rings by kind (full and sliding grouped-query layers: two rings
    of two lifetimes in one cache): a pytree of the two arrays that stands
    where one ring stands for every other configuration (``cache_k``: the full
    layers' pair, ``cache_v``: the sliding layers'), so that the programs carry
    and donate both kinds' rings as they carry one, and whoever asks a ring for
    its rows, dtype, bytes or device gets the pair's."""

    k: jax.Array
    v: jax.Array

    @property
    def shape(self) -> tuple:
        return self.k.shape

    @property
    def dtype(self):
        return self.k.dtype

    @property
    def nbytes(self) -> int:
        return self.k.nbytes + self.v.nbytes

    def devices(self):
        return self.k.devices()


def init_kv_cache(
    cfg,
    num_slots: int,
    max_context: int,
    dtype: jnp.dtype = jnp.bfloat16,
) -> dict:
    """Zeroed {"k","v"} pages for ``cfg`` (its attention layers, KV heads
    and head size): ``num_slots`` rings of ``max_context`` rows a layer. For
    latent attention ``k`` is the one latent ring and ``v`` None; for a latent
    stack with sliding layers ``k`` is the full layers' ring and ``v`` the
    sliding layers' (:func:`sliding_ring_rows` rows of their own row width).
    For a grouped-query stack with sliding layers ``k`` is the full layers'
    :class:`RingPair` (K and V rings as long as the context) and ``v`` the
    sliding layers' (K and V rings of :func:`sliding_ring_rows` rows, which
    wrap): two ``(k, v)`` rings of two lifetimes, the same KV heads in both."""
    if cfg.sliding and not cfg.latent:
        def pair(layers, rows):
            shape = cache_shape(layers, num_slots, rows, cfg.kv_heads, cfg.head_dim)
            return RingPair(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))

        return {"k": pair(cfg.num_full_layers, max_context),
                "v": pair(cfg.num_sliding_layers, sliding_ring_rows(cfg))}
    if cfg.latent and cfg.sliding:  # rings by kind: the sliding one in ``v``'s place
        full = cache_shape(cfg.num_full_layers, num_slots, max_context, 1, cfg.latent_row_dim)
        sliding = cache_shape(
            cfg.num_sliding_layers, num_slots, sliding_ring_rows(cfg), 1, cfg.sliding_row_dim
        )
        return {"k": jnp.zeros(full, dtype), "v": jnp.zeros(sliding, dtype)}
    if cfg.latent:
        shape = cache_shape(
            cfg.num_attention_layers, num_slots, max_context, 1, cfg.latent_row_dim
        )
        return {"k": jnp.zeros(shape, dtype), "v": None}
    if cfg.eva:  # the ring is a window, whatever the context
        max_context = cfg.window_size
    shape = cache_shape(
        cfg.num_attention_layers, num_slots, max_context, cfg.kv_heads, cfg.head_dim
    )
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def sliding_ring_rows(cfg) -> int:
    """Rows of a sliding layer's ring, whatever the context: a prefill chunk
    of ``q_chunk_size`` rows beside the ``sliding_window_size - 1`` rows before
    it that its first query reads, in whole chunks, so that a chunk written as
    one aligned block overwrites only rows that no window reaches any more
    (1,024 for a window of 513 under chunks of 512)."""
    chunk = cfg.q_chunk_size
    return chunk * (1 + -(-(cfg.sliding_window_size - 1) // chunk))


def init_ssm_state(cfg, num_slots: int, dtype: jnp.dtype = jnp.bfloat16) -> dict:
    """Zeroed {"ssm","conv"} for ``cfg``'s Mamba-2 layers: per layer and
    slot the recurrent state [H, P, N] (float32 always) and the conv tail
    [K - 1, channels] (the last inputs of the depthwise conv, channels
    minor-most as the chip tiles them)."""
    ssm, conv = mamba.state_shapes(cfg, num_slots)
    return {"ssm": jnp.zeros(ssm, jnp.float32), "conv": jnp.zeros(conv, dtype)}


def state_insert(
    ssm: jax.Array,
    conv: jax.Array,
    states: jax.Array,
    tails: jax.Array,
    slot: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """Write what a prefill left (states [Lm, H, P, N], tails [Lm, K - 1, C])
    into ``slot`` (traced scalar), whole: unlike a ring page, a recurrent
    state has no stale part to mask, so a slot's next tenant starts from its
    own prefill and nothing else."""
    slot = jnp.asarray(slot, jnp.int32)

    def put(store, x):
        start = (jnp.int32(0), slot) + (jnp.int32(0),) * (store.ndim - 2)
        return jax.lax.dynamic_update_slice(store, x[:, None].astype(store.dtype), start)

    return put(ssm, states), put(conv, tails)


def init_lightning_state(cfg, num_slots: int) -> jax.Array:
    """Zeroed [Ll, S, H, D, D] float32 for ``cfg``'s lightning layers
    (``models/lightning.py``): per layer and slot the decaying state, float32
    whatever the compute dtype. Zero is a sequence's start; a prompt's first
    chunk starts from zeros whatever the slot holds, so nothing clears a
    slot between tenants."""
    from opendiloco_tpu.models import lightning

    return jnp.zeros(lightning.state_shape(cfg, num_slots), jnp.float32)


def init_kda_state(cfg, num_slots: int, dtype: jnp.dtype = jnp.bfloat16) -> dict:
    """Zeroed {"state", "tail"} for ``cfg``'s kda layers (``models/kda.py``),
    the dict form ``init_ssm_state`` has: per layer and slot the delta-rule
    state [H, D, D] (float32 always: [Lk, S, H, D, D]) and the tail of the
    convolution on q, k and v [taps - 1, 3 H D] (its last inputs, channels
    minor-most, stored a row of the window before the slots: [Lk, taps - 1, S,
    3 H D], ``kda.state_shapes`` says why). Zero is a sequence's start; a
    prompt's first chunk starts from zeros whatever the slot holds, so nothing
    clears a slot between tenants. (:func:`state_insert` takes slots-leading
    stores and is not shared: every prompt of such a stack goes in chunks,
    which write the slot's state and tail themselves.)"""
    from opendiloco_tpu.models import kda

    state, tail = kda.state_shapes(cfg, num_slots)
    return {"state": jnp.zeros(state, jnp.float32), "tail": jnp.zeros(tail, dtype)}


def init_pooled_cache(
    cfg, num_slots: int, max_context: int, dtype: jnp.dtype = jnp.bfloat16
) -> jax.Array:
    """Zeroed pooled-key ring for attention under a selection by blocks
    (``cfg.blocks``), ``[Ls, S, Nkv, Dh, T / kernel_stride]`` beside the
    attention layers' ``(k, v)`` ring, rows minor-most as theirs: window j (the
    mean of the keys of rows [stride j, stride j + kernel)) at row j for the
    sequence's whole life, written when row ``stride j + kernel - 1`` is (by
    the chunk that holds it, by the decode step at it) **from the K ring's own
    rows**: no slot keeps a half-pooled window, and a reader at position p
    takes the windows that have closed, ``stride j + kernel - 1 <= p``. As the
    index ring, no program writes it inside its scan over the layers."""
    sizes = cfg.block_sizes
    return jnp.zeros(
        cache_shape(cfg.num_attention_layers, num_slots, sizes.pooled_rows(int(max_context)),
                    cfg.kv_heads, cfg.head_dim), dtype,
    )


def pooled_chunk_insert(cache_p: jax.Array, slot, pooled: jax.Array, first) -> jax.Array:
    """A prefill chunk's pooled keys of all layers, pooled [Ls, Nkv, Dh, n] in
    storage order (the windows from ``first`` on; those the chunk did not
    close already hold what the ring held), into ``slot`` at ring rows
    [first, first + n) (``slot``, ``first`` traced)."""
    zero = jnp.int32(0)
    where = (zero, jnp.asarray(slot, jnp.int32), zero, zero, jnp.asarray(first, jnp.int32))
    return jax.lax.dynamic_update_slice(cache_p, pooled[:, None].astype(cache_p.dtype), where)


def init_cca_state(cfg, num_slots: int, dtype: jnp.dtype = jnp.bfloat16) -> jax.Array:
    """Zeroed [L, S, ``cfg.cca_state_dim``]: per layer and slot what CCA's
    projection reads of the token before (``llama._cca_qkv``: q and k before
    the convolutions, the same between the two, the values the next token
    takes), one row, values minor-most. Zero is a sequence's start."""
    return jnp.zeros((cfg.num_hidden_layers, num_slots, cfg.cca_state_dim), dtype)


def cca_state_insert(state: jax.Array, rows: jax.Array, slot: jax.Array) -> jax.Array:
    """Write what a prefill's last real token left (rows [L, cca_state_dim])
    into ``slot`` (traced scalar), whole: as a recurrent state, the row has no
    stale part to mask, so a slot's next tenant starts from its own prompt."""
    start = (jnp.int32(0), jnp.asarray(slot, jnp.int32), jnp.int32(0))
    return jax.lax.dynamic_update_slice(state, rows[:, None].astype(state.dtype), start)


def eva_pooled_rows(cfg, max_context: int) -> int:
    """Rows of a slot's pooled ring: a pooled row per chunk of every window
    that a context of ``max_context`` positions touches (whole windows, so
    that the decode kernel's tiles, which are a window's pooled rows or a
    divisor of them, cut the ring evenly)."""
    return -(-int(max_context) // cfg.window_size) * cfg.eva_chunks_per_window


def init_eva_state(cfg, num_slots: int, max_context: int, dtype: jnp.dtype = jnp.bfloat16) -> dict:
    """Zeroed {"pool_k", "pool_v", "stats"} for EVA attention: the pooled ring
    ([L, S, Nkv, Dh, :func:`eva_pooled_rows`] each, the ``(k, v)`` ring's order)
    and, per layer, slot and KV head, the pooling under way of the chunk the
    slot's position lies in ([L, S, Nkv, 2 Dh + 2] float32:
    ``ops.attention.eva_pool``'s stats)."""
    L, Nkv, Dh = cfg.num_hidden_layers, cfg.kv_heads, cfg.head_dim
    shape = cache_shape(L, num_slots, eva_pooled_rows(cfg, max_context), Nkv, Dh)
    return {
        "pool_k": jnp.zeros(shape, dtype), "pool_v": jnp.zeros(shape, dtype),
        "stats": jnp.zeros((L, num_slots, Nkv, 2 * Dh + 2), jnp.float32),
    }


def eva_window_rows(x: jax.Array, length, window: int) -> jax.Array:
    """Of a prompt's rows x [P, ...] those of the window that ``length``
    (traced) lies in, [min(P, window), ...]: positions [window * (length //
    window), ...), which a slot's ring takes at row 0. Where the window runs
    past P the rows beyond are zeros (a prompt that ends on a window's edge
    hands over nothing that is read)."""
    P = x.shape[0]
    if P <= window:
        return x
    x = jnp.pad(x, ((0, -P % window), *((0, 0),) * (x.ndim - 1)))
    first = jnp.asarray(length, jnp.int32) // window * window
    return jax.lax.dynamic_slice_in_dim(x, first, window, axis=0)


def eva_insert(
    cache_k, cache_v, pool_k, pool_v, stats, ks, vs, pooled_ks, pooled_vs, chunk_stats, slot,
):
    """Hand a prefilled prompt to ``slot``: the K/V of its last window [L, R,
    Nkv, Dh] (:func:`eva_window_rows`: the prompt's positions from its last
    window's edge on) land at ring rows [0, R), of which those beyond the
    prompt's length are stale and masked until the slot's own writes reach
    them; its pooled rows [L, J, Nkv, Dh] at pooled rows [0, J) (those of
    chunks that had not ended with the prompt are rewritten by the decode
    steps before their window ends); and the pooling under way of the chunk
    that the prompt ends in ([L, Nkv, 2 Dh + 2]) whole. -> the five, updated."""
    for rows, ring, what in ((ks, cache_k, "window"), (pooled_ks, pool_k, "pooled")):
        if rows.shape[1] > ring_rows(ring):
            raise ValueError(
                f"a prompt's {rows.shape[1]} {what} rows exceed the ring's {ring_rows(ring)}"
            )
    slot, zero = jnp.asarray(slot, jnp.int32), jnp.int32(0)

    def put(cache, x):
        x = _rows_minor(x)[:, None].astype(cache.dtype)
        return jax.lax.dynamic_update_slice(cache, x, (zero, slot, zero, zero, zero))

    stats = jax.lax.dynamic_update_slice(
        stats, chunk_stats[:, None].astype(stats.dtype), (zero, slot, zero, zero)
    )
    return (
        put(cache_k, ks), put(cache_v, vs), put(pool_k, pooled_ks), put(pool_v, pooled_vs), stats,
    )


def init_index_cache(
    cfg, num_slots: int, max_context: int, dtype: jnp.dtype = jnp.bfloat16
) -> jax.Array:
    """Zeroed index-key ring for learned sparse attention, ``[L, S, Di, T]``:
    ``num_slots`` rings of ``max_context`` rows a layer, rows minor-most."""
    return jnp.zeros(  # beside the layers whose ring is the context's: the full ones
        (cfg.num_full_layers, num_slots, cfg.index_head_dim, int(max_context)), dtype
    )


def index_write_rows(cache_i: jax.Array, keys: jax.Array, lens: jax.Array) -> jax.Array:
    """A decode step's index keys of all layers, keys [L, S, Di], at ring row
    ``lens % T`` of each slot, as XLA does it: the reference of
    ``decode_kernels.index_ring_write`` and the path off the TPU (on the chip
    a scatter into rows-minor pages re-lays the whole ring). A slot at
    ``lens`` 0 holds no sequence that decodes (a prompt has a token) and is
    written nothing: it may be a slot whose prompt is arriving in chunks, and
    its row 0 is that prompt's."""
    S, T = cache_i.shape[1], cache_i.shape[-1]
    at = jnp.where(lens > 0, jnp.mod(lens, T), T)  # row T: dropped
    return cache_i.at[:, jnp.arange(S), :, at].set(
        jnp.moveaxis(keys, 0, 1).astype(cache_i.dtype), mode="drop"
    )


def index_insert(cache_i: jax.Array, iks: jax.Array, slot) -> jax.Array:
    """A whole prompt's index keys [L, P, Di] into ``slot`` (traced) at ring
    rows [0, P); rows beyond the prompt's length (a bucket's padding) land too,
    stale and masked as any slot's rows beyond its length are."""
    if iks.shape[1] > cache_i.shape[-1]:
        raise ValueError(f"prefill length {iks.shape[1]} exceeds slot context {cache_i.shape[-1]}")
    zero = jnp.int32(0)
    rows = jnp.swapaxes(iks, 1, 2)[:, None].astype(cache_i.dtype)  # [L, 1, Di, P]
    return jax.lax.dynamic_update_slice(
        cache_i, rows, (zero, jnp.asarray(slot, jnp.int32), zero, zero)
    )


def index_chunk_insert(cache_i, slot, iks: jax.Array, start, count) -> jax.Array:
    """A prefill chunk's index keys of all layers, iks [L, C, Di], into
    ``slot`` at ring rows [start, start + C) (``slot``, ``start``, ``count``
    traced), of which only the first ``count`` are the chunk's own: the rows
    behind them (a last chunk's padding) keep what the ring held. ``start +
    C`` lies within the ring (the engine holds ``max_context`` to whole
    chunks): one block of whole lanes where the chunk is a multiple of 128."""
    L, C, Di = iks.shape
    zero = jnp.int32(0)
    where = (zero, jnp.asarray(slot, jnp.int32), zero, jnp.asarray(start, jnp.int32))
    old = jax.lax.dynamic_slice(cache_i, where, (L, 1, Di, C))
    own = jnp.arange(C) < count
    new = jnp.where(own, jnp.swapaxes(iks, 1, 2)[:, None].astype(cache_i.dtype), old)
    return jax.lax.dynamic_update_slice(cache_i, new, where)


def layer_rows_insert(cache_k, cache_v, layer, slot, k, v, start, count, whole_chunks=True):
    """One layer's K and V rows of a prefill chunk, k and v [C, Nkv, Dh], into
    ``slot``'s pages at ring rows [start, start + C), of which only the first
    ``count`` are the chunk's own (as :func:`index_chunk_insert`): a block of
    whole lanes where the chunk is a multiple of 128 rows. ``whole_chunks``
    (static) says that ``start + C`` lies within the ring, as it does where
    the ring is whole chunks and a prompt goes in from row 0; without it (a
    suffix behind a prefix of any length, padded to a bucket) only ``start +
    count`` does, and the block is the ring's last C rows where the padding
    would pass its end, the chunk's rows moved down within it. A latent ring
    takes its rows as k [C, 1, Dl] with None for ``cache_v`` and ``v``; a ring
    that wraps takes the chunk at ``start`` modulo its rows (whole chunks: the
    block does not pass its end)."""
    C = k.shape[0]
    zero = jnp.int32(0)
    start = jnp.asarray(start, jnp.int32)
    own = jnp.arange(C) < count
    if not whole_chunks:  # the block's row i holds the chunk's row i - down
        down = jnp.maximum(start + C - ring_rows(cache_k), 0)
        start, own = start - down, jnp.roll(own, down) & (jnp.arange(C) >= down)
    where = (jnp.asarray(layer, jnp.int32), jnp.asarray(slot, jnp.int32), zero, zero, start)

    def put(cache, x):
        if cache is None:  # a latent ring is the one array
            return None
        old = jax.lax.dynamic_slice(cache, where, (1, 1, *cache.shape[2:4], C))
        if not whole_chunks:
            x = jnp.roll(x, down, axis=0)
        new = jnp.where(own, _rows_minor(x).astype(cache.dtype)[None, None], old)
        return jax.lax.dynamic_update_slice(cache, new, where)

    return put(cache_k, k), put(cache_v, v)


def slot_layer_pages(cache: jax.Array, layer, slot) -> jax.Array:
    """One slot's part of one layer's pages (both traced): K or V [Nkv, Dh,
    T], or the index ring's [Di, T]."""
    return jax.lax.dynamic_index_in_dim(
        jax.lax.dynamic_index_in_dim(cache, layer, 0, False), slot, 0, False
    )


def write_live_row(cache_k, cache_v, layer, k, v, lens):
    """:func:`write_row` that writes nothing for a slot at ``lens`` 0 (the
    decode step of a configuration whose prompts may be arriving in chunks: a
    prefilling slot rides no step, and its row 0 is its prompt's)."""
    rows = jnp.arange(cache_k.shape[1])
    idx = jnp.where(lens > 0, jnp.mod(lens, ring_rows(cache_k)), ring_rows(cache_k))

    def put(cache, x):
        if cache is None:  # a latent ring is the one array
            return None
        return cache.at[layer, rows, :, :, idx].set(x.astype(cache.dtype), mode="drop")

    return put(cache_k, k), put(cache_v, v)


def cache_shape(
    layers: int, slots: int, rows: int, kv_heads: int, head_dim: int
) -> tuple[int, ...]:
    """The shape of ``k`` (and of ``v``) in storage order, for callers that
    describe a cache without allocating one (compile tests, benches)."""
    return (layers, slots, kv_heads, head_dim, rows)


def ring_rows(cache: jax.Array) -> int:
    """T, the rows of one ring page (of a cache, or of one layer's pages)."""
    return cache.shape[-1]


def _rows_minor(x: jax.Array) -> jax.Array:
    """K/V as the module's callers exchange it, [..., rows, Nkv, Dh], in
    storage order [..., Nkv, Dh, rows]."""
    return jnp.moveaxis(x, -3, -1)


def rows_first(pages: jax.Array) -> jax.Array:
    """Pages in storage order [..., Nkv, Dh, rows] as rows [..., rows, Nkv,
    Dh]: the exchange format, and what the XLA attention references read (a
    copy of what it is given)."""
    return jnp.moveaxis(pages, -1, -3)


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
    keep the previous tenant's bytes, stale and masked. A latent ring takes
    its rows as [L, P, R + rope], with None for ``cache_v`` and ``vs``."""
    P, T = ks.shape[1], ring_rows(cache_k)
    if P > T:
        raise ValueError(f"prefill length {P} exceeds slot context {T}")
    zero = jnp.int32(0)
    start = (zero, jnp.asarray(slot, jnp.int32), zero, zero, zero)

    def put(cache, x):
        if cache is None:
            return None
        if x.ndim == 3:  # latent rows: the row is the one head
            x = x[:, :, None]
        x = _rows_minor(x)[:, None].astype(cache.dtype)  # [L, 1, Nkv, Dh, P]
        return jax.lax.dynamic_update_slice(cache, x, start)

    return put(cache_k, ks), put(cache_v, vs)


def write_row(
    cache_k: jax.Array,
    cache_v: jax.Array,
    layer: jax.Array,
    k: jax.Array,
    v: jax.Array,
    lens: jax.Array,
) -> tuple[jax.Array, jax.Array]:
    """The decode step's row write as XLA does it: each slot's new K/V (k, v
    [S, Nkv, Dh]) lands at ring row ``lens % T`` of ``layer``'s pages. The
    reference of the decode kernel's in-place write and the path off the
    TPU; on the chip a scatter into rows-minor pages re-lays the whole cache
    (ISSUE 29), which is why the kernel writes the row itself. A latent
    ring takes ``k`` as [S, 1, R + rope], with None for ``cache_v`` and ``v``."""
    rows = jnp.arange(cache_k.shape[1])
    idx = jnp.mod(lens, ring_rows(cache_k))

    def put(cache, x):  # cache[layer, rows, :, :, idx] is [S, Nkv, Dh]
        if cache is None:
            return None
        return cache.at[layer, rows, :, :, idx].set(x.astype(cache.dtype))

    return put(cache_k, k), put(cache_v, v)


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
    keep = jnp.arange(ring_rows(cache_k)) < plen

    def copy(cache):
        page = jnp.where(
            keep, jnp.take(cache, src, axis=1), jnp.take(cache, dst, axis=1)
        )
        return cache.at[:, dst].set(page)

    return copy(cache_k), copy(cache_v)


def fetch_pages(
    cache_k: jax.Array, cache_v: jax.Array, slot: jax.Array, rows: int
) -> tuple[jax.Array, jax.Array]:
    """One slot's leading ``rows`` (static) ring rows, [L, rows, Nkv, Dh]
    each, by value: the host tier's page-out. :func:`cache_insert` takes
    them back."""
    def cut(cache):
        page = jnp.take(cache, slot, axis=1)  # [L, Nkv, Dh, T]
        return rows_first(jax.lax.slice_in_dim(page, 0, rows, axis=-1))

    return cut(cache_k), cut(cache_v)


def layer_pages(
    cache_k: jax.Array, cache_v: jax.Array, layer
) -> tuple[jax.Array, jax.Array]:
    """One layer's pages [S, Nkv, Dh, T], as a scan over the caches hands
    them out; ``layer`` may be traced (a copy of the layer's pages then)."""
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
