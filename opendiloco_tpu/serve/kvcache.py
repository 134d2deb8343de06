"""Slot-paged ring KV cache bookkeeping for the serve plane.

The device arrays live in ``models.ring_cache.init_kv_cache`` (one fixed
ring page per layer and batch slot, in that module's storage order; pages
cross to the host as rows [L, rows, Nkv, Dh]); this module owns the host-side
bookkeeping — which slots are free, which compile-size bucket a prompt
pads to — so the engine's jitted ops see only dense arrays and traced
scalars.

:class:`HostKVTier` is the cold tier behind KV tiering (``ODTP_KV_TIER``):
a host-memory store for slot pages evicted D2H between decode steps,
optionally quantized with the outer plane's ``blockwise4bit`` codec, plus
a prefix-cache namespace (prompt-prefix K/V keyed by content hash +
weights epoch) that outlives slot churn and feeds the fleet's
prefix-cache directory.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
from typing import Optional, Sequence

import numpy as np

from opendiloco_tpu.diloco.compression import get_codec


class SlotAllocator:
    """Free-list over the cache's S batch slots.

    Continuous batching needs nothing fancier: a finished sequence frees
    its slot between decode steps and the next queued prompt claims it
    immediately; the page is reused in place (stale entries are masked
    until the new tenant's writes reach them — see llama.cache_insert).
    """

    def __init__(self, num_slots: int):
        if num_slots < 1:
            raise ValueError(f"need at least one slot, got {num_slots}")
        self.num_slots = num_slots
        # pop() takes from the tail, so keep ascending order reversed:
        # slot 0 is handed out first (stable slot ids make tests readable)
        self._free = list(range(num_slots))[::-1]

    def alloc(self) -> Optional[int]:
        """Claim a slot, or None when the batch is full."""
        return self._free.pop() if self._free else None

    def free(self, slot: int) -> None:
        if not 0 <= slot < self.num_slots:
            raise ValueError(f"slot {slot} out of range")
        if slot in self._free:
            raise ValueError(f"slot {slot} double-freed")
        self._free.append(slot)

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_active(self) -> int:
        return self.num_slots - len(self._free)


def common_prefix_len(a: Sequence[int], b: Sequence[int]) -> int:
    """Length of the shared leading run of two prompts (prefix-cache
    detection). Pure host bookkeeping; O(min len)."""
    n = min(len(a), len(b))
    for i in range(n):
        if a[i] != b[i]:
            return i
    return n


def pick_bucket(n: int, buckets: Sequence[int]) -> Optional[int]:
    """Smallest prefill compile bucket that fits an n-token prompt, or
    None when the prompt exceeds every bucket (the scheduler rejects it
    rather than compiling an unbounded family of prefill programs)."""
    for b in sorted(buckets):
        if n <= b:
            return b
    return None


# -- prefix hashing (fleet prefix-cache directory) ----------------------------

# prefix store/advertise granularity: prompt prefixes hash at these exact
# lengths, so a replica's advertisement and the router's lookup agree on
# the key without shipping token lists over the health channel
PREFIX_GRID = (16, 32, 64, 128, 256, 512, 1024, 2048)


def prefix_key(prompt: Sequence[int], glen: int) -> str:
    """Stable cross-process content hash of ``prompt[:glen]`` — the
    prefix-directory key. sha1 over the int32 token bytes, truncated: 16
    hex chars is plenty for a directory that holds thousands of entries,
    and keeps advertisement frames small."""
    raw = np.asarray(list(prompt[:glen]), np.int32).tobytes()
    return hashlib.sha1(raw).hexdigest()[:16]


def prefix_grid_lengths(n: int) -> list:
    """Grid lengths usable for an n-token prompt, longest first. Capped
    at n-1: the suffix pass must keep at least the final prompt token to
    run (its logits seed decode) — same cap as live-slot prefix reuse."""
    return [g for g in sorted(PREFIX_GRID, reverse=True) if g <= n - 1]


# -- host-memory cold tier -----------------------------------------------------


@dataclasses.dataclass
class _TierEntry:
    payload_k: bytes
    payload_v: bytes
    meta_k: dict
    meta_v: dict
    shape: tuple  # [L, rows, Kh, Dh] of ONE page (k and v are same shape)
    raw_bytes: int  # uncompressed f32 bytes both pages would occupy
    epoch: int = 0  # weights epoch (prefix entries only; -1 = any)


class HostKVTier:
    """Host-memory cold KV tier: evicted slot pages + a prefix cache.

    Two namespaces share one ``host_slots`` page budget:

    - **paused pages** (``put_paused``/``pop_paused``, keyed by request
      id): a live-but-cold sequence's ring page, evicted D2H so its batch
      slot can serve someone else and paged back H2D on resume. Pinned —
      the zero-drop guarantee means a paused sequence's state is never
      discarded; when pinned pages fill the budget the scheduler simply
      stops evicting.
    - **prefix entries** (``put_prefix``/``get_prefix``, keyed by
      ``(prefix_key, glen)``): prompt-prefix K/V stored at prefill time,
      tagged with the weights epoch that produced it. LRU-dropped under
      budget pressure and invalidated when the engine hot-swaps weights
      (stale-epoch entries never serve — cached prefix K/V must match the
      resident weights, the same consistency rule the ring cache keeps by
      NOT surviving a swap... inverted: the ring keeps old K/V with a
      staleness bound, the prefix store simply refuses to cross epochs).

    Pages are stored codec-encoded (``ODTP_KV_TIER_CODEC``): ``none`` is
    a bit-exact f32 round trip of the bf16/f32 cache values, ``blockwise4bit``
    reuses the outer plane's 4-bit codec for ~8x smaller resident bytes at
    a bounded, test-pinned restore error. All methods are called from the
    scheduler loop thread only (same single-owner discipline as the
    engine); byte/page counters are read racily by gauges, which is fine.
    """

    def __init__(self, *, host_slots: int = 32, codec: str = "none"):
        if host_slots < 1:
            raise ValueError(f"need at least one host slot, got {host_slots}")
        self.host_slots = int(host_slots)
        self.codec_name = str(codec)
        self.codec = get_codec(self.codec_name)
        self._paused: dict[int, _TierEntry] = {}
        # insertion order IS recency order (move_to_end on hit)
        self._prefix: collections.OrderedDict[tuple, _TierEntry] = (
            collections.OrderedDict()
        )
        # transfer accounting (raw f32-equivalent bytes moved per direction
        # plus codec-resident bytes, for the tier gauges / bench artifact)
        self.pages_out = 0
        self.pages_in = 0
        self.bytes_out = 0
        self.bytes_in = 0
        self.prefix_stores = 0
        self.prefix_hits = 0
        self.prefix_dropped = 0
        self.prefix_stale_purged = 0

    # -- encode/decode -------------------------------------------------------

    def _encode(self, k: np.ndarray, v: np.ndarray, epoch: int) -> _TierEntry:
        kf = np.ascontiguousarray(k, np.float32)
        vf = np.ascontiguousarray(v, np.float32)
        pk, mk = self.codec.encode(kf.reshape(-1))
        pv, mv = self.codec.encode(vf.reshape(-1))
        return _TierEntry(
            payload_k=bytes(pk),
            payload_v=bytes(pv),
            meta_k=mk,
            meta_v=mv,
            shape=tuple(k.shape),
            raw_bytes=kf.nbytes + vf.nbytes,
            epoch=int(epoch),
        )

    def _decode(self, e: _TierEntry) -> tuple[np.ndarray, np.ndarray]:
        n = int(np.prod(e.shape))
        k = np.asarray(
            self.codec.decode(e.payload_k, (n,), e.meta_k), np.float32
        ).reshape(e.shape)
        v = np.asarray(
            self.codec.decode(e.payload_v, (n,), e.meta_v), np.float32
        ).reshape(e.shape)
        return k, v

    # -- paused pages (pinned) ----------------------------------------------

    def can_pin(self) -> bool:
        """Room to accept one more paused page? Prefix entries do not
        block a pin — they are droppable and ``put_paused`` reclaims them
        LRU-first; only pinned pages are immovable budget."""
        return len(self._paused) < self.host_slots

    def put_paused(self, req_id: int, k: np.ndarray, v: np.ndarray) -> None:
        if req_id in self._paused:
            raise ValueError(f"request {req_id} already paused in the tier")
        if not self.can_pin():
            raise RuntimeError(
                f"host tier full ({self.host_slots} pinned pages)"
            )
        e = self._encode(k, v, epoch=-1)
        # pinned pages preempt droppable prefix entries under budget
        while len(self._paused) + len(self._prefix) >= self.host_slots and (
            self._prefix
        ):
            self._prefix.popitem(last=False)
            self.prefix_dropped += 1
        self._paused[req_id] = e
        self.pages_out += 1
        self.bytes_out += e.raw_bytes

    def pop_paused(self, req_id: int) -> tuple[np.ndarray, np.ndarray]:
        e = self._paused.pop(req_id)
        self.pages_in += 1
        self.bytes_in += e.raw_bytes
        return self._decode(e)

    def drop_paused(self, req_id: int) -> bool:
        """Discard a paused page without restoring it (request cancelled
        or expired while cold)."""
        return self._paused.pop(req_id, None) is not None

    # -- prefix namespace ----------------------------------------------------

    def has_prefix(self, key: str, glen: int, epoch: int) -> bool:
        e = self._prefix.get((key, int(glen)))
        return e is not None and e.epoch == int(epoch)

    def put_prefix(
        self, key: str, glen: int, epoch: int, k: np.ndarray, v: np.ndarray
    ) -> bool:
        """Store a prompt prefix's pages; returns False when the budget is
        all pinned (nothing droppable) and the entry was declined."""
        while len(self._paused) + len(self._prefix) >= self.host_slots:
            if not self._prefix:
                return False
            self._prefix.popitem(last=False)
            self.prefix_dropped += 1
        self._prefix[(key, int(glen))] = self._encode(k, v, epoch)
        self._prefix.move_to_end((key, int(glen)))
        self.prefix_stores += 1
        return True

    def get_prefix(
        self, key: str, glen: int, epoch: int
    ) -> Optional[tuple[np.ndarray, np.ndarray]]:
        kk = (key, int(glen))
        e = self._prefix.get(kk)
        if e is None:
            return None
        if e.epoch != int(epoch):
            # weight-swap staleness: the stored K/V was produced by older
            # weights; serving it would silently mix epochs
            del self._prefix[kk]
            self.prefix_stale_purged += 1
            return None
        self._prefix.move_to_end(kk)
        self.prefix_hits += 1
        self.pages_in += 1
        self.bytes_in += e.raw_bytes
        return self._decode(e)

    def purge_stale(self, epoch: int) -> int:
        """Drop every prefix entry not produced by ``epoch`` (called after
        a weight hot-swap). Paused pages are untouched: their K/V pairs
        with the sequence's own history, exactly like a live slot's ring
        page surviving a swap."""
        stale = [
            kk for kk, e in self._prefix.items() if e.epoch != int(epoch)
        ]
        for kk in stale:
            del self._prefix[kk]
        self.prefix_stale_purged += len(stale)
        return len(stale)

    def resident_prefixes(self, epoch: int) -> list:
        """``[[key, glen], ...]`` of epoch-valid prefix entries — the
        fleet advertisement payload (rides replica health frames; old
        peers ignore the extra field)."""
        return [
            [key, glen]
            for (key, glen), e in self._prefix.items()
            if e.epoch == int(epoch)
        ]

    # -- introspection -------------------------------------------------------

    @property
    def paused_count(self) -> int:
        return len(self._paused)

    @property
    def prefix_count(self) -> int:
        return len(self._prefix)

    def occupancy(self) -> float:
        return (len(self._paused) + len(self._prefix)) / self.host_slots

    def stored_bytes(self) -> int:
        return sum(
            len(e.payload_k) + len(e.payload_v)
            for e in list(self._paused.values()) + list(self._prefix.values())
        )

    def stats(self) -> dict:
        return {
            "codec": self.codec_name,
            "host_slots": self.host_slots,
            "paused": len(self._paused),
            "prefix_entries": len(self._prefix),
            "occupancy": round(self.occupancy(), 4),
            "pages_out": self.pages_out,
            "pages_in": self.pages_in,
            "bytes_out": self.bytes_out,
            "bytes_in": self.bytes_in,
            "stored_bytes": self.stored_bytes(),
            "prefix_stores": self.prefix_stores,
            "prefix_hits": self.prefix_hits,
            "prefix_dropped": self.prefix_dropped,
            "prefix_stale_purged": self.prefix_stale_purged,
        }
