"""In-process loopback backend: N worker threads, one shared world.

The testing analogue of the reference's loopback DHT swarm
(tests/test_diloco_hivemind.py:42-50) -- but deterministic and socket-free,
which the reference explicitly lacks (its straggler test is skipped as flaky,
test_diloco_hivemind.py:154-156). The whole DiLoCo algorithm runs against
this backend on CPU, making outer-loop logic unit-testable.

Elastic semantics match the production backend: a round completes when every
*live* peer has contributed; a peer that closes (drops) no longer blocks the
group, and the returned group size is the number of actual contributions --
so peer-drop detection (optimizer.py) is exercisable in tests.

It is also what workers that share a host run in production: a TPU host is
one process that owns all its chips, so its workers (or its one worker) form
their galaxy over a ``LoopbackWorld``. A round's data path therefore touches
each byte as few times as a mean needs (``_mean``): n contributions are read
once each and the result is written once -- the first contribution is copied
into the one output, the others are added in arrival order, the sum is
divided in place. The bits are those of ``np.sum(contribs, axis=0) / n``.

Who owns what:

- A caller's input arrays are read, never written, and never part of a
  result. With the identity codec they are contributed as they are (no round
  trip through bytes: nothing is lost on that wire, so nothing is modelled).
- A lossy codec's decode output is private to the round, so it may become
  the result: the accumulator of n > 1 contributions, or, for one
  contribution, the result itself with no pass at all.
- A published result is immutable. Every collector but the last copies it,
  outside the world's lock; the last one of a generation takes the arrays
  themselves. What ``all_reduce`` returns is the caller's alone, to keep and
  to write into, for as long as it keeps it.
- The world keeps the output arrays it hands out (``hostpool.OutputPool``:
  new pages are what a pass costs on a TPU host) and writes into one again
  only once nothing else refers to it, so the line above holds unchanged.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Optional

import numpy as np

from opendiloco_tpu import obs
from opendiloco_tpu.diloco import chaos
from opendiloco_tpu.diloco.backend import (
    AllReduceError,
    OuterBackend,
    PeerProgress,
)
from opendiloco_tpu.diloco.compression import Codec, get_codec, record_wire
from opendiloco_tpu.diloco.hostpool import OutputPool


class LoopbackWorld:
    """Shared state for an in-process swarm with elastic membership."""

    def __init__(self, n_peers: int, compression: str = "none"):
        self.n_peers = n_peers
        self.codec: Codec = get_codec(compression)
        self.lock = threading.Lock()
        self.cond = threading.Condition(self.lock)
        self.progress: dict[str, PeerProgress] = {}
        self.state_provider: Optional[Callable[[], dict[str, Any]]] = None
        self.live: set[str] = set()
        # all-reduce round state, keyed by round key (f"{tag}-epoch-{epoch}").
        # Keyed slots are what let streaming fragment sync run several
        # tagged rounds CONCURRENTLY through one world; each slot carries
        # its own generation counter because keys legitimately repeat
        # (tag "state" resolves epoch from the peer's own progress, which
        # stays put across back-to-back state-averaging rounds).
        self._rounds: dict[str, dict] = {}
        # gossip round state: round_key -> {"_partition": [...], chunk: {...}}
        self._gossip: dict = {}
        # pair-exchange mailboxes: round_key -> {peer_id: (meta, payload)}
        # (NoLoCo gossip, diloco/gossip.py); "_taken" tracks pickup for GC
        self._pairbox: dict[str, dict] = {}
        # async-gossip offer board: frag_id -> {peer_id: offer}; an offer
        # is claimed ATOMICALLY under this lock (claimer pops it and sets
        # its "result"), so two claimers can never grab the same partner
        self._offers: dict[int, dict[str, dict]] = {}
        self._async_seq = 0  # match-key nonce (repeat matches never collide)
        # the rounds' output arrays, kept across rounds (under self.lock):
        # a round hands out n per position, and a consumer may still hold
        # the last round's while the next is written. A position is a
        # round's tag and an array's index in its call: the blocking
        # boundary calls once a piece (``grads-p0``, ``grads-p1``, ...), each
        # call's arrays starting at index 0, and by index alone pieces that
        # hold same-shaped leaves would push each other's arrays out
        self._outputs = OutputPool(keep=2 * max(1, n_peers))

    def make_backends(self) -> list["LoopbackBackend"]:
        return [LoopbackBackend(self, f"peer-{i}") for i in range(self.n_peers)]


class LoopbackBackend(OuterBackend):
    def __init__(self, world: LoopbackWorld, peer_id: str):
        self.world = world
        self._peer_id = peer_id
        # round health ledger, same shape as TcpBackend's: loopback is the
        # oracle the chaos tests hold the TCP rescaling math against
        self.round_ledger: list[dict] = []
        self.last_round_health: dict = {}
        with world.lock:
            world.live.add(peer_id)

    def _chaos_gate(self) -> None:
        """Chaos hooks for the in-process backend: straggler/latency sleeps,
        plus transient contribution failures retried with the same bounded
        backoff the TCP round retry uses. Zero-cost when ODTP_CHAOS unset."""
        cp = chaos.plane()
        if cp is None:
            return
        d = cp.straggle_s() + cp.delay_s("loopback")
        if d:
            time.sleep(d)
        attempt = 0
        while cp.drop_conn("loopback"):
            time.sleep(min(chaos.backoff_s(attempt), 1.0))
            attempt += 1

    def _record_round_health(self, tag, epoch, group: int) -> None:
        expected = self.world.n_peers
        health = {
            "round": f"{tag}-epoch-{epoch}",
            "group_size": group,
            "expected": expected,
            "elastic": bool(group < expected),
            "retries": 0,
        }
        self.last_round_health = health
        self.round_ledger.append(health)
        if len(self.round_ledger) > 256:
            del self.round_ledger[:-256]
        tr = obs.tracer()
        if tr is not None:
            tr.instant("outer/round", worker=self._peer_id, **health)
            tr.count("outer_rounds")
            if health["elastic"]:
                tr.count("outer_rounds_elastic")

    @property
    def peer_id(self) -> str:
        return self._peer_id

    def num_peers(self) -> int:
        with self.world.lock:
            return len(self.world.live)

    def gossip_view(self):
        with self.world.lock:
            return sorted(self.world.live), None

    def pair_exchange(self, payload, meta, *, partner_id, round_key,
                      timeout=None):
        """Symmetric push-pull through a keyed in-world mailbox: deposit
        own frame, wait for the partner's. Partner close() mid-round (or a
        divergent pairing putting the partner on a different key) resolves
        as AllReduceError — the gossip plane's dropped-round non-event."""
        self._chaos_gate()
        w = self.world
        deadline = time.monotonic() + (timeout if timeout else 60.0)
        with w.cond:
            slot = w._pairbox.setdefault(round_key, {"_taken": set()})
            slot[self._peer_id] = (dict(meta), bytes(payload))
            w.cond.notify_all()
            while partner_id not in slot:
                if partner_id not in w.live:
                    slot.pop(self._peer_id, None)
                    self._pairbox_gc(round_key)
                    raise AllReduceError(
                        f"gossip partner {partner_id} left mid-round "
                        f"({round_key})"
                    )
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    slot.pop(self._peer_id, None)
                    self._pairbox_gc(round_key)
                    raise AllReduceError(
                        f"gossip pair round {round_key} timed out waiting "
                        f"for {partner_id}"
                    )
                w.cond.wait(timeout=min(remaining, 0.1))
            p_meta, p_payload = slot[partner_id]
            slot["_taken"].add(self._peer_id)
            self._pairbox_gc(round_key)
        return p_meta, p_payload

    def async_pair_match(self, *, frag_id, epoch, window, patience=None):
        """Bounded-staleness matchmaking through the in-world offer board.

        Claim the closest-epoch standing offer within ``window`` if one
        exists (deterministic tie-break by peer id); otherwise post our
        own offer and wait up to ``patience`` to be claimed. The claimer
        mints the match key, so both sides leave with the identical key
        and the transfer rides the ordinary ``pair_exchange`` mailbox.
        """
        w = self.world
        deadline = time.monotonic() + (patience if patience else 5.0)
        with w.cond:
            board = w._offers.setdefault(int(frag_id), {})
            cands = sorted(
                (abs(int(epoch) - o["epoch"]), pid)
                for pid, o in board.items()
                if pid != self._peer_id and o["result"] is None
                and pid in w.live
                and abs(int(epoch) - o["epoch"]) <= int(window)
            )
            if cands:
                _, pid = cands[0]
                other = board.pop(pid)
                w._async_seq += 1
                lo, hi = sorted((self._peer_id, pid))
                match_key = (
                    f"async-f{int(frag_id)}:{lo}|{hi}:{w._async_seq}"
                )
                other["result"] = (self._peer_id, int(epoch), match_key)
                w.cond.notify_all()
                return pid, other["epoch"], match_key
            offer: dict = {"epoch": int(epoch), "result": None}
            board[self._peer_id] = offer
            w.cond.notify_all()
            while offer["result"] is None:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                w.cond.wait(timeout=min(remaining, 0.05))
            # withdraw if still standing (a claimer pops matched offers)
            if board.get(self._peer_id) is offer:
                board.pop(self._peer_id, None)
            return offer["result"]

    def _pairbox_gc(self, round_key: str) -> None:
        """Under world.lock: drop a fully-consumed (or abandoned) slot and
        cap the box so dropped rounds' deposits cannot accumulate."""
        box = self.world._pairbox
        slot = box.get(round_key)
        if slot is not None:
            deposited = set(slot) - {"_taken"}
            if not deposited or deposited <= slot["_taken"]:
                box.pop(round_key, None)
        while len(box) > 256:
            box.pop(next(iter(box)))

    def all_reduce(self, arrays, *, timeout=None, tag="grads", epoch=None, group_cap=0):
        """Average across live peers. The round completes when every live
        peer has contributed; dropped peers stop blocking the group the
        moment they close(). Lossy codecs are applied to each contribution
        to model wire compression faithfully. ``group_cap`` partitions the
        live peers into deterministic per-round groups (gossip mode).

        ``arrays`` are read and never written, and no result aliases them.
        The returned arrays are float32 and the caller's own: it may keep
        them across later rounds and write into them (the world writes into
        one again only after the caller has let go of it). A round of n
        contributions costs n reads and one write for the mean (``_mean``),
        and one copy for each collector but the last, made outside the
        world's lock; one peer with the identity codec pays one copy in all,
        one peer with a lossy codec none. The arrays written are ones the
        world keeps from round to round under ``tag`` and the array's index
        in the call: a caller that cuts a round into several calls (the
        blocking boundary's pieces) gives each its own tag, and from the
        second round on no call touches a new page."""
        self._chaos_gate()
        # TcpBackend key parity: epoch=None resolves to this peer's own
        # reported epoch (default 0). Rounds are KEYED now — a raw None in
        # the key would split a round between callers that pass the epoch
        # explicitly (the optimizer) and ones that don't (state averaging,
        # tests), where the old single-slot world happily mixed them.
        if epoch is None:
            with self.world.lock:
                own = self.world.progress.get(self._peer_id)
            epoch = own.epoch if own else 0
        w = self.world
        # per-worker stage spans mirror the TCP stage names: encode (the
        # codec's round trip; the identity codec has none), reduce_wait
        # (park until the round mean publishes), reduce (the publishing
        # peer's computation of the mean), adopt (copy the published result)
        tr = obs.tracer()
        round_key = f"{tag}-epoch-{epoch}"
        t0 = time.perf_counter() if tr is not None else 0.0
        mine = _contribution(w.codec, arrays)
        if tr is not None:
            tr.add_span(
                "outer/encode", t0, time.perf_counter(),
                worker=self._peer_id, round=round_key,
            )
        deadline = time.monotonic() + (timeout or 3600.0)
        t_wait = time.perf_counter() if tr is not None else 0.0

        def take(i, like):  # under w.lock
            return w._outputs.take((tag, i), like)

        with w.cond:
            if group_cap:
                pub, last = self._group_round(
                    mine, round_key, group_cap, deadline, take
                )
            else:
                pub, last = self._world_round(
                    mine, round_key, deadline, t_wait, take
                )
            # the result is immutable once published: take the reference
            # here and copy after the lock is released, so that n peers copy
            # side by side and other tags' rounds are not held up behind a
            # gigabyte memcpy. The generation's last collector takes the
            # arrays themselves, once no copy of them is still in flight.
            t_adopt = time.perf_counter() if tr is not None else 0.0
            if last:
                while pub.readers:
                    w.cond.wait(timeout=0.1)
                result = pub.arrays
            else:
                pub.readers += 1
                result = [take(i, a) for i, a in enumerate(pub.arrays)]
        if not last:
            try:
                for dst, src in zip(result, pub.arrays):
                    np.copyto(dst, src)
            finally:
                with w.cond:
                    pub.readers -= 1
                    w.cond.notify_all()
        if tr is not None:
            tr.add_span(
                "outer/adopt", t_adopt, time.perf_counter(),
                worker=self._peer_id, round=round_key, copied=not last,
            )
        self._record_round_health(tag, epoch, pub.group)
        return result, pub.group

    def _publish(self, contribs, round_key, take) -> "_Published":
        """Under world.lock: the mean of ``contribs`` (in the order given)
        in arrays from ``take(i, like)``, with the ``outer/reduce`` span of
        the peer that computes it."""
        w = self.world
        tr = obs.tracer()
        t0 = time.perf_counter() if tr is not None else 0.0
        arrays, path, nbytes = _mean(contribs, not _is_identity(w.codec), take)
        if tr is not None:
            tr.add_span(
                "outer/reduce", t0, time.perf_counter(),
                worker=self._peer_id, round=round_key, group=len(contribs),
                path=path, bytes=nbytes,
            )
        return _Published(arrays, len(contribs), t0)

    def _world_round(self, mine, round_key, deadline, t_wait, take):
        """Under world.lock: contribute to the round of every live peer and
        wait for its mean. -> (published result, whether this peer is the
        last of its generation to collect it)."""
        w = self.world
        tr = obs.tracer()
        slot = w._rounds.setdefault(
            round_key,
            {
                "round": 0,
                "contrib": {},
                "result": None,
                "result_round": -1,
                "pending": set(),
            },
        )
        my_round = slot["round"]
        slot["contrib"][self._peer_id] = mine
        w.cond.notify_all()
        t_reduce = None  # set by the one peer that computes the mean
        while slot["result_round"] < my_round:
            if set(slot["contrib"]) >= w.live and slot["contrib"]:
                # complete: first thread to notice publishes the mean, the
                # contributions taken in arrival order
                slot["result"] = self._publish(
                    list(slot["contrib"].values()), round_key, take
                )
                t_reduce = slot["result"].t_reduce
                slot["result_round"] = my_round
                slot["round"] += 1
                # collectors of this generation (slot GC: the key's
                # state is dropped once every contributor -- or its
                # survivor set, if some died -- has collected the result)
                slot["pending"] = set(slot["contrib"])
                slot["contrib"] = {}
                w.cond.notify_all()
                break
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                # give up: retract our contribution so a later round
                # doesn't count a stale tensor from a dead peer
                slot["contrib"].pop(self._peer_id, None)
                w.cond.notify_all()
                raise AllReduceError(f"{self._peer_id}: all-reduce timed out")
            w.cond.wait(timeout=min(remaining, 0.1))
        if tr is not None:
            # reduce_wait is waiting and nothing else: the publishing
            # peer's own computation of the mean is outer/reduce
            tr.add_span(
                "outer/reduce_wait", t_wait,
                time.perf_counter() if t_reduce is None else t_reduce,
                worker=self._peer_id, round=round_key,
            )
        pub = slot["result"]
        # GC: keys repeat across epochs (and tags multiply with
        # streaming fragments) -- drop the slot once every live
        # contributor has collected and no next generation has begun
        slot["pending"] = {
            p for p in slot["pending"]
            if p != self._peer_id and p in w.live
        }
        last = not slot["pending"]
        if last and not slot["contrib"]:
            w._rounds.pop(round_key, None)
        return pub, last

    def _group_round(self, mine, key, cap, deadline, take):
        """Under world.lock: partition live peers into per-round groups of
        <= cap and average within the group only (mirrors the rendezvous
        daemon's capped matchmaking). The FIRST arriver freezes the
        partition for the round so later joiners and membership churn can't
        split the groups. -> (published result, whether this peer is the
        last of its group to collect it)."""
        import random

        w = self.world
        round_state = w._gossip.setdefault(key, {})
        if "_partition" not in round_state:
            members = sorted(w.live)
            random.Random(key).shuffle(members)
            round_state["_partition"] = [
                tuple(sorted(members[i : i + cap]))
                for i in range(0, len(members), cap)
            ]
        group = next(
            (g for g in round_state["_partition"] if self._peer_id in g), None
        )
        if group is None:
            # the partition was frozen before we were live: behave like
            # the TCP client's "group does not contain self" retry path
            raise AllReduceError(f"{self._peer_id}: not in gossip partition")
        slot = round_state.setdefault(
            group, {"contrib": {}, "done": set(), "result": None}
        )
        slot["contrib"][self._peer_id] = mine
        w.cond.notify_all()
        while True:
            live_members = [
                m for m in group if m in w.live or m in slot["contrib"]
            ]
            if set(slot["contrib"]) >= set(live_members):
                if slot["result"] is None:
                    # first member to notice publishes, in group order
                    slot["result"] = self._publish(
                        [slot["contrib"][m] for m in live_members], key, take
                    )
                slot["done"].add(self._peer_id)
                last = slot["done"] >= set(live_members)
                if last:
                    round_state.pop(group, None)
                    if not any(isinstance(k, tuple) for k in round_state):
                        w._gossip.pop(key, None)
                return slot["result"], last
            if time.monotonic() >= deadline:
                slot["contrib"].pop(self._peer_id, None)
                w.cond.notify_all()
                raise AllReduceError(f"{self._peer_id}: gossip round timed out")
            w.cond.wait(timeout=0.1)

    def report_progress(self, progress: PeerProgress) -> None:
        with self.world.lock:
            self.world.progress[progress.peer_id] = progress

    def peer_progress(self) -> list[PeerProgress]:
        with self.world.lock:
            live = self.world.live
            return [p for pid, p in self.world.progress.items() if pid in live]

    def fetch_state(self):
        with self.world.lock:
            provider = self.world.state_provider
        return provider() if provider else None

    def serve_state(self, get_state) -> None:
        with self.world.lock:
            self.world.state_provider = get_state

    def close(self) -> None:
        """Drop out of the swarm: stop blocking in-flight rounds."""
        with self.world.cond:
            self.world.live.discard(self._peer_id)
            self.world.progress.pop(self._peer_id, None)
            self.world.cond.notify_all()


class _Published:
    """One generation's mean. Immutable from the moment it is published:
    collectors copy it outside the world's lock, and ``readers`` counts the
    copies in flight, so that the last collector is not handed ``arrays``
    to write into while another still reads them."""

    __slots__ = ("arrays", "group", "readers", "t_reduce")

    def __init__(self, arrays: list[np.ndarray], group: int, t_reduce: float):
        self.arrays = arrays
        self.group = group
        self.readers = 0
        self.t_reduce = t_reduce  # where the publisher's waiting ended


def _is_identity(codec: Codec) -> bool:
    """The ``none`` codec (the base class): a memoryview out, a view back."""
    return type(codec) is Codec


def _contribution(codec: Codec, arrays) -> list[np.ndarray]:
    """What one peer puts into a round. A lossy codec's round trip is the
    modelled wire loss, and its decode output belongs to the round. The
    identity codec loses nothing, so the caller's arrays go in as they are
    (as float32), borrowed: ``_mean`` only reads them."""
    identity = _is_identity(codec)
    out = []
    for a in arrays:
        if identity:
            a = np.asarray(a, np.float32)
            wire_bytes = a.nbytes
        else:
            payload, meta = codec.encode(a)
            wire_bytes = len(payload)
            a = codec.decode(payload, a.shape, meta)
        record_wire(codec.name, a.size * 4, wire_bytes)
        out.append(a)
    return out


def _mean(
    contribs: list[list[np.ndarray]], private: bool, take
) -> tuple[list[np.ndarray], str, int]:
    """The mean of n contributions, array by array, in n reads and one
    write: -> (arrays, path, bytes written). Bit for bit
    ``np.sum([c[i] for c in contribs], axis=0) / n``, which stacks the list
    into a fresh (n, ...) array, reduces that into a second and divides
    into a third (numpy adds the rows of such a stack one after another,
    which is what the loop below does).

    ``private`` says the contributions are the round's own (decode outputs
    of a lossy codec): then the first one is the accumulator, and a single
    one is the mean itself (path ``handover``: no pass at all). Borrowed
    contributions (the identity codec's: the callers' arrays) are only
    read: the first is copied into an output array that ``take(i, like)``
    provides, laid out in memory as the contribution is, so that the copy
    is a flat one (path ``copy`` when it is the only contribution,
    ``accumulate`` otherwise)."""
    n = len(contribs)
    first = contribs[0]
    if n == 1 and private and all(_accumulator(a) for a in first):
        return first, "handover", 0
    out = []
    for i, a in enumerate(first):
        parts = [c[i] for c in contribs]
        if any(p.shape != a.shape for p in parts):
            raise ValueError(
                f"all-reduce array {i}: contributions differ in shape: "
                f"{[p.shape for p in parts]}"
            )
        if a.size == 1:
            # a sum over one element per row is the one case numpy reduces
            # pairwise (from 8 rows on): keep the expression, it is free
            out.append(np.asarray(np.sum(parts, axis=0) / n, np.float32))
            continue
        if private and _accumulator(a):
            acc = a
        else:
            acc = take(i, a)
            np.copyto(acc, a)
        for p in parts[1:]:
            np.add(acc, p, out=acc)
        if n > 1:
            acc /= n
        out.append(acc)
    return out, "copy" if n == 1 else "accumulate", sum(a.nbytes for a in out)


def _accumulator(a: np.ndarray) -> bool:
    """Whether a private array can be summed into and handed out."""
    return a.dtype == np.float32 and a.flags.writeable
