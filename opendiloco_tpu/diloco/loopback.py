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
        live peers into deterministic per-round groups (gossip mode)."""
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
        if group_cap:
            out, n = self._group_reduce(arrays, tag, epoch, group_cap, timeout)
            self._record_round_health(tag, epoch, n)
            return out, n
        w = self.world
        codec = w.codec
        # per-worker stage spans mirror the TCP stage names: encode (codec
        # roundtrip), reduce_wait (park until the round mean publishes),
        # adopt (copy the published result)
        tr = obs.tracer()
        round_key = f"{tag}-epoch-{epoch}"
        t0 = time.perf_counter() if tr is not None else 0.0
        compressed = [
            codec.decode(*_enc(codec, a)) for a in arrays
        ]  # simulate wire roundtrip
        if tr is not None:
            tr.add_span(
                "outer/encode", t0, time.perf_counter(),
                worker=self._peer_id, round=round_key,
            )
        deadline = time.monotonic() + (timeout or 3600.0)
        t_wait = time.perf_counter() if tr is not None else 0.0
        with w.cond:
            slot = w._rounds.setdefault(
                round_key,
                {
                    "round": 0,
                    "contrib": {},
                    "result": None,
                    "result_group": 0,
                    "result_round": -1,
                    "pending": set(),
                },
            )
            my_round = slot["round"]
            slot["contrib"][self._peer_id] = compressed
            w.cond.notify_all()
            t_reduce = None  # set by the one peer that computes the mean
            while slot["result_round"] < my_round:
                if set(slot["contrib"]) >= w.live and slot["contrib"]:
                    # complete: first thread to notice publishes the mean
                    t_reduce = time.perf_counter() if tr is not None else 0.0
                    contribs = list(slot["contrib"].values())
                    n = len(contribs)
                    slot["result"] = [
                        np.sum([c[i] for c in contribs], axis=0) / n
                        for i in range(len(arrays))
                    ]
                    slot["result_group"] = n
                    slot["result_round"] = my_round
                    slot["round"] += 1
                    # collectors of this generation (slot GC: the key's
                    # state is dropped once every contributor -- or its
                    # survivor set, if some died -- has copied the result)
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
                now = time.perf_counter()
                tr.add_span(
                    "outer/reduce_wait", t_wait,
                    now if t_reduce is None else t_reduce,
                    worker=self._peer_id, round=round_key,
                )
                if t_reduce is not None:
                    tr.add_span(
                        "outer/reduce", t_reduce, now,
                        worker=self._peer_id, round=round_key, group=n,
                    )
            t_adopt = time.perf_counter() if tr is not None else 0.0
            result = [a.copy() for a in slot["result"]]
            group = slot["result_group"]
            # GC: keys repeat across epochs (and tags multiply with
            # streaming fragments) -- drop the slot once every live
            # contributor has collected and no next generation has begun
            slot["pending"] = {
                p for p in slot["pending"]
                if p != self._peer_id and p in w.live
            }
            if not slot["pending"] and not slot["contrib"]:
                w._rounds.pop(round_key, None)
        if tr is not None:
            tr.add_span(
                "outer/adopt", t_adopt, time.perf_counter(),
                worker=self._peer_id, round=round_key,
            )
        self._record_round_health(tag, epoch, group)
        return result, group

    def _group_reduce(self, arrays, tag, epoch, cap, timeout):
        """Partition live peers into per-round groups of <= cap and average
        within the group only (mirrors the rendezvous daemon's capped
        matchmaking). The FIRST arriver freezes the partition for the round
        so later joiners and membership churn can't split the groups."""
        import random

        w = self.world
        codec = w.codec
        key = f"{tag}-epoch-{epoch}"
        compressed = [codec.decode(*_enc(codec, a)) for a in arrays]
        deadline = time.monotonic() + (timeout or 3600.0)
        with w.cond:
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
            slot = round_state.setdefault(group, {"contrib": {}, "done": set()})
            slot["contrib"][self._peer_id] = compressed
            w.cond.notify_all()
            while True:
                live_members = [
                    m for m in group if m in w.live or m in slot["contrib"]
                ]
                if set(slot["contrib"]) >= set(live_members):
                    contribs = [slot["contrib"][m] for m in live_members]
                    n = len(contribs)
                    result = [
                        np.sum([c[i] for c in contribs], axis=0) / n
                        for i in range(len(arrays))
                    ]
                    slot["done"].add(self._peer_id)
                    if slot["done"] >= set(live_members):
                        round_state.pop(group, None)
                        if not any(
                            isinstance(k, tuple) for k in round_state
                        ):
                            w._gossip.pop(key, None)
                    return [a.copy() for a in result], n
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


def _enc(codec: Codec, a: np.ndarray):
    payload, meta = codec.encode(a)
    record_wire(codec.name, a.size * 4, len(payload))
    return payload, a.shape, meta
