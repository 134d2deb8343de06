"""DiLoCoOptimizer: the algorithm orchestrator.

TPU-native re-design of the reference's ``DiLoCoOptimizer``
(open_diloco/hivemind_diloco.py:303-738) with the normative update rule of
the pure-torch driver (open_diloco/train_diloco_torch.py:336-353):

  every step:        inner AdamW step on device (jit, sharded)
  every local_steps: pseudo_grad = master - device_params        [D2H]
                     averaged    = backend.all_reduce(pseudo_grad)  [DCN]
                     outer Nesterov SGD updates host master
                     device_params <- master                     [H2D]

The master copy lives in host RAM as float32 numpy (the equivalent of
hivemind's CPU-offloaded outer optimizer, hivemind_diloco.py:399-400,
158-167). The inner jit step never changes shape/sharding across the outer
boundary, so the 500-step inner phases never recompile.
"""

from __future__ import annotations

import concurrent.futures
import functools
import queue
import threading
import time
from typing import TYPE_CHECKING, Any, Optional

import jax
import numpy as np

from opendiloco_tpu import native, obs
from opendiloco_tpu.config import DilocoConfig
from opendiloco_tpu.diloco import outer_device, planner
from opendiloco_tpu.diloco.backend import OuterBackend, PeerProgress, wait_for_peers
from opendiloco_tpu.diloco.compression import get_codec
from opendiloco_tpu.diloco.error_feedback import ErrorFeedback
from opendiloco_tpu.diloco.gossip import GossipPlane
from opendiloco_tpu.diloco.outer_device import DeviceOuterPlane, PutBack
from opendiloco_tpu.diloco.outer_optimizer import OuterSGD, noloco_step
from opendiloco_tpu.diloco.streaming import StreamScheduler
from opendiloco_tpu.parallel.world import HostWorld

if TYPE_CHECKING:  # annotation-only: a module-level import would close the
    # trainer -> obs -> diloco.schema -> diloco.optimizer -> trainer cycle
    from opendiloco_tpu.trainer import InnerTrainer
from opendiloco_tpu.utils.debug import schema_fingerprint
from opendiloco_tpu.utils.logger import get_text_logger

log = get_text_logger(__name__)

@functools.partial(jax.jit, donate_argnums=(0,))
def _frag_add(cur, delta):
    """params += delta over one fragment's leaves (streaming landing/
    launch). The old param buffers are donated — the caller rebinds the
    fragment entries to the fresh outputs, so they are dead either way."""
    return [a + b for a, b in zip(cur, delta)]


# join-keepalive cadence: must beat the rendezvous registration TTL (60 s
# default in both daemons) so a worker stuck in its first multi-minute XLA
# compile is never reaped as dead before taking a step
_ANNOUNCE_INTERVAL_S = 15.0


class PeerDropError(RuntimeError):
    """Raised when a DiLoCo worker disappears and fail_rank_drop is set
    (reference: train_fsdp.py:452-457)."""


def _piece_tag(k: int, n: int) -> str:
    """The all-reduce tag of piece ``k`` of a blocking round cut into ``n``:
    a tag a piece, not one tag called n times, because a backend's results
    are views it reclaims at the next call under the SAME tag
    (``TcpBackend.all_reduce``), and the way back is still reading piece k's
    when piece k + 1's round opens. A round in one piece keeps ``grads``."""
    return "grads" if n == 1 else f"grads-p{k}"


class _WireRound:
    """One blocking round's all-reduces, the same under either placement: a
    call a piece under the piece's own tag (``_piece_tag``), all of them
    under ONE deadline (``averaging_timeout`` from the first call's start,
    so a round cut into n pieces cannot take n times as long), their seconds
    summed and their health rows folded into one.

    A peer that drops between two pieces leaves the later ones a smaller
    group. Every survivor sees the same groups (the rendezvous decides them),
    each leaf is the mean of those that contributed it, and the round
    completes as the elastic round it is: ``group_size()`` is the smallest
    piece's, which is what ``_check_group_size`` (and ``fail_rank_drop``)
    then sees. A piece that raises fails the round, as a failed all-reduce
    always did."""

    def __init__(self, opt: "DiLoCoOptimizer", n_pieces: int):
        self._opt, self._n = opt, n_pieces
        self._deadline: Optional[float] = None
        self._seen: Any = None
        self.seconds = 0.0
        self.sizes: list[int] = []
        self.health: dict = {}

    def reduce(self, k: int, arrays: list[np.ndarray]) -> list[np.ndarray]:
        opt = self._opt
        now = time.monotonic()
        if self._deadline is None:
            self._deadline = now + opt.cfg.averaging_timeout
        # what is left of the round's time, and never nothing: the backend
        # does the timing out (under multihost it is the messenger's failure
        # that the whole slice raises on, in lockstep)
        left = max(self._deadline - now, 1.0)
        t1 = time.perf_counter()
        averaged, n, _ = opt._wan_all_reduce(
            arrays, timeout=left, epoch=opt.epoch, tag=_piece_tag(k, self._n)
        )
        t2 = time.perf_counter()
        self.seconds += t2 - t1
        self.sizes.append(n)
        self._fold(getattr(opt.backend, "last_round_health", None))
        tr = obs.tracer()
        if tr is not None:
            tr.add_span(
                "outer/allreduce", t1, t2, epoch=opt.epoch, group=n,
                piece=k, bytes=sum(a.nbytes for a in arrays),
            )
        return averaged

    def _fold(self, health: Optional[dict]) -> None:
        if not health or health is self._seen:
            return  # the backend keeps no health, or wrote none for this call
        self._seen = health
        was = self.health
        # the plan fields (link_plan, link_shares, hier) are the last piece's
        self.health = {
            **health,
            "elastic": bool(was.get("elastic") or health.get("elastic")),
            "expected": max(was.get("expected", 0), health.get("expected", 0)),
            "retries": was.get("retries", 0) + (health.get("retries") or 0),
        }

    def group_size(self) -> int:
        n = min(self.sizes)
        if n != max(self.sizes):
            log.warning(
                "outer step %d: a peer dropped between the round's pieces "
                "(group sizes %s); the round completes with %d",
                self._opt.epoch, self.sizes, n,
            )
            self.health["elastic"] = True
            self.health["expected"] = max(
                self.health.get("expected", 0), max(self.sizes)
            )
        return n


class _BoundaryFetch(threading.Thread):
    """The boundary's device-to-host fetch on a thread of its own, so that it
    overlaps the straggler wait. It times itself: ``seconds`` is the fetch's
    own interval (the row's ``outer_d2h_s``), and with an armed tracer the
    ``outer/d2h`` span is recorded from this thread as the fetch ends.
    ``stats`` (the device plane's ``last_fetch``) is asked, once the fetch
    has ended, what it did: ``bytes`` and ``shards`` assembled on the host
    and ``new_bytes``, those of them written into arrays allocated in this
    round -- attributes of the span, and ``outer_d2h_new_bytes`` in the row.

    ``piecewise`` (the blocking device-placement boundary): ``fetch`` is
    called with a ``deliver(k, arrays)`` to hand each piece on as it lands,
    and ``arrivals()`` yields them to the boundary's next stage while later
    pieces are still on their way. The fetch's interval is then cut into one
    ``outer/d2h`` span a piece (from the piece before's landing to its own),
    each with ``piece`` and ``bytes``, the last with the whole fetch's
    ``shards`` and ``new_bytes`` as well."""

    def __init__(self, tr, epoch: int, fetch, stats=None, piecewise: bool = False):
        super().__init__(name="outer-d2h")
        self._tr, self._epoch, self._fetch, self._stats = tr, epoch, fetch, stats
        self._result = self._error = None
        self.seconds = 0.0
        self.stats: dict = {}
        self._arrived: Optional[queue.SimpleQueue] = (
            queue.SimpleQueue() if piecewise else None
        )
        self._landings: list[tuple] = []  # (piece, when, bytes)

    def _deliver(self, k: int, arrays: list) -> None:
        self._landings.append(
            (k, time.perf_counter(), sum(a.nbytes for a in arrays))
        )
        self._arrived.put((k, arrays))

    def run(self) -> None:
        t0 = time.perf_counter()
        try:
            if self._arrived is None:
                self._result = self._fetch()
            else:
                self._result = self._fetch(self._deliver)
            if self._stats is not None:
                self.stats = dict(self._stats())
        except BaseException as e:  # re-raised by wait(), in the caller
            self._error = e
        t1 = time.perf_counter()
        if self._landings:
            t1 = self._landings[-1][1]  # the last piece's landing
        self.seconds = t1 - t0
        if self._arrived is not None:
            self._arrived.put(None)  # the fetch has ended, well or not
        if self._tr is None:
            return
        if self._arrived is None:
            self._tr.add_span(
                "outer/d2h", t0, t1, epoch=self._epoch, **self.stats
            )
            return
        # what the whole fetch assembled rides the last piece's span
        whole = {k: v for k, v in self.stats.items() if k != "bytes"}
        for k, when, nbytes in self._landings:
            last = (k, when, nbytes) == self._landings[-1]
            self._tr.add_span(
                "outer/d2h", t0, when, epoch=self._epoch, piece=k, bytes=nbytes,
                **(whole if last else {}),
            )
            t0 = when

    def arrivals(self):
        """The pieces as they land, in their order: ``(k, host arrays)``.
        Ends when the fetch has; a fetch that raised raises here."""
        while (item := self._arrived.get()) is not None:
            yield item
        if self._error is not None:
            raise self._error

    def row(self) -> dict:
        """The fetch's part of the optimizer's row."""
        out = {"outer_d2h_s": self.seconds}
        if "new_bytes" in self.stats:
            out["outer_d2h_new_bytes"] = self.stats["new_bytes"]
        if self._arrived is not None:
            out["outer_pieces"] = len(self._landings)
        return out

    def wait(self):
        """Join; -> what the fetch returned (its exception raised here)."""
        self.join()
        if self._error is not None:
            raise self._error
        return self._result


def resolve_outer_placement(cfg: DilocoConfig, trainer, world) -> str:
    """Resolve ``outer_placement`` to 'host' or 'device'.

    'auto' picks device on TPU meshes (the master fits HBM there; the host
    offload is a GPU-memory artifact of the reference) and host elsewhere.
    Device placement requires single-process meshes (the plane is not
    collective-aware) — multihost falls back to host with a warning
    rather than failing the run. Gossip composes: a pair round fetches
    only its fragment's leaves (host_frag) and lands the mixed result
    back through the plane's donated jits."""
    if cfg.outer_placement == "host":
        return "host"
    if cfg.outer_placement == "auto":
        dev = trainer.plan.mesh.devices.flat[0]
        if "tpu" not in getattr(dev, "device_kind", "").lower():
            return "host"
    if world.process_count > 1:
        log.warning(
            "outer_placement=device is single-process only (multihost "
            "slices replicate the host master); falling back to host"
        )
        return "host"
    return "device"


class DiLoCoOptimizer:
    """Owns inner trainer state transitions + the outer DiLoCo loop."""

    def __init__(
        self,
        trainer: InnerTrainer,
        backend: Optional[OuterBackend],
        cfg: DilocoConfig,
        state: dict,
        batch_size: int,
        world: Optional[HostWorld] = None,
    ):
        self.trainer = trainer
        # world-messenger split (reference train_fsdp.py:183,205-212): only
        # the messenger process of a multihost slice owns a WAN backend;
        # follower processes meet it at mesh collectives (parallel/world.py)
        self.world = world if world is not None else HostWorld()
        if self.world.is_messenger and backend is None:
            raise ValueError("the world-messenger process needs a backend")
        self.backend = backend if self.world.is_messenger else None
        self.cfg = cfg
        self.batch_size = batch_size
        self.target_samples = batch_size * cfg.local_steps

        # outer data plane placement: host numpy master (reference
        # hivemind offload semantics) or a device-resident plane
        # (diloco/outer_device.py) with fused, donated boundary ops
        self.placement = resolve_outer_placement(cfg, trainer, self.world)
        # host master copy (float32). Flatten once; treedef is stable.
        # Under multihost the gather is a mesh collective: every process of
        # the slice holds the identical full replica.
        flat_dev, self.treedef = jax.tree.flatten(state["params"])
        self._plane: Optional[DeviceOuterPlane] = None
        if self.placement == "device":
            self._plane = DeviceOuterPlane(
                trainer,
                flat_dev,
                lr=cfg.outer_lr,
                momentum=cfg.outer_momentum,
                nesterov=cfg.outer_nesterov,
                compression=cfg.compression,
                # gossip keeps its per-PARTNER EF ledgers host-side in the
                # GossipPlane (the pair wire encode happens on host); the
                # device plane's in-jit residual add is per-worker and
                # would mix partners' residuals into every pair round
                error_feedback=cfg.error_feedback
                and cfg.outer_mode != "gossip",
            )
            # the plane owns master + momentum; the host list stays empty
            # (every device-mode path goes through self._plane)
            self.master: list[np.ndarray] = []
        else:
            self.master = [
                np.array(x, dtype=np.float32)
                for x in self.world.gather_params(flat_dev)
            ]
        # error feedback (diloco/error_feedback.py): per-leaf residual of
        # the codec's quantization/sparsification error, folded into the
        # next round's pseudo-gradient before encoding. Device placement
        # fuses the residual add into the plane's pseudo-gradient jit and
        # stores the residuals in HBM; host placement adds in prepare().
        self._ef: Optional[ErrorFeedback] = None
        if cfg.error_feedback and cfg.outer_mode != "gossip":
            self._ef = ErrorFeedback(
                get_codec(cfg.compression),
                len(flat_dev),
                device_setter=(
                    self._plane.set_ef_residuals
                    if self._plane is not None
                    else None
                ),
            )
        self.outer_opt = OuterSGD(
            lr=cfg.outer_lr, momentum=cfg.outer_momentum, nesterov=cfg.outer_nesterov
        )

        # NoLoCo gossip plane (diloco/gossip.py): pair scheduling + the
        # point-to-point push-pull + per-partner error feedback. Messenger
        # process only — followers receive the mixed result via fanout.
        self._gossip: Optional[GossipPlane] = None
        if cfg.outer_mode == "gossip" and self.backend is not None:
            self._gossip = GossipPlane(
                self.backend,
                len(flat_dev),
                compression=cfg.compression,
                error_feedback=cfg.error_feedback,
            )

        self._schema = schema_fingerprint(state["params"])
        # streaming fragment sync (arxiv 2501.18512): size-balanced
        # contiguous partition of leaf indices, derived from the (shared)
        # schema so every peer computes the identical partition with no
        # coordination; fragment synced at epoch e is e mod N. Sizes come
        # from the device leaves (identical to the master shapes) so both
        # placements derive the same partition.
        self._fragments: Optional[list[list[int]]] = None
        if cfg.streaming_fragments > 1:
            leaf_sizes = [int(x.size) for x in flat_dev]
            n_frag = min(cfg.streaming_fragments, len(leaf_sizes))
            # cross-peer-critical: every peer must derive the SAME n_frag
            # non-empty fragments or the fragment all-reduces desync; the
            # planner raises explicitly when it cannot (a bare assert
            # would vanish under `python -O`)
            self._fragments = planner.fragment_partition(leaf_sizes, n_frag)
        self.epoch = 0  # completed outer steps
        self.local_step = 0  # inner steps within current epoch
        self.samples_in_epoch = 0
        self.max_num_peers = 1
        self._epoch_t0 = time.monotonic()
        self.last_outer_metrics: dict[str, Any] = {}

        # overlapped-communication state (arxiv 2502.12996): at most one
        # outer all-reduce in flight while inner training continues
        self._pending: Optional[dict[str, Any]] = None
        # pre-round snapshot served while the BLOCKING outer_step mutates
        # the master in place (OuterSGD.step is in-place): without it a peer
        # onboarding mid-round could fetch a torn master with mixed
        # pre/post-update leaves (hivemind's load_state_from_peers always
        # returns a consistent epoch snapshot, hivemind_diloco.py:528-531)
        self._blocking_snap: Optional[dict[str, Any]] = None
        # serializes state serving against round-boundary publications
        self._serve_lock = threading.Lock()
        self._abandoned: Optional[Any] = None  # dropped round still running
        self._landed_metrics: Optional[dict[str, Any]] = None
        self._apply_delta = None
        # how to lower the outer programs again (``program_texts``): one
        # list with the device plane's
        self._recipes = (
            self._plane.recipes if self._plane is not None else obs.programs.Recipes()
        )
        obs.programs.register(self)
        # fragment (None: all leaves) -> the pieces its blocking round runs in
        self._piece_cache: dict = {}
        # persistent pseudo-gradient buffers (reference: hivemind averages
        # into the outer optimizer's persistent grad buffers,
        # hivemind_diloco.py:68-119). Fresh model-sized allocations every
        # round hit kernel page-fault/compaction stalls at 1b scale; two
        # slots so the overlapped path never writes into buffers a wedged
        # abandoned round might still be streaming from
        self._pg_bufs: list[Optional[list[np.ndarray]]] = [None, None]
        # alternation is tracked explicitly, NOT by epoch parity: onboarding
        # (load_state_from_peers) teleports self.epoch to the swarm's value,
        # which could land the next round on the slot an abandoned round is
        # still streaming from
        self._pg_slot = 0

        # streaming x overlap (arxiv 2501.18512 + 2502.12996): staggered
        # in-phase fragment rounds with eager first-step estimates,
        # driven from a trainer post-dispatch hook so launches never
        # leave the inner loop. Single-process only (the scheduler lands
        # on the training thread and the device plane is not
        # collective-aware); multihost falls back to the blocking
        # fragment path, which outer_step already handles.
        self._stream: Optional[StreamScheduler] = None
        if self._fragments is not None and cfg.overlap_comm != "none":
            if self.world.process_count > 1:
                log.warning(
                    "streaming_fragments x overlap_comm is single-process "
                    "only; falling back to blocking fragment sync"
                )
            else:
                self._stream = StreamScheduler(self)
                trainer.add_post_dispatch_hook(self._stream_tick)

        if self.backend is not None:
            self.backend.serve_state(self._state_for_peers)
            # announce at join, BEFORE the first (slow) inner-step compile:
            # progress gossip is what makes this peer visible to the other
            # workers' WAIT_FOR_ALL polling (backend.py wait_for_peers). The
            # first in-step report only happens after the first train_step
            # returns (~minutes of XLA compile on a cold cache), and an
            # unannounced peer reads as "no other peers known" to a faster
            # worker, which then matchmakes a solo group — observed live on
            # TPU with two staggered 150m workers. The reference announces
            # tracker state on join (hivemind_diloco.py:174-282 progress
            # tracker starts reporting at construction). A single announce
            # is NOT enough: the rendezvous registration TTL (60 s default)
            # would expire during a multi-minute silent compile and the
            # daemon would reap the peer, so a background thread keeps
            # re-announcing until the first step() lands.
            try:
                self._announce(samples=0, sps=0.0)
            except Exception as e:  # never kill the joiner over gossip
                # same contract as the keepalive below: a flaky rendezvous
                # at construction time must not take down the worker — the
                # keepalive retries in seconds anyway
                log.warning("join announce failed: %s", e)
            self._first_step_evt = threading.Event()
            self._announce_lock = threading.Lock()
            # the keepalive pins the epoch it announced at JOIN: desync
            # onboarding teleports self.epoch to the swarm's value before
            # the first (slow) compile, and a keepalive announcing the
            # swarm epoch with samples=0 / sps=0 (eta inf) would stall
            # every established peer's WAIT_FOR_ALL for the full timeout;
            # announcing the join epoch keeps the compiling joiner behind
            # the >=2-epoch discount in backend.wait_for_peers until its
            # first real report
            join_epoch = self.epoch

            def _keepalive():
                failures = 0
                while not self._first_step_evt.wait(_ANNOUNCE_INTERVAL_S):
                    # check+announce atomic vs the first step's report: a
                    # tick already past wait() must not publish a stale
                    # samples=0 row AFTER the first in-step report landed
                    with self._announce_lock:
                        if self._first_step_evt.is_set():
                            return
                        try:
                            self._announce(samples=0, sps=0.0, epoch=join_epoch)
                            failures = 0
                        except Exception as e:  # never kill the joiner over gossip
                            failures += 1
                            log.warning("join keepalive announce failed: %s", e)
                            if failures >= 3:
                                # backend closed / rendezvous gone: stop
                                # warning forever; the in-step report path
                                # takes over if the worker ever steps
                                return

            t = threading.Thread(target=_keepalive, daemon=True)
            t.start()

    def _announce(
        self, *, samples: int, sps: float, epoch: Optional[int] = None
    ) -> None:
        """Report this peer's progress to the gossip fabric (the one
        construction site for PeerProgress: join announce, compile
        keepalive, and the in-step report all go through here)."""
        self.backend.report_progress(
            PeerProgress(
                peer_id=self.backend.peer_id,
                epoch=self.epoch if epoch is None else epoch,
                samples=samples,
                samples_per_second=sps,
                timestamp=time.time(),
            )
        )

    def _pseudo_grad_into(self, boundary: list, slot: int) -> list[np.ndarray]:
        """master - boundary, written into the persistent slot buffers."""
        bufs = self._pg_bufs[slot]
        if (
            bufs is None
            or len(bufs) != len(self.master)
            or any(b.shape != m.shape for b, m in zip(bufs, self.master))
        ):
            bufs = [np.empty(m.shape, np.float32) for m in self.master]
            self._pg_bufs[slot] = bufs
        return [
            native.sub(m, d, out=b)
            for m, d, b in zip(self.master, boundary, bufs)
        ]

    # ------------------------------------------------------------------
    # onboarding (reference: load_state_from_peers, train_fsdp.py:348-349)
    # ------------------------------------------------------------------

    def _state_refs_unlocked(self) -> tuple[list[np.ndarray], int, dict]:
        """(master, epoch, outer_opt state) as REFERENCES — no array copies.

        Safe to copy after the lock is released because every mutation path
        rebinds (fresh lists / cloned optimizers) instead of writing the
        published arrays in place; a captured reference stays bit-stable.
        """
        if self._pending is not None:
            # while a round is in flight, epoch is already advanced but the
            # master excludes that round's update; serve the consistent
            # pre-round snapshot so an onboarding peer never adopts a
            # (new epoch, old master) mismatch
            p = self._pending
            return p["master_snap"], p["epoch"], p["opt_snap"]
        snap = self._blocking_snap
        if snap is not None:
            # blocking outer step in progress: serve the consistent
            # pre-round snapshot, never the mid-round live master
            return snap["master"], snap["epoch"], snap["outer_opt"]
        return self.master, self.epoch, self.outer_opt.state_dict_refs()

    def _device_state_for_peers(self) -> dict[str, Any]:
        """Serve-thread snapshot in device placement: the host view is
        fetched lazily, only when a peer actually asks. Lock order is
        plane.lock -> _serve_lock everywhere. The pre-published host
        snapshot (state-averaging rounds) is checked first under
        _serve_lock alone so fetches never stall behind a WAN leg."""
        plane = self._plane
        with self._serve_lock:
            snap = self._blocking_snap
            if snap is not None:
                master = [m.copy() for m in snap["master"]]
                opt = snap["outer_opt"]
                bufs = opt.get("bufs")
                return {
                    "master": master,
                    "epoch": snap["epoch"],
                    "outer_opt": {
                        **{k: opt[k] for k in ("lr", "momentum", "nesterov")},
                        "bufs": None if bufs is None else [b.copy() for b in bufs],
                    },
                }
        # plane.lock held across the whole device fetch: the training
        # thread's donating apply deletes the old buffers, so a concurrent
        # device_get would read freed memory. Holding it also pins the
        # (masters, epoch) pair — every device-mode mutator advances the
        # epoch while still inside plane.lock.
        with plane.lock:
            with self._serve_lock:
                p = self._pending
                if p is not None and "plane_pre" in p:
                    # overlapped round in flight: epoch already advanced,
                    # plane possibly rebound to the eager estimate — serve
                    # the retained pre-round device arrays instead
                    m_refs, b_refs = p["plane_pre"]
                    epoch = p["epoch"]
                else:
                    m_refs, b_refs = plane.masters, plane.bufs
                    epoch = self.epoch
            master, bufs = plane.host_state((m_refs, b_refs))
        return {
            "master": master,
            "epoch": epoch,
            "outer_opt": {
                "lr": plane.lr,
                "momentum": plane.momentum,
                "nesterov": plane.nesterov,
                "bufs": bufs,
            },
        }

    def _state_for_peers(self) -> dict[str, Any]:
        if self._plane is not None:
            return self._device_state_for_peers()
        # the lock makes the flag checks + reference reads atomic against
        # the round-boundary publications (all of which also hold the lock):
        # without it, a fetch that passes the flag checks just before a
        # round completes could capture a (pre-round master, post-round
        # epoch) mix. The multi-GB array copies happen AFTER release so an
        # onboarding peer's fetch never blocks the training thread's
        # round-boundary publication (which needs the same lock).
        with self._serve_lock:
            master, epoch, opt_sd = self._state_refs_unlocked()
        bufs = opt_sd.get("bufs")
        return {
            "master": [m.copy() for m in master],
            "epoch": epoch,
            "outer_opt": {
                **opt_sd,
                "bufs": None if bufs is None else [b.copy() for b in bufs],
            },
        }

    # ------------------------------------------------------------------
    # serve-plane snapshot export (opendiloco_tpu/serve weight hot-swap)
    # ------------------------------------------------------------------

    def master_snapshot(
        self, wire_dtype: Optional[str] = None
    ) -> tuple[int, list[np.ndarray]]:
        """(epoch, master leaves) for the in-process serving plane — the
        weights-only sibling of ``_state_for_peers``: same epoch-consistency
        rules (pending / blocking rounds serve the pre-round snapshot), no
        momentum fetch, no array copies on the host path (mutators rebind,
        so captured references stay bit-stable).

        Device placement fetches under ``plane.lock``; ``wire_dtype``
        (plain-fp16 state codec only) narrows inside jit so the D2H copy
        moves half-width bytes."""
        plane = self._plane
        if plane is None:
            with self._serve_lock:
                master, epoch, _ = self._state_refs_unlocked()
            return epoch, list(master)
        # mirror _device_state_for_peers: the pre-published host snapshot
        # is served under _serve_lock alone so a swap pull never stalls
        # behind a blocking outer round's WAN leg
        with self._serve_lock:
            snap = self._blocking_snap
            if snap is not None:
                return snap["epoch"], [np.asarray(m) for m in snap["master"]]
        with plane.lock:
            with self._serve_lock:
                p = self._pending
                if p is not None and "plane_pre" in p:
                    m_refs, _ = p["plane_pre"]
                    epoch = p["epoch"]
                else:
                    m_refs, epoch = plane.masters, self.epoch
            masters = plane.host_masters(m_refs, wire_dtype=wire_dtype)
        return epoch, masters

    def master_snapshot_wire(self) -> tuple[int, list[tuple], str]:
        """Codec-encoded master snapshot: (epoch, blobs, codec_name) with
        ``blobs[i] = (payload, meta, shape)`` per master leaf in params
        flatten order — the serve engine's hot-swap feed.

        Reuses the onboarding ``state_codec`` (fp16 by default,
        ``ODTP_STATE_CODEC`` overrides) so a swap transfer moves
        half-width bytes, and the device plane pre-casts the D2H fetch to
        wire width when the codec's encode is idempotent under it."""
        from opendiloco_tpu.diloco.compression import device_wire_dtype, get_codec
        from opendiloco_tpu.diloco.tcp import state_codec

        codec = state_codec(get_codec(self.cfg.compression))
        epoch, masters = self.master_snapshot(
            wire_dtype=device_wire_dtype(codec.name)
        )
        blobs = []
        for m in masters:
            flat = np.ascontiguousarray(m).reshape(-1)
            payload, meta = codec.encode(flat)
            blobs.append((payload, meta, tuple(m.shape)))
        return epoch, blobs, codec.name

    def _broadcast_remote_state(self, remote: Optional[dict]) -> Optional[dict]:
        """Fan a fetched swarm state from the messenger to every process of
        the slice (collective: all processes call, followers pass None).
        Small header by value; master/momentum arrays over the mesh."""
        w = self.world
        header = None
        if remote is not None:
            opt = remote["outer_opt"]
            header = {
                "epoch": int(remote["epoch"]),
                "opt_scalars": {
                    k: opt[k] for k in ("lr", "momentum", "nesterov")
                },
                "has_bufs": opt.get("bufs") is not None,
            }
        header = w.broadcast_obj(header)
        if header is None:
            return None
        tmpl = [np.zeros(m.shape, np.float32) for m in self.master]
        master = w.broadcast_arrays(
            [np.asarray(m, np.float32) for m in remote["master"]]
            if remote is not None
            else tmpl
        )
        bufs = None
        if header["has_bufs"]:
            bufs = w.broadcast_arrays(
                [np.asarray(b, np.float32) for b in remote["outer_opt"]["bufs"]]
                if remote is not None
                else tmpl
            )
        return {
            "master": master,
            "epoch": header["epoch"],
            "outer_opt": {**header["opt_scalars"], "bufs": bufs},
        }

    def load_state_from_peers(self, state: dict) -> Optional[dict]:
        """Adopt a peer's master params/epoch; returns updated device state.
        Multihost: a collective — every process of the slice must call."""
        self.drop_pending()  # adopting remote state supersedes in-flight comm
        remote = (
            self.backend.fetch_state() if self.world.is_messenger else None
        )
        if self.world.process_count > 1:
            remote = self._broadcast_remote_state(remote)
        if remote is None:
            return None
        if self._plane is not None:
            opt = remote["outer_opt"]
            with self._plane.lock:
                self._plane.load(
                    remote["master"],
                    opt.get("bufs"),
                    lr=opt.get("lr"),
                    momentum=opt.get("momentum"),
                    nesterov=opt.get("nesterov"),
                )
                # scalar mirror only; the plane owns the momentum bufs
                self.outer_opt.load_state_dict({**opt, "bufs": None})
                with self._serve_lock:
                    self._blocking_snap = None
                    self.epoch = int(remote["epoch"])
                    self.local_step = 0
                    self.samples_in_epoch = 0
                leaves = self._plane.sync_params(jax.tree.leaves(state["params"]))
                state["params"] = jax.tree.unflatten(self.treedef, leaves)
            return self.trainer.force_step_position(
                state, self.epoch * self.cfg.local_steps
            )
        with self._serve_lock:
            self._blocking_snap = None  # superseded pre-round snapshot
            self.master = [
                np.asarray(m, np.float32).copy() for m in remote["master"]
            ]
            self.epoch = int(remote["epoch"])
            self.outer_opt.load_state_dict(remote["outer_opt"])
            self.local_step = 0
            self.samples_in_epoch = 0
        state = self._write_master_to_device(state)
        # resume the LR schedule where the swarm is, not at warmup step 0
        return self.trainer.force_step_position(
            state, self.epoch * self.cfg.local_steps
        )

    # ------------------------------------------------------------------
    # inner step
    # ------------------------------------------------------------------

    def _behind_swarm(self) -> bool:
        """True when another peer is >=2 epochs ahead: our pseudo-gradients
        would poison the average (desync detection, hivemind_diloco.py:528-531).
        One epoch of skew is normal near boundaries."""
        if self.backend is None:
            return False
        for p in self.backend.peer_progress():
            if p.peer_id != self.backend.peer_id and p.epoch >= self.epoch + 2:
                return True
        return False

    def _desynced(self) -> bool:
        """The desync decision, agreed across the slice: only the messenger
        sees peer progress, so its verdict is broadcast (one tiny collective
        per epoch start under multihost, a passthrough single-host). Every
        process must reach this in lockstep — it is called at local_step 0,
        which advances identically everywhere."""
        behind = self._behind_swarm() if self.world.is_messenger else False
        if self.world.process_count > 1:
            behind = bool(self.world.broadcast_obj(behind))
        return behind

    def step(self, state: dict, batch: dict) -> tuple[dict, dict]:
        """One inner optimizer step; triggers the outer step at the epoch
        boundary. Returns (state, metrics)."""
        if self._pending is not None:
            state = self._poll_pending(state, block=False)
        if self.local_step == 0 and self._desynced():
            # discard the stale local phase and adopt the swarm state before
            # burning compute on an epoch the group has moved past
            updated = self.load_state_from_peers(state)
            if updated is not None:
                state = updated
                log.warning(
                    "desynced from swarm; re-downloaded state at epoch %d",
                    self.epoch,
                )
        state, metrics = self.trainer.train_step(state, batch)
        self.local_step += 1
        self.samples_in_epoch += self.batch_size
        if self.backend is not None and not self._first_step_evt.is_set():
            # stop the join keepalive announcer; under the lock so an
            # in-flight keepalive tick finishes its (stale) announce BEFORE
            # this step's fresh report below can be overwritten by it
            with self._announce_lock:
                self._first_step_evt.set()

        # progress gossip is a synchronous rendezvous RPC on the TCP backend;
        # rate-limit it so the training loop never blocks on it per-step
        # (always report at the epoch boundary so matchmaking sees fresh state)
        now = time.monotonic()
        at_boundary = self.local_step >= self.cfg.local_steps
        if self.backend is not None and (
            at_boundary or now - getattr(self, "_last_report", 0.0) > 0.5
        ):
            self._last_report = now
            elapsed = max(now - self._epoch_t0, 1e-6)
            self._announce(
                samples=self.samples_in_epoch,
                sps=self.samples_in_epoch / elapsed,
            )

        metrics = dict(metrics)
        metrics["epoch"] = self.epoch
        if self._landed_metrics is not None:  # overlapped round completed
            metrics.update(self._landed_metrics)
            self._landed_metrics = None
        if self.local_step >= self.cfg.local_steps:
            if self._stream is not None:
                # streaming: the fragments already synced mid-phase (or
                # are still flying); the boundary is pure bookkeeping
                state, outer_metrics = self._stream.boundary(state)
            else:
                # the overlapped path is full-model; a fragmented config
                # (streaming under multihost fallback) takes the blocking
                # fragment path instead
                overlap = (
                    self.cfg.overlap_comm != "none"
                    and self._fragments is None
                    and not self._is_state_avg_epoch()
                )
                if overlap and self.cfg.outer_mode == "gossip":
                    # full-model overlapped gossip: the delta-landing
                    # machinery is pseudo-gradient-only. Overlapped gossip
                    # rides the streaming scheduler instead (set
                    # streaming_fragments > 1: each fragment pairs and
                    # lands mid-phase) — full-model boundaries block.
                    if not getattr(self, "_warned_gossip_overlap", False):
                        self._warned_gossip_overlap = True
                        log.warning(
                            "overlap_comm without streaming_fragments "
                            "falls back to blocking under outer_mode="
                            "'gossip'; set streaming_fragments>1 for "
                            "overlapped gossip rounds"
                        )
                    overlap = False
                if overlap:
                    state, outer_metrics = self._outer_step_overlapped(state)
                else:
                    state, outer_metrics = self.outer_step(state)
            metrics.update(outer_metrics)
            tr = obs.tracer()
            if tr is not None:
                # epoch rides the overseer roll-up; the watchdog's stall
                # deadline resets here so EVERY backend (loopback included,
                # where no TCP round-health hook fires) counts as progress
                tr.gauge("outer_epoch", self.epoch)
                wd = obs.anomaly.watchdog()
                if wd is not None:
                    wd.note_progress(self.epoch)
        return state, metrics

    def _stream_tick(self, state: dict) -> dict:
        """Trainer post-dispatch hook: the streaming scheduler's
        heartbeat. Fires after every inner dispatch and BEFORE step()
        increments local_step, so the just-dispatched inner step is
        ``local_step + 1``."""
        return self._stream.tick(state, self.local_step + 1)

    def _is_state_avg_epoch(self) -> bool:
        """Full-state-averaging epochs run the blocking path (they rewrite
        the master wholesale; overlapping them buys nothing)."""
        return (
            self.cfg.average_state_every > 0
            and (self.epoch + 1) % self.cfg.average_state_every == 0
        )

    # ------------------------------------------------------------------
    # overlapped outer step (Eager Updates for Overlapped Communication
    # and Computation in DiLoCo, arxiv 2502.12996)
    # ------------------------------------------------------------------

    def _outer_step_overlapped(self, state: dict) -> tuple[dict, dict]:
        """Launch the outer all-reduce in the background and keep training.

        Blocking DiLoCo rewrites the device from the boundary params theta_b
        to the new master M'. Overlapped, the device keeps stepping from
        theta_b; when the average lands we apply the SAME rewrite as a delta:
        params += (M' - theta_b). "eager" additionally applies the update
        estimated from the local pseudo-gradient immediately and corrects
        with (M'_true - M'_est) on arrival.
        """
        if self._plane is not None:
            return self._outer_step_overlapped_device(state)
        assert schema_fingerprint(state["params"]) == self._schema, (
            "parameter schema changed mid-epoch"
        )
        t0 = time.monotonic()
        tr = obs.tracer()
        t0p = time.perf_counter() if tr is not None else 0.0
        if self._pending is not None:  # at most one round in flight
            state = self._poll_pending(state, block=True)
        self._drain_abandoned()

        # overlap the boundary D2H with the straggler wait (same trick as
        # the blocking path): params are final at the boundary. Multihost:
        # the gather is a mesh collective issued by every process's fetcher
        # thread; the WAN launch below is messenger-only.
        fetcher = _BoundaryFetch(
            tr, self.epoch,
            lambda: self.world.gather_params(jax.tree.leaves(state["params"])),
        )
        fetcher.start()
        if self.world.is_messenger:
            wait_for_peers(
                self.backend,
                target_samples=self.target_samples,
                own_epoch=self.epoch,
                strategy=self.cfg.all_reduce_strategy,
                timeout_waiting_for_peers=self.cfg.timeout_waiting_for_peers,
                log=log,
            )
        wait_s = time.monotonic() - t0
        if tr is not None:
            tr.add_span(
                "outer/barrier_wait", t0p, time.perf_counter(),
                epoch=self.epoch,
            )
        boundary = fetcher.wait()
        self._pg_slot ^= 1
        # the messenger puts the pseudo-gradient on the wire; in eager mode
        # every process also computes it (identical, from the replicated
        # master) for the local estimate below. A delayed-mode follower
        # needs neither — the landing path works from boundary/master_snap
        # — so it skips the full-model subtraction AND the two model-sized
        # slot buffers (~8 GB idle at 1b scale)
        pseudo_grad = (
            self._pseudo_grad_into(boundary, slot=self._pg_slot)
            if self.world.is_messenger or self.cfg.overlap_comm == "eager"
            else None
        )
        if self._ef is not None and pseudo_grad is not None:
            # residual folded into the wire pg (and the eager estimate
            # below, which must match what the swarm averages); the round's
            # roundtrip error stages pending until the landing commits it.
            # Eager followers run this too — identical pg from the
            # replicated master keeps residuals process-symmetric.
            self._ef.prepare("main", range(len(pseudo_grad)), pseudo_grad)

        pending: dict[str, Any] = {
            "master_snap": [m.copy() for m in self.master],
            "opt_snap": self.outer_opt.state_dict(),
            "boundary": boundary,
            "epoch": self.epoch,
            "t_launch": t0,
            # followers carry no future; landing is decided by the
            # messenger and broadcast (see _poll_pending)
            "future": (
                self._spawn_all_reduce(pseudo_grad, self.epoch)
                if self.world.is_messenger
                else None
            ),
        }

        if self.cfg.overlap_comm == "eager":
            # immediate update from the local pseudo-gradient (own epoch's
            # contribution stands in for the average until it arrives)
            est_opt = OuterSGD(
                lr=self.cfg.outer_lr,
                momentum=self.cfg.outer_momentum,
                nesterov=self.cfg.outer_nesterov,
            )
            est_opt.load_state_dict(pending["opt_snap"])
            est_master = [m.copy() for m in pending["master_snap"]]
            est_opt.step(est_master, pseudo_grad)
            delta = [e - b for e, b in zip(est_master, boundary)]
            state = self._apply_delta_to_device(state, delta)
            pending["est_master"] = est_master

        # publish atomically against the serve thread: the eager master
        # rebind, the pending round, and the epoch advance must appear
        # together (a fetch between them would pair an estimated master
        # with the old epoch, or a new epoch with no pending snapshot)
        with self._serve_lock:
            if "est_master" in pending:
                self.master = pending["est_master"]
            self._pending = pending
            self.epoch += 1
            self.local_step = 0
            self.samples_in_epoch = 0
        self._epoch_t0 = time.monotonic()
        outer_metrics = {
            "outer_step_s": time.monotonic() - t0,
            **fetcher.row(),
            "outer_wait_s": wait_s,
            "outer_overlapped": 1,
        }
        if tr is not None:
            tr.add_span(
                "outer/launch", t0p, time.perf_counter(), epoch=self.epoch - 1
            )
            tr.gauge("outer_wait_s", wait_s)
        self.last_outer_metrics = outer_metrics
        return state, outer_metrics

    def _outer_step_overlapped_device(self, state: dict) -> tuple[dict, dict]:
        """Device-placement overlapped launch: pseudo-gradient and (eager)
        estimate are fused device ops; the boundary params never need a
        full-width D2H (the wire fetch is wire-width, the f32
        pseudo-gradient is retained ON DEVICE for the landing math instead
        of a host boundary/master snapshot)."""
        plane = self._plane
        assert schema_fingerprint(state["params"]) == self._schema, (
            "parameter schema changed mid-epoch"
        )
        t0 = time.monotonic()
        tr = obs.tracer()
        t0p = time.perf_counter() if tr is not None else 0.0
        if self._pending is not None:  # at most one round in flight
            state = self._poll_pending(state, block=True)
        self._drain_abandoned()

        # overlap the (wire-width) pseudo-gradient D2H with the straggler
        # wait; device placement is single-process, so this process IS the
        # messenger and both pg forms are always needed (host for the wire,
        # f32 device for the landing delta)
        device_leaves = jax.tree.leaves(state["params"])
        # device copy of the boundary params: both overlap modes compute the
        # deferred boundary rewrite as new_master - boundary (the SAME
        # associativity as the host path's (m - lr*d) - boundary); deriving
        # it from the pseudo-gradient instead rounds at pg scale and drifts
        # ~1e3 ulps over a few rounds once inner AdamW amplifies it
        eager = self.cfg.overlap_comm == "eager"
        boundary_dev = plane.copy_leaves(device_leaves)
        fetcher = _BoundaryFetch(
            tr, self.epoch,
            lambda: plane.pseudo_grad(device_leaves, keep_device=eager),
            stats=lambda: plane.last_fetch,
        )
        fetcher.start()
        wait_for_peers(
            self.backend,
            target_samples=self.target_samples,
            own_epoch=self.epoch,
            strategy=self.cfg.all_reduce_strategy,
            timeout_waiting_for_peers=self.cfg.timeout_waiting_for_peers,
            log=log,
        )
        wait_s = time.monotonic() - t0
        if tr is not None:
            tr.add_span(
                "outer/barrier_wait", t0p, time.perf_counter(),
                epoch=self.epoch,
            )
        pg_host, pg_norm, pg_dev = fetcher.wait()
        if tr is not None:
            tr.gauge("pseudo_grad_norm", pg_norm)
        if self._ef is not None:
            # the plane's jit already added the residual (full-width D2H:
            # pg_host is the exact f32 the backend will encode); prepare
            # only stages the roundtrip error
            self._ef.prepare("main", range(len(pg_host)), pg_host)

        pending: dict[str, Any] = {
            "epoch": self.epoch,
            "t_launch": t0,
            "future": self._spawn_all_reduce(pg_host, self.epoch),
        }
        # the plane mutation (eager estimate), the pending publication, and
        # the epoch advance must appear atomically to the serve thread's
        # device path (which takes plane.lock then _serve_lock)
        with plane.lock:
            pending["plane_pre"] = (plane.masters, plane.bufs)
            if eager:
                # immediate update from the local pseudo-gradient; the
                # estimate rebinds the live plane (pg_dev and the boundary
                # copy are donated) and returns the device delta for the
                # inner params
                delta = plane.estimate(pg_dev, boundary_dev)
                state = self._apply_delta_to_device(state, delta)
            else:
                # delayed: the landing rewrites the boundary params to the
                # true new master, so it needs the retained boundary copy
                pending["boundary_dev"] = boundary_dev
            with self._serve_lock:
                self._pending = pending
                self.epoch += 1
                self.local_step = 0
                self.samples_in_epoch = 0
        self._epoch_t0 = time.monotonic()
        outer_metrics = {
            "outer_step_s": time.monotonic() - t0,
            **fetcher.row(),
            "outer_wait_s": wait_s,
            "pseudo_grad_norm": pg_norm,
            "outer_overlapped": 1,
        }
        if tr is not None:
            tr.add_span(
                "outer/launch", t0p, time.perf_counter(), epoch=self.epoch - 1
            )
            tr.gauge("outer_wait_s", wait_s)
        self.last_outer_metrics = outer_metrics
        return state, outer_metrics

    def _drain_abandoned(self) -> None:
        """A dropped round may still be running (its reduce can't be
        cancelled); let it drain before writing fresh pseudo-gradients into
        slot buffers it might still be streaming from. Called by BOTH outer
        paths: the blocking path writes slot 0, which an abandoned overlapped
        round may own."""
        if self._abandoned is None:
            return
        drained = True
        try:
            self._abandoned.result(timeout=self.cfg.averaging_timeout + 60)
        except (TimeoutError, concurrent.futures.TimeoutError):
            # on 3.10 futures.TimeoutError is NOT the builtin; both must be
            # caught or a wedged round silently counts as drained
            drained = False
        except Exception:
            pass
        self._abandoned = None
        if not drained:
            # a truly wedged round may still be streaming from its
            # pseudo-grad buffers: surrender both slots to it and
            # allocate fresh ones rather than risk torn bytes on the
            # wire (leaks one buffer set, once, on a pathological path)
            self._pg_bufs = [None, None]

    def _spawn_all_reduce(self, pseudo_grad: list, epoch: int):
        """Run backend.all_reduce on a daemon thread (a wedged round must
        never block interpreter exit) with the round epoch pinned at submit
        time (the training thread advances self.epoch immediately after)."""
        fut: concurrent.futures.Future = concurrent.futures.Future()

        def _run():
            if not fut.set_running_or_notify_cancel():
                return  # dropped before the round started
            try:
                fut.set_result(
                    self.backend.all_reduce(
                        pseudo_grad,
                        timeout=self.cfg.averaging_timeout,
                        epoch=epoch,
                    )
                )
            except BaseException as e:  # surfaced via fut.result()
                fut.set_exception(e)

        threading.Thread(
            target=_run, name="odtp-outer-comm", daemon=True
        ).start()
        return fut

    def _messenger_fanout(self, produce, shapes):
        """THE multihost fan-out protocol (both the blocking and the
        overlapped outer paths ride it): run ``produce() -> (arrays, meta)``
        on the messenger, copy the result out of any pooled backend buffers,
        broadcast a small header first — so a messenger-side failure makes
        the whole slice raise in lockstep instead of followers hanging at
        the array fan-out — then broadcast the arrays (followers pass
        zero templates of ``shapes``). Returns ``(arrays, meta)``."""
        exc: Optional[BaseException] = None
        arrays, meta = None, {}
        if self.world.is_messenger:
            try:
                arrays, meta = produce()
                # own the data before the fan-out: backend results are
                # views into pooled buffers the next call reclaims
                # (np.array COPIES; asarray on an f32 view wouldn't)
                arrays = [np.array(a, dtype=np.float32) for a in arrays]
            except BaseException as e:
                exc = e
        header = self.world.broadcast_obj(
            {
                "err": None if exc is None else f"{type(exc).__name__}: {exc}",
                "meta": meta,
            }
            if self.world.is_messenger
            else None
        )
        if exc is not None:
            raise exc
        if header["err"] is not None:
            raise RuntimeError(f"messenger outer round failed: {header['err']}")
        arrays = self.world.broadcast_arrays(
            arrays
            if self.world.is_messenger
            else [np.zeros(s, np.float32) for s in shapes]
        )
        return arrays, header["meta"]

    def _overlap_result(self, pending: dict, *, block: bool):
        """(averaged, group_size) of an in-flight round. Single-host: the
        future's result. Multihost: the messenger resolves its future and
        fans the result out via _messenger_fanout."""
        fut = pending["future"]
        timeout = None if not block else self.cfg.averaging_timeout + 60
        if self.world.process_count == 1:
            return fut.result(timeout=timeout)

        def produce():
            avg, n = fut.result(timeout=timeout)
            return avg, {"n": n}

        avg, meta = self._messenger_fanout(
            produce, [m.shape for m in pending["master_snap"]]
        )
        return avg, int(meta["n"])

    def _poll_pending(self, state: dict, *, block: bool) -> dict:
        """Resolve an in-flight outer all-reduce if it completed (or wait
        for it when ``block``); applies the (corrected) outer update as a
        device delta. Multihost: whether the round landed is the
        messenger's host-local fact, so the verdict rides one tiny
        collective per poll — every process reaches here in lockstep (the
        poll sites are all step-count-deterministic)."""
        pending = self._pending
        if pending is None:
            return state
        fut = pending["future"]
        if not block:
            done = fut.done() if fut is not None else False
            if self.world.process_count > 1:
                done = bool(
                    self.world.broadcast_obj(
                        done if self.world.is_messenger else None
                    )
                )
            if not done:
                return state
        # keep _pending published until the landed master/opt are assigned:
        # the serve thread falls back to the live (still pre-round in the
        # delayed mode) master the moment _pending clears, so clearing
        # before the assignment would open a (new epoch, old master) window
        # for onboarding peers. The finally also clears on failure, where
        # the live state is the correct thing to serve.
        tr = obs.tracer()
        try:
            if tr is not None and block:
                t_wait = time.perf_counter()
                avg, group_size = self._overlap_result(pending, block=block)
                tr.add_span(
                    "outer/barrier_wait", t_wait, time.perf_counter(),
                    epoch=pending["epoch"],
                )
            else:
                avg, group_size = self._overlap_result(pending, block=block)
            self._check_group_size(group_size)
            if self._ef is not None:
                # the round's compressed pg was adopted by the swarm: its
                # roundtrip error becomes the live residual (no-op on
                # delayed-mode followers, which never prepared)
                self._ef.commit("main")

            t_apply = time.perf_counter()
            if "plane_pre" in pending:
                # device placement: fused landing. plane.lock is held from
                # the donating land op until the pending round is cleared —
                # the serve thread's device path could otherwise pick up
                # the just-donated pre-round refs from _pending and
                # device_get freed buffers.
                plane = self._plane
                pre_m, pre_b = pending["plane_pre"]
                with plane.lock:
                    if "boundary_dev" in pending:  # delayed
                        delta = plane.land_delayed(
                            pre_m, pre_b, pending["boundary_dev"], avg
                        )
                    else:  # eager: correct the estimated update
                        delta = plane.land_eager(pre_m, pre_b, avg)
                    state = self._apply_delta_to_device(state, delta)
                    with self._serve_lock:
                        self._pending = None
            else:
                master = [m.copy() for m in pending["master_snap"]]
                opt = OuterSGD(
                    lr=self.cfg.outer_lr,
                    momentum=self.cfg.outer_momentum,
                    nesterov=self.cfg.outer_nesterov,
                )
                opt.load_state_dict(pending["opt_snap"])
                opt.step(master, avg)

                if "est_master" in pending:  # eager: correct the estimate
                    delta = [
                        t - e for t, e in zip(master, pending["est_master"])
                    ]
                else:  # delayed: the deferred boundary rewrite
                    delta = [t - b for t, b in zip(master, pending["boundary"])]
                state = self._apply_delta_to_device(state, delta)
                with self._serve_lock:
                    self.outer_opt = opt
                    self.master = master
            apply_s = time.perf_counter() - t_apply
            if tr is not None:
                tr.add_span(
                    "outer/apply", t_apply, t_apply + apply_s,
                    epoch=pending["epoch"], group=group_size,
                )
        except BaseException:
            if self._ef is not None:
                # dropped round: discard the staged error, keep the
                # previous residual live (the next pseudo-gradient
                # re-captures the lost update — nothing double-counts)
                self._ef.abort("main")
            raise
        finally:
            with self._serve_lock:
                self._pending = None
        landed_s = time.monotonic() - pending["t_launch"]
        # surface the landing in the next metrics row (dashboards would
        # otherwise never see overlapped round size/latency)
        self._landed_metrics = {
            "outer_allreduce_s": landed_s,
            "outer_apply_s": apply_s,
            "num_peers": group_size,
            **self._round_health_metrics(),
        }
        if tr is not None:
            tr.instant(
                "outer/landed",
                epoch=pending["epoch"], group=group_size,
                landed_s=round(landed_s, 6),
            )
            tr.gauge("outer_allreduce_s", landed_s)
        self.last_outer_metrics = dict(self._landed_metrics)
        log.info(
            "outer step %d (overlapped): all-reduce over %d peers landed "
            "after %.3fs",
            pending["epoch"],
            group_size,
            landed_s,
        )
        return state

    def _round_health_metrics(self, health: Optional[dict] = None) -> dict:
        """Elastic-round fields from the backend's health ledger (or from
        ``health``: a blocking round's rows folded over its pieces,
        ``_WireRound``), merged into the metrics row of every landed outer
        round: dashboards and the chaos soak read partial groups as data,
        not as errors."""
        if health is None:
            health = getattr(self.backend, "last_round_health", None) or {}
        out = {}
        if "elastic" in health:
            out["elastic"] = bool(health["elastic"])
            out["expected_peers"] = int(health.get("expected", 0))
        if health.get("retries"):
            out["round_retries"] = int(health["retries"])
        # adaptive-transport fields (tcp.py records them when armed): the
        # plan hash and per-part shares of the butterfly this round ran on
        if health.get("link_plan"):
            out["link_plan"] = health["link_plan"]
        if health.get("link_shares"):
            out["link_shares"] = list(health["link_shares"])
        # hierarchical-round fields: which aggregators this round's plan
        # elected. The chaos soak asserts aggregator re-election after a
        # SIGKILL straight from these rows.
        if health.get("hier"):
            out["hier_plan"] = health["hier"].get("plan")
            out["hier_aggregators"] = list(
                health["hier"].get("aggregators", [])
            )
        return out

    def _check_group_size(self, group_size: int) -> None:
        if group_size < self.max_num_peers:
            msg = f"Lost a diloco worker: {group_size} < {self.max_num_peers}"
            if self.cfg.fail_rank_drop:
                raise PeerDropError(msg)
            log.warning(msg)
        self.max_num_peers = max(self.max_num_peers, group_size)

    def drop_pending(self) -> None:
        """Abandon an in-flight round (its result will never be applied).
        A running reduce can't be cancelled; it is tracked so the next
        launch drains it before reusing the round key."""
        if self._stream is not None:
            self._stream.drop_all()
        if self._pending is not None:
            fut = self._pending["future"]
            if fut is not None and not fut.cancel():
                self._abandoned = fut
            self._pending = None
        if self._ef is not None:
            # abandoned rounds never commit; the live residual survives
            # state adoption (it is this worker's own compression debt)
            self._ef.abort_all()
        if self._gossip is not None:
            # same contract per partner: pending pair rounds are discarded,
            # committed residual ledgers survive
            self._gossip.abort_all()

    def flush(self, state: dict) -> dict:
        """Resolve any in-flight outer communication (call before
        checkpointing or shutdown so the master reflects every launched
        round)."""
        if self._stream is not None:
            state = self._stream.flush(state)
        return self._poll_pending(state, block=True)

    def _apply_frag_delta(self, state: dict, frag: list, delta: list) -> dict:
        """Apply a fragment-indexed delta to the live params: one donated
        jit add over the fragment's leaves; untouched leaves pass through
        live (the H2D — host placement only — moves one fragment, not the
        model). The jit cache is keyed by the fragment's avals, so a fixed
        partition compiles exactly N tiny executables."""
        leaves = jax.tree.leaves(state["params"])
        cur = [leaves[i] for i in frag]
        if delta and not isinstance(delta[0], jax.Array):
            sh = jax.tree.leaves(self.trainer.state_shardings["params"])
            delta = [
                jax.device_put(np.asarray(d, np.float32), sh[i])
                for d, i in zip(delta, frag)
            ]
        fresh = _frag_add(cur, delta)
        merged = list(leaves)
        for j, i in enumerate(frag):
            merged[i] = fresh[j]
        state = dict(state)
        state["params"] = jax.tree.unflatten(self.treedef, merged)
        return state

    def _apply_delta_to_device(self, state: dict, delta_flat: list) -> dict:
        if self._apply_delta is None:
            sh = self.trainer.state_shardings["params"]
            self._apply_delta = jax.jit(
                lambda p, d: jax.tree.map(lambda a, b: a + b, p, d),
                donate_argnums=(0,),
                in_shardings=(sh, sh),
                out_shardings=sh,
            )
        delta = self._leaves_to_device(delta_flat)
        if "outer/apply_delta" not in self._recipes:
            shapes, add = obs.programs.abstract((state["params"], delta)), self._apply_delta
            self._recipes.note("outer/apply_delta", None, lambda: add.lower(*shapes))
        state = dict(state)
        state["params"] = self._apply_delta(state["params"], delta)
        return state

    def program_recipes(self):
        """How to lower the boundary's device programs that have run, under
        ``outer/...``: the pseudo-gradient and the apply of a device plane, the
        delta's add where the state comes back as a delta (``obs.programs``
        names their operations, so that a trace's reader does not take them for
        the inner step's). Not the overlapped modes' landings."""
        return self._recipes

    def program_texts(self) -> dict:
        """{program name: compiled text} of ``program_recipes``."""
        return self._recipes.texts()

    # ------------------------------------------------------------------
    # outer step (reference: _update_global_epoch, hivemind_diloco.py:570-679)
    # ------------------------------------------------------------------

    def _pieces(self, frag: Optional[list[int]]) -> list[list[int]]:
        """The pieces this epoch's blocking round crosses the wire in, as
        positions into the round's own list of leaves: ``cut_pieces`` of the
        leaves' float32 bytes and of nothing else -- not the placement, not
        the mesh -- fixed the first time a fragment is asked for, so that
        every worker of a galaxy opens the same rounds under the same tags.
        One piece, the leaves in their own order, for a round that needs the
        whole list in hand before the wire: error feedback, gossip, a
        state-averaging epoch."""
        masters = self.master if self._plane is None else self._plane.masters
        idxs = range(len(masters)) if frag is None else frag
        if (
            self.cfg.outer_mode == "gossip"
            or self._ef is not None
            or self._is_state_avg_epoch()
        ):
            return [list(range(len(idxs)))]
        key = None if frag is None else tuple(frag)
        if key not in self._piece_cache:
            self._piece_cache[key] = outer_device.cut_pieces(
                [masters[i].size * 4 for i in idxs]
            )
        return self._piece_cache[key]

    def _wan_all_reduce(
        self,
        arrays: list[np.ndarray],
        *,
        timeout: float,
        epoch: Optional[int] = None,
        tag: Optional[str] = None,
        group_cap: Optional[int] = None,
    ) -> tuple[list[np.ndarray], int, int]:
        """The WAN leg of an outer round: ``backend.all_reduce`` on the
        messenger, then a mesh broadcast of the averaged result to the
        follower processes — the TPU shape of the reference's
        post-outer-step fan-out (train_fsdp.py:410-413, NCCL broadcast
        from each worker's rank 0).

        Returns ``(averaged, group_size, live_peers)``; ``live_peers`` is
        the swarm's current peer count (the gossip health signal — pair
        size says nothing about the swarm). Multihost: a collective; every
        process calls with same-shaped ``arrays`` (follower inputs are
        shape templates — they computed the identical pseudo-gradient from
        their replicated master, so the arrays are already in hand). A
        messenger-side failure is re-broadcast so the whole slice raises
        in lockstep instead of followers hanging at the fan-out."""
        kw: dict[str, Any] = {"timeout": timeout}
        if epoch is not None:
            kw["epoch"] = epoch
        if tag is not None:
            kw["tag"] = tag
        if group_cap is not None:
            kw["group_cap"] = group_cap
        if self.world.process_count == 1:
            avg, n = self.backend.all_reduce(arrays, **kw)
            return avg, n, self.backend.num_peers()

        def produce():
            avg, n = self.backend.all_reduce(arrays, **kw)
            return avg, {"n": n, "live": self.backend.num_peers()}

        avg, meta = self._messenger_fanout(produce, [a.shape for a in arrays])
        return avg, int(meta["n"]), int(meta["live"])

    def _gossip_round(
        self,
        masters: list[np.ndarray],
        bufs: Optional[list[np.ndarray]],
        pgs: list[np.ndarray],
        *,
        idxs,
        frag_id: int,
        epoch: int,
    ):
        """One NoLoCo pair round through the gossip plane, with the same
        messenger/follower fan-out shape as ``_wan_all_reduce``.

        Returns ``(mix_m, mix_b, avg_g, pair_n, live_peers)``; ``pair_n``
        is 0 when the round dropped (partner death / timeout / "hold"
        self-round) — mix arrays are None then and the caller treats the
        boundary as a non-event (master untouched, EF residual retained).
        """
        k = len(masters)
        if self.world.process_count == 1:
            res = self._gossip.exchange(
                epoch=epoch, frag_id=frag_id, idxs=idxs,
                masters=masters, bufs=bufs, pgs=pgs,
                timeout=self.cfg.averaging_timeout,
            )
            live = self.backend.num_peers()
            if res is None:
                return None, None, None, 0, live
            mix_m, mix_b, avg_g, _partner, n = res
            return mix_m, mix_b, avg_g, n, live

        # momentum-armed-ness must be config-symmetric across processes:
        # follower shape templates are derived from it without messaging
        has_b = bufs is not None

        def produce():
            res = self._gossip.exchange(
                epoch=epoch, frag_id=frag_id, idxs=idxs,
                masters=masters, bufs=bufs, pgs=pgs,
                timeout=self.cfg.averaging_timeout,
            )
            live = self.backend.num_peers()
            if res is None:
                # dropped round: fan the INPUTS out (cheap, right shapes);
                # n=0 tells every process to ignore them
                return masters + (bufs or []) + pgs, {"n": 0, "live": live}
            mix_m, mix_b, avg_g, _partner, n = res
            return mix_m + (mix_b or []) + avg_g, {"n": n, "live": live}

        shapes = [a.shape for a in masters + (bufs or []) + pgs]
        arrays, meta = self._messenger_fanout(produce, shapes)
        n, live = int(meta["n"]), int(meta["live"])
        if n == 0:
            return None, None, None, 0, live
        mix_m = arrays[:k]
        mix_b = arrays[k:2 * k] if has_b else None
        avg_g = arrays[-k:]
        return mix_m, mix_b, avg_g, n, live

    def _outer_step_device(self, state: dict) -> tuple[dict, dict]:
        """Blocking outer round, device placement: the pseudo-gradient and
        the Nesterov apply are fused, donated jit ops over all the round's
        leaves; in between, the round crosses the host in pieces
        (``_pieces``) and in three stages that run side by side: the
        fetch (``_BoundaryFetch``'s thread; wire-width D2H) hands each piece
        on as it lands, this thread all-reduces it under the piece's own tag
        (``_WireRound``), and ``PutBack``'s thread has the average back on the
        devices while the next piece is still arriving. The fetch starts
        before the straggler wait and overlaps it; no piece's all-reduce
        starts before the wait has returned. A round one of whose pieces
        raises has failed as a failed all-reduce fails -- the stage threads
        joined, the puts dropped, masters, momentum and parameters untouched,
        because the apply has not been dispatched; a peer that drops between
        two pieces makes it an elastic round (``_WireRound``). Rounds that
        need the whole list in hand before the wire (error feedback, gossip,
        a state-averaging epoch) are the same code with one piece.

        No clone-then-rebind and no pre-round host snapshot for normal
        rounds — donation makes the apply atomic under plane.lock, which the
        serve thread's device path also takes. State-averaging rounds do
        pre-publish a host snapshot (their WAN leg would otherwise stall
        onboarding fetches behind plane.lock).

        The row's three parts overlap and no longer add up to
        ``outer_step_s``: ``outer_d2h_s`` the fetch's own start to its last
        piece's landing, ``outer_allreduce_s`` the sum of the pieces'
        all-reduce calls, ``outer_apply_s`` from the last piece's average in
        hand to the return; ``outer_h2d_s`` the first put's start to the last
        piece resident, ``outer_pieces`` how many pieces the round ran in."""
        plane = self._plane
        if self._pending is not None:  # a blocking round supersedes overlap
            state = self._poll_pending(state, block=True)
        self._drain_abandoned()
        assert schema_fingerprint(state["params"]) == self._schema, (
            "parameter schema changed mid-epoch"
        )
        state_avg = self._is_state_avg_epoch()
        if state_avg:
            master_snap, buf_snap = plane.host_state()
            with self._serve_lock:
                self._blocking_snap = {
                    "master": master_snap,
                    "epoch": self.epoch,
                    "outer_opt": {
                        "lr": plane.lr,
                        "momentum": plane.momentum,
                        "nesterov": plane.nesterov,
                        "bufs": buf_snap,
                    },
                }
        t0 = time.monotonic()
        tr = obs.tracer()
        t0p = time.perf_counter() if tr is not None else 0.0

        frag: Optional[list[int]] = None
        device_leaves = jax.tree.leaves(state["params"])
        if self._fragments is not None:
            frag = self._fragments[self.epoch % len(self._fragments)]
        gossip = self.cfg.outer_mode == "gossip"
        pieces = self._pieces(frag)
        # wire-width D2H of this boundary's fragment; the norm rides the
        # same jit as one HBM reduction, armed tracer or not
        fetcher = _BoundaryFetch(
            tr, self.epoch,
            lambda deliver: plane.pseudo_grad(
                device_leaves if frag is None
                else [device_leaves[i] for i in frag],
                frag, pieces=pieces, deliver=deliver,
            ),
            stats=lambda: plane.last_fetch,
            piecewise=True,
        )
        fetcher.start()
        if not gossip:
            # gossip skips the straggler wait: a pair round has no group
            # to assemble (no global barrier); the pair push-pull itself
            # bounds how long a fast worker waits on its partner
            wait_for_peers(
                self.backend,
                target_samples=self.target_samples,
                own_epoch=self.epoch,
                strategy=self.cfg.all_reduce_strategy,
                timeout_waiting_for_peers=self.cfg.timeout_waiting_for_peers,
                log=log,
            )
        wait_s = time.monotonic() - t0
        if tr is not None:
            tr.add_span(
                "outer/barrier_wait", t0p, time.perf_counter(),
                epoch=self.epoch,
            )
        if gossip:
            # pair-mix on host (the wire encode is host-side anyway), then
            # land the mixed fragment back through the plane's donated jits
            ((_, pseudo_grad),) = fetcher.arrivals()
            _, pg_norm, _ = fetcher.wait()
            if tr is not None:
                tr.gauge("pseudo_grad_norm", pg_norm)
            return self._outer_step_device_gossip(
                state, device_leaves, frag, pseudo_grad,
                t0=t0, t0p=t0p, wait_s=wait_s, d2h_row=fetcher.row(),
                pg_norm=pg_norm,
            )

        back = PutBack(plane, frag, pieces)
        back.start()
        wire = _WireRound(self, len(pieces))
        try:
            for k, piece in fetcher.arrivals():
                if self._ef is not None:
                    # residual already added in the plane's jit; stage the
                    # error (one piece: the whole list)
                    self._ef.prepare(
                        "main",
                        frag if frag is not None else range(len(piece)),
                        piece,
                    )
                back.submit(k, wire.reduce(k, piece))
            group_size = wire.group_size()
            self._check_group_size(group_size)
            t_apply = time.perf_counter()
            averaged = back.wait()
        except BaseException:
            back.drop()
            fetcher.join()
            if self._ef is not None:
                self._ef.abort("main")
            raise
        _, pg_norm, _ = fetcher.wait()
        if tr is not None:
            tr.gauge("pseudo_grad_norm", pg_norm)
        if self._ef is not None:
            self._ef.commit("main")
        allreduce_s = wire.seconds
        log.info(
            "outer step %d: all-reduce over %d peers took %.3fs in %d pieces",
            self.epoch, group_size, allreduce_s, len(pieces),
        )

        if state_avg:
            # fused apply, then the full-state averaging leg: master D2H'd
            # on demand, averaged over the WAN, adopted back. The
            # pre-published _blocking_snap keeps onboarding fetches
            # consistent (and unblocked) throughout.
            plane.apply_average(averaged, frag)
            master_host, _ = plane.host_state()
            averaged_state, n, _ = self._wan_all_reduce(
                master_host, timeout=self.cfg.averaging_timeout, tag="state"
            )
            plane.load_masters(averaged_state)
            log.info(
                "averaged full state over %d peers at epoch %d", n, self.epoch
            )
            with plane.lock:
                leaves = plane.sync_params(device_leaves, frag)
                state["params"] = jax.tree.unflatten(self.treedef, leaves)
                with self._serve_lock:
                    self.epoch += 1
                    self.local_step = 0
                    self.samples_in_epoch = 0
                    self._blocking_snap = None
        else:
            # plane.lock spans the donating apply, the params sync, and
            # the epoch advance: a serve-thread fetch sees exactly the
            # pre- or the post-round (plane, epoch) pair, never a mix.
            # sync= folds the params <- master overwrite into the apply
            # jit (donating the old param buffers) — one dispatch and one
            # fewer full-model pass than apply + sync_params
            with plane.lock:
                leaves = plane.apply_average(
                    averaged, frag, sync=device_leaves
                )
                state["params"] = jax.tree.unflatten(self.treedef, leaves)
                with self._serve_lock:
                    self.epoch += 1
                    self.local_step = 0
                    self.samples_in_epoch = 0
        if tr is not None:
            tr.add_span(
                "outer/apply", t_apply, time.perf_counter(), epoch=self.epoch - 1
            )
        self._epoch_t0 = time.monotonic()
        outer_metrics = {
            "outer_step_s": time.monotonic() - t0,
            **fetcher.row(),
            "outer_allreduce_s": allreduce_s,
            "outer_h2d_s": back.seconds,
            "outer_apply_s": time.perf_counter() - t_apply,
            "outer_wait_s": wait_s,
            "pseudo_grad_norm": pg_norm,
            "num_peers": group_size,
            **self._round_health_metrics(wire.health),
        }
        if tr is not None:
            tr.add_span(
                "outer/step", t0p, time.perf_counter(),
                epoch=self.epoch - 1, group=group_size,
            )
            tr.gauge("outer_step_s", outer_metrics["outer_step_s"])
            tr.gauge("outer_allreduce_s", allreduce_s)
            tr.gauge("outer_wait_s", wait_s)
        self.last_outer_metrics = outer_metrics
        return state, outer_metrics

    def _outer_step_device_gossip(
        self,
        state: dict,
        device_leaves: list,
        frag: Optional[list[int]],
        pseudo_grad: list[np.ndarray],
        *,
        t0: float,
        t0p: float,
        wait_s: float,
        d2h_row: dict,
        pg_norm: float,
    ) -> tuple[dict, dict]:
        """Gossip tail of the blocking device-placement round: the pair
        mix and NoLoCo step run on host f32 copies of this boundary's
        fragment (the pair wire encode is host-side regardless), then the
        mixed result lands back through the plane's donated H2D jits —
        the D2H/H2D still moves one fragment, not the model."""
        plane = self._plane
        tr = obs.tracer()
        idxs = frag if frag is not None else list(range(len(device_leaves)))
        masters_np, bufs_np = plane.host_frag(frag)
        if self.cfg.outer_momentum != 0.0 and bufs_np is None:
            # zeros when momentum never armed: wire shapes must be static
            bufs_np = [np.zeros_like(m) for m in masters_np]
        # NOTE: blocking-streaming keys the fragment to the epoch, so under
        # async bounded-staleness gossip two workers align on a fragment
        # only when their epoch distance is a multiple of the fragment
        # count (otherwise both self-round). The streaming-overlap path
        # syncs EVERY fragment each epoch and matches at any distance.
        frag_id = (
            self.epoch % len(self._fragments)
            if self._fragments is not None else 0
        )
        t1 = time.monotonic()
        t1p = time.perf_counter() if tr is not None else 0.0
        mix_m, mix_b, avg_g, group_size, live_peers = self._gossip_round(
            masters_np, bufs_np, pseudo_grad,
            idxs=idxs, frag_id=frag_id, epoch=self.epoch,
        )
        dropped = group_size == 0
        self._check_group_size(live_peers)
        allreduce_s = time.monotonic() - t1
        if tr is not None:
            tr.add_span(
                "outer/allreduce", t1p, time.perf_counter(),
                epoch=self.epoch, group=group_size,
            )
        t_apply = time.perf_counter()
        log.info(
            "outer step %d: gossip exchange over %d peers took %.3fs",
            self.epoch, group_size, allreduce_s,
        )
        if self._is_state_avg_epoch() and not dropped:
            # NoLoCo pair mixing already averages the masters every round;
            # the periodic full-state leg would need a global collective
            # (exactly what gossip removes), so it is a no-op here
            log.debug(
                "average_state_every is redundant under gossip "
                "(masters mix every pair round); skipping"
            )
        if dropped:
            # non-event: master/momentum/EF stay put, params keep local
            # progress (next pseudo-gradient re-captures this epoch)
            with self._serve_lock:
                self.epoch += 1
                self.local_step = 0
                self.samples_in_epoch = 0
                self._blocking_snap = None
        else:
            new_m, new_b = noloco_step(
                mix_m, mix_b, avg_g,
                lr=self.cfg.outer_lr,
                momentum=self.cfg.outer_momentum,
                nesterov=self.cfg.outer_nesterov,
            )
            with plane.lock:
                leaves = plane.gossip_land(
                    frag, new_m, new_b, sync=device_leaves
                )
                state["params"] = jax.tree.unflatten(self.treedef, leaves)
                with self._serve_lock:
                    self.epoch += 1
                    self.local_step = 0
                    self.samples_in_epoch = 0
                    self._blocking_snap = None
        if tr is not None:
            tr.add_span(
                "outer/apply", t_apply, time.perf_counter(),
                epoch=self.epoch - 1,
            )
        self._epoch_t0 = time.monotonic()
        outer_metrics = {
            "outer_step_s": time.monotonic() - t0,
            **d2h_row,
            "outer_allreduce_s": allreduce_s,
            "outer_apply_s": time.perf_counter() - t_apply,
            "outer_wait_s": wait_s,
            "pseudo_grad_norm": pg_norm,
            "num_peers": group_size,
            **self._round_health_metrics(),
        }
        if tr is not None:
            tr.add_span(
                "outer/step", t0p, time.perf_counter(),
                epoch=self.epoch - 1, group=group_size,
            )
            tr.gauge("outer_step_s", outer_metrics["outer_step_s"])
            tr.gauge("outer_allreduce_s", allreduce_s)
            tr.gauge("outer_wait_s", wait_s)
        self.last_outer_metrics = outer_metrics
        return state, outer_metrics

    def outer_step(self, state: dict) -> tuple[dict, dict]:
        if self._plane is not None:
            return self._outer_step_device(state)
        if self._pending is not None:  # a blocking round supersedes overlap
            state = self._poll_pending(state, block=True)
        # an abandoned overlapped round (desync re-onboard -> drop_pending)
        # may still be streaming from slot 0; never write into it live
        self._drain_abandoned()
        # parameter layout must be stable across the epoch (schema-hash
        # assertion, hivemind_diloco.py:560-568,575) -- a changed pytree
        # here means silent desync, not a recoverable condition
        assert schema_fingerprint(state["params"]) == self._schema, (
            "parameter schema changed mid-epoch"
        )
        # publish the pre-round state for onboarding peers. Holds the master
        # list by reference (no copy): every mutation below rebinds
        # self.master to a freshly built list instead of writing into these
        # arrays, so the snapshot stays bit-stable for the serve thread.
        # Left in place on failure (the pre-round snapshot is the only
        # guaranteed-consistent state if the round aborts midway).
        with self._serve_lock:
            self._blocking_snap = {
                "master": self.master,
                "epoch": self.epoch,
                # refs, not copies: the round below clones-then-rebinds the
                # optimizer, so these buf arrays stay bit-stable
                "outer_opt": self.outer_opt.state_dict_refs(),
            }
        t0 = time.monotonic()
        tr = obs.tracer()
        t0p = time.perf_counter() if tr is not None else 0.0

        # overlap the D2H transfer with the straggler wait (SURVEY hard-part
        # 2): the params are final at the boundary, so fetch them while
        # polling slow peers instead of after. Streaming fragments fetch
        # ONLY this boundary's fragment -- the off-wire transfer savings
        # must match the on-wire ones
        frag: Optional[list[int]] = None
        device_leaves = jax.tree.leaves(state["params"])
        if self._fragments is not None:
            frag = self._fragments[self.epoch % len(self._fragments)]
        # multihost: a mesh all-gather — every process's fetcher thread
        # issues the same collective, and each joins before the fan-out
        # broadcast below, so the per-process collective order is fixed
        fetcher = _BoundaryFetch(
            tr, self.epoch,
            lambda: self.world.gather_params(
                device_leaves if frag is None
                else [device_leaves[i] for i in frag]
            ),
        )
        fetcher.start()
        if self.world.is_messenger and self.cfg.outer_mode != "gossip":
            # followers skip the straggler wait: they have no peer view,
            # and they re-join the messenger at the fan-out collective.
            # Gossip skips it entirely — a pair round has no group to
            # assemble (THE point: no global barrier); the pair push-pull
            # itself bounds how long a fast worker waits on its partner.
            wait_for_peers(
                self.backend,
                target_samples=self.target_samples,
                own_epoch=self.epoch,
                strategy=self.cfg.all_reduce_strategy,
                timeout_waiting_for_peers=self.cfg.timeout_waiting_for_peers,
                log=log,
            )
        wait_s = time.monotonic() - t0
        if tr is not None:
            tr.add_span(
                "outer/barrier_wait", t0p, time.perf_counter(),
                epoch=self.epoch,
            )
        device_flat = fetcher.wait()

        if frag is not None:
            # streaming sync: only this boundary's fragment forms a
            # pseudo-gradient and rides the wire (fragment-sized arrays,
            # not the persistent full-model slots)
            pseudo_grad = [
                native.sub(self.master[i], d)
                for i, d in zip(frag, device_flat)
            ]
        else:
            # pseudo-gradient = master - current device params (persistent
            # slot buffer: the blocking path consumes it synchronously,
            # slot 0 only)
            pseudo_grad = self._pseudo_grad_into(device_flat, slot=0)
        if self._ef is not None:
            # residual folded into the wire pg in place (gossip keeps its
            # per-partner EF inside the GossipPlane instead, so self._ef
            # is None there and this is always the all-reduce path)
            self._ef.prepare(
                "main",
                frag if frag is not None else range(len(pseudo_grad)),
                pseudo_grad,
            )

        if tr is not None:
            # fused OMP dot (native fallback: np.dot) instead of a serial
            # per-leaf host reduction; device placement computes this norm
            # inside the pseudo-gradient jit instead (outer_device.py)
            sq = 0.0
            for g in pseudo_grad:
                sq += native.sqnorm(np.asarray(g, np.float32).reshape(-1))
            tr.gauge("pseudo_grad_norm", float(np.sqrt(sq)))

        t1 = time.monotonic()
        t1p = time.perf_counter() if tr is not None else 0.0
        gossip = self.cfg.outer_mode == "gossip"
        dropped = False
        mix_m: Optional[list[np.ndarray]] = None
        mix_b: Optional[list[np.ndarray]] = None
        if gossip:
            # NoLoCo (arxiv 2506.10911): mix (master, momentum) with ONE
            # locally-scheduled partner per round over a point-to-point
            # push-pull (diloco/gossip.py) — no barrier, no collective —
            # then run the unchanged Nesterov rule on the mixed state with
            # the pair-averaged pseudo-gradient (the modified-Nesterov
            # correction, expressed through step_mixed_indices)
            idxs = frag if frag is not None else list(range(len(self.master)))
            g_masters = [self.master[i] for i in idxs]
            g_bufs = None
            if self.cfg.outer_momentum != 0.0:
                oo = self.outer_opt
                # zeros when momentum never armed: wire shapes must be
                # static so both sides' sections always line up
                g_bufs = [
                    np.zeros_like(self.master[i]) if oo.bufs is None
                    else oo.bufs[i]
                    for i in idxs
                ]
            # under async staleness (ODTP_ASYNC_STALENESS > 0) the plane
            # free-runs: exchange matches any in-window partner on this
            # fragment instead of pairing per (epoch, fragment) — see the
            # fragment-alignment note in _outer_step_device_gossip
            frag_id = (
                self.epoch % len(self._fragments)
                if self._fragments is not None else 0
            )
            mix_m, mix_b, averaged, group_size, live_peers = (
                self._gossip_round(
                    g_masters, g_bufs, pseudo_grad,
                    idxs=idxs, frag_id=frag_id, epoch=self.epoch,
                )
            )
            dropped = group_size == 0
            # pair size says nothing about the swarm: peer-drop detection
            # (incl. fail_rank_drop) runs on the live-peer count instead
            self._check_group_size(live_peers)
        else:
            # the wire is the device placement's, piece for piece and tag
            # for tag (``_pieces``), so that workers of either placement
            # meet in the same rounds; here the pieces go one after another
            pieces = self._pieces(frag)
            wire = _WireRound(self, len(pieces))
            averaged = [None] * len(pseudo_grad)
            try:
                for k, piece in enumerate(pieces):
                    got = wire.reduce(k, [pseudo_grad[j] for j in piece])
                    for j, a in zip(piece, got):
                        averaged[j] = a
                group_size = wire.group_size()
                self._check_group_size(group_size)
            except BaseException:
                if self._ef is not None:
                    self._ef.abort("main")
                raise
            if self._ef is not None:
                self._ef.commit("main")
        allreduce_s = time.monotonic() - t1
        if tr is not None and gossip:
            tr.add_span(
                "outer/allreduce", t1p, time.perf_counter(),
                epoch=self.epoch, group=group_size,
            )
        t_apply = time.perf_counter()
        log.info(
            "outer step %d: %s over %d peers took %.3fs",
            self.epoch,
            "gossip exchange" if gossip else "all-reduce",
            group_size,
            allreduce_s,
        )

        # clone-then-rebind: OuterSGD.step updates params AND momentum bufs
        # in place, and a serve-thread fetch may hold references to the
        # current master/buf arrays (copies happen outside the lock); every
        # live array must stay bit-stable once published
        if not dropped:
            new_master = [m.copy() for m in self.master]
            new_opt = self.outer_opt.clone()
            if gossip:
                new_opt.step_mixed_indices(
                    new_master, mix_m, mix_b, averaged,
                    frag if frag is not None else range(len(new_master)),
                )
            elif frag is not None:
                new_opt.step_indices(new_master, averaged, frag)
            else:
                new_opt.step(new_master, averaged)
            self.master = new_master
            self.outer_opt = new_opt

        # optional periodic full state averaging (hivemind
        # average_state_every, hivemind_diloco.py:634-638): corrects any
        # drift the lossy pseudo-gradient compression accumulates
        if self._is_state_avg_epoch():
            averaged_state, n, _ = self._wan_all_reduce(
                self.master, timeout=self.cfg.averaging_timeout, tag="state"
            )
            # np.array COPIES: the result views live in a pooled backend
            # buffer that the next all_reduce call reclaims (see the
            # lifetime contract on TcpBackend.all_reduce)
            self.master = [np.array(a, dtype=np.float32) for a in averaged_state]
            log.info("averaged full state over %d peers at epoch %d", n, self.epoch)

        if dropped:
            # dropped pair round: a non-event by design. Master, momentum,
            # and per-partner EF residual all stay put; the params KEEP
            # their local progress (writing the stale master back would
            # erase this epoch's inner training), so the next boundary's
            # pseudo-gradient (master - params) re-captures the update and
            # the fresh epoch key re-pairs.
            pass
        elif frag is not None:
            # streaming semantics: only the synced fragment resets to the
            # (freshly outer-stepped) master; every other leaf KEEPS its
            # local training progress AND stays on-device (the live jax
            # arrays pass through device_put untouched, so the H2D moves
            # one fragment, not the model). Its master stays frozen until
            # its own sync boundary comes around.
            merged = list(device_leaves)
            for i in frag:
                merged[i] = self.master[i]
            state["params"] = self._leaves_to_device(merged)
        else:
            state = self._write_master_to_device(state)  # [H2D]
        if tr is not None:
            # outer SGD (clone-then-rebind) + optional state averaging + H2D
            tr.add_span(
                "outer/apply", t_apply, time.perf_counter(), epoch=self.epoch
            )

        with self._serve_lock:
            self.epoch += 1
            self.local_step = 0
            self.samples_in_epoch = 0
            # master + epoch + outer_opt are all post-round now: resume
            # serving live state (a fetch sees exactly the pre- or the
            # post-round state, never a mix)
            self._blocking_snap = None
        self._epoch_t0 = time.monotonic()
        outer_metrics = {
            "outer_step_s": time.monotonic() - t0,
            **fetcher.row(),
            "outer_allreduce_s": allreduce_s,
            "outer_apply_s": time.perf_counter() - t_apply,
            "outer_wait_s": wait_s,
            "num_peers": group_size,
            **self._round_health_metrics(None if gossip else wire.health),
        }
        if tr is not None:
            tr.add_span(
                "outer/step", t0p, time.perf_counter(),
                epoch=self.epoch - 1, group=group_size,
            )
            tr.gauge("outer_step_s", outer_metrics["outer_step_s"])
            tr.gauge("outer_allreduce_s", allreduce_s)
            tr.gauge("outer_wait_s", wait_s)
        self.last_outer_metrics = outer_metrics
        return state, outer_metrics

    def _leaves_to_device(self, leaves: list) -> dict:
        """Flat host leaves -> sharded device params. Under multihost every
        process holds identical host values (replicated master discipline)
        and fills only its addressable shards; live jax.Arrays (streaming
        fragments' unsynced leaves) pass through untouched."""
        params = jax.tree.unflatten(self.treedef, leaves)
        shardings = self.trainer.state_shardings["params"]
        return jax.tree.map(
            lambda a, s: self.world.to_global(a, s), params, shardings
        )

    def _write_master_to_device(self, state: dict) -> dict:
        state["params"] = self._leaves_to_device(self.master)
        return state

    # ------------------------------------------------------------------
    # checkpoint integration (reference: hivemind_diloco.py:697-714)
    # ------------------------------------------------------------------

    def state_dict(self) -> dict:
        if self._pending is not None:
            log.warning(
                "state_dict() with an outer round in flight; call "
                "flush(state) first for a master that includes it"
            )
        if self._plane is not None:
            # host view either placement: checkpoints are
            # placement-portable (ckpt.py serializes numpy trees)
            master, bufs = self._plane.host_state()
            sd = {
                "master": master,
                "outer_opt": {
                    "lr": self._plane.lr,
                    "momentum": self._plane.momentum,
                    "nesterov": self._plane.nesterov,
                    "bufs": bufs,
                },
                "epoch": self.epoch,
                "local_step": self.local_step,
                "samples_in_epoch": self.samples_in_epoch,
            }
            if self._ef is not None:
                sd["ef_residual"] = self._plane.ef_host_state()
            if self._gossip is not None:
                sd["gossip_ef"] = self._gossip.host_state()
            return sd
        sd = {
            "master": [m.copy() for m in self.master],
            "outer_opt": self.outer_opt.state_dict(),
            "epoch": self.epoch,
            "local_step": self.local_step,
            "samples_in_epoch": self.samples_in_epoch,
        }
        if self._ef is not None:
            sd["ef_residual"] = self._ef.host_residuals()
        if self._gossip is not None:
            # per-partner residual ledgers (diloco/gossip.py): compression
            # debt owed to each pair link survives the checkpoint
            sd["gossip_ef"] = self._gossip.host_state()
        return sd

    def load_state_dict(self, sd: dict) -> None:
        if self._plane is not None:
            # lock order is plane.lock -> _serve_lock (the serve thread's
            # device path takes them in that order too)
            opt = sd["outer_opt"]
            with self._plane.lock:
                self._plane.load(
                    sd["master"],
                    opt.get("bufs"),
                    lr=opt.get("lr"),
                    momentum=opt.get("momentum"),
                    nesterov=opt.get("nesterov"),
                )
                if self._ef is not None:
                    # residuals are placement-portable: host-placement
                    # checkpoints may carry None entries (leaves that
                    # never committed), which load as zeros
                    self._plane.load_ef(sd.get("ef_residual"))
                # scalar mirror only; the plane owns the momentum bufs
                self.outer_opt.load_state_dict({**opt, "bufs": None})
                with self._serve_lock:
                    self._blocking_snap = None
                    self.epoch = int(sd["epoch"])
                    self.local_step = int(sd["local_step"])
                    self.samples_in_epoch = int(
                        sd.get(
                            "samples_in_epoch",
                            self.local_step * self.batch_size,
                        )
                    )
            if self._gossip is not None:
                self._gossip.load(sd.get("gossip_ef"))
            return
        with self._serve_lock:
            self._blocking_snap = None  # superseded pre-round snapshot
            self.master = [
                np.asarray(m, np.float32).copy() for m in sd["master"]
            ]
            self.outer_opt.load_state_dict(sd["outer_opt"])
            if self._ef is not None:
                self._ef.load(sd.get("ef_residual"))
            self.epoch = int(sd["epoch"])
            self.local_step = int(sd["local_step"])
            # older checkpoints lack samples_in_epoch; reconstruct so a
            # mid-epoch resume reports true progress and peers' wait_for_all
            # doesn't stall
            self.samples_in_epoch = int(
                sd.get("samples_in_epoch", self.local_step * self.batch_size)
            )
        if self._gossip is not None:
            self._gossip.load(sd.get("gossip_ef"))
