"""Continuous-batching scheduler: request queue + the decode loop.

One daemon thread owns the engine and runs the classic continuous-
batching cycle — retire finished sequences (slots free immediately),
admit queued prompts into free slots (prefill joins them to the running
batch), take one decode step for every live slot, and between decode
steps give the engine a chance to hot-swap weights. An iteration's programs
are all enqueued before the host reads any of them: a cold admission leaves
its first token on the device, the step behind it takes it there, and the
reads follow in the order the chip finishes them. The loop also keeps one
decode step ahead of the host: an iteration enqueues the next step, fed the
tokens of the step before on the device, *before* it reads that step's tokens,
so the chip has the next step (and, before it, this iteration's prefills)
queued when a step ends. The first tokens of the prompts a step fed lie before
that step on the device and are read with it, each stamped ``t_first`` as it
lands, just before the step's own tokens; the iteration then emits the step,
retiring what it finished, and waits for nothing that was enqueued after it.
The next iteration's admission pass follows the emit at once, as it followed
a blocking step's. A prompt that goes in chunks (learned sparse attention, a
prompt longer than every bucket) makes its slot *prefilling* until its last
chunk is enqueued: an iteration enqueues one chunk of one prefilling slot, the
oldest first, before the decode step it enqueues, and with no slot decoding
the chunks run back to back. A prefilling slot rides no step: no token of it
is emitted and no row of its prompt is overwritten; its last chunk leaves the
first token on the device, and from there it is an admission like any other.
Who rides the next
step is said before the tokens are in hand: a request that the token in flight
ends by length does not, one that may end on an ``eos_id`` does and has its
row dropped if it did. Whatever needs the newest tokens on the host, or a
cache that no program is writing, first reads and emits the step in flight
(``_drain``). Requests are queued
by any thread via :meth:`ContinuousBatcher.submit` and signal completion
through a per-request event; nothing is ever dropped by the scheduler —
a request either completes, is rejected at submit time (prompt too long
/ queue full), or is failed explicitly when the server is torn down
mid-flight.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Optional, Sequence

import numpy as np

from opendiloco_tpu import obs
from opendiloco_tpu.models.ring_cache import ring_live_rows
from opendiloco_tpu.models.traits import refuse
from opendiloco_tpu.obs import reqtrace
from opendiloco_tpu.serve.engine import PREV_TOKEN_ON_DEVICE, Admission, ServeEngine
from opendiloco_tpu.serve.kvcache import (
    HostKVTier,
    SlotAllocator,
    common_prefix_len,
    pick_bucket,
    prefix_grid_lengths,
    prefix_key,
)

# a reused prefix must be worth the copy: below this many shared tokens
# the batcher prefills cold (the suffix pass would cover ~the whole
# prompt anyway)
MIN_PREFIX_TOKENS = 4

# slot evictions started per scheduler iteration: bounds how much page-out
# work one pass can stack between decode steps, so a long queue drains the
# batch gradually instead of stalling a whole step on D2H traffic
EVICT_PER_PASS = 2


@dataclasses.dataclass
class Request:
    prompt: list
    max_new_tokens: int
    eos_id: Optional[int] = None
    id: int = 0
    # admission control: lower tier = more important (0 interactive);
    # t_deadline is absolute time.monotonic() — a queued request past it
    # is doomed (its client gave up) and is shed instead of decoded
    priority: int = 0
    t_deadline: Optional[float] = None
    t_submit: float = 0.0
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    tokens: list = dataclasses.field(default_factory=list)
    error: Optional[str] = None
    epoch: Optional[int] = None  # weights epoch that finished the request
    cancelled: bool = False
    # request-trace id in this process's reqtrace ring (None = untraced)
    trace: Optional[str] = None
    _done: threading.Event = dataclasses.field(default_factory=threading.Event)

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    def cancel(self) -> None:
        """Ask the loop to retire this request (client went away). The
        slot frees on the next scheduler iteration — decoding stops
        instead of running the remaining tokens into a dead socket."""
        self.cancelled = True

    def finish(self, error: Optional[str] = None) -> None:
        self.error = error
        self.t_done = time.perf_counter()
        self._done.set()

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.t_done is None else self.t_done - self.t_submit

    @property
    def ttft_s(self) -> Optional[float]:
        return None if self.t_first is None else self.t_first - self.t_submit


@dataclasses.dataclass
class _Slot:
    req: Request
    cache_len: int  # tokens in the ring page (absolute position of next write)
    last_token: int
    # decode steps since this tenancy began (admit or tier restore): the
    # eviction policy's coldness signal AND its thrash guard
    resident_steps: int = 0
    # a cold admission whose first token is still on the device: the next
    # decode step feeds it there, and ``last_token`` is not one until then
    admission: Optional[Admission] = None
    t_slot: float = 0.0  # when the request got its slot


@dataclasses.dataclass
class _Rows:
    """A decode step that is enqueued and unread: the tenant each of its rows
    was enqueued for. A row goes to that ``_Slot`` object if it still holds the
    slot when the tokens arrive, and to no other."""

    tenants: dict  # slot id -> _Slot
    # of those, the ones admitted since the step before: their first tokens lie
    # before this step on the device and reach the host with its tokens
    first: list  # [(slot id, _Slot)]
    epoch: int  # the weights the step was enqueued under


@dataclasses.dataclass
class _Paused:
    """A live request whose ring page lives in the host tier: everything
    needed to resume decode exactly where it stopped, minus the K/V
    (which :class:`HostKVTier` holds keyed by ``req.id``)."""

    req: Request
    cache_len: int
    last_token: int


class ContinuousBatcher:
    def __init__(
        self,
        engine: ServeEngine,
        *,
        max_queue: int = 1024,
        swap_every_steps: int = 16,
        gauge_every_steps: int = 32,
        prefix_cache: bool = False,
        kv_tier: Optional[HostKVTier] = None,
        tier_quantum_steps: int = 8,
        tier_min_resident_steps: int = 2,
    ):
        self.engine = engine
        # prefix reuse and the host tier copy, cut and restore a slot's past
        # as the rows of one ring: whether this configuration's is that is the
        # table's to say (``models.traits``)
        if prefix_cache:
            refuse(engine.cfg, "prefix_reuse", "prefix_cache")
        if kv_tier is not None:
            refuse(engine.cfg, "page_out", "kv_tier")
        self.max_queue = int(max_queue)
        self.swap_every_steps = max(1, int(swap_every_steps))
        self.gauge_every_steps = max(1, int(gauge_every_steps))
        self.prefix_cache = bool(prefix_cache)
        # host-memory cold tier (None = today's all-resident behavior,
        # bit-identical). quantum = steps a RESUMED/long-resident slot is
        # guaranteed before a paused peer may displace it (round-robin
        # time-slicing period); min_resident = floor before a QUEUED
        # request may displace anyone (TTFT pressure evicts sooner, but
        # never a slot that has not decoded at all)
        self.kv_tier = kv_tier
        self.tier_quantum_steps = max(1, int(tier_quantum_steps))
        self.tier_min_resident_steps = max(1, int(tier_min_resident_steps))
        self.slots = SlotAllocator(engine.num_slots)
        self._active: dict[int, _Slot] = {}  # slot id -> state
        # (slot, tenant) admitted since the last decode step was enqueued, whose
        # first token the next step will feed on the device (loop thread only)
        self._awaiting: list[tuple[int, _Slot]] = []
        # (slot, tenant) whose prompts go in chunks and have chunks left, oldest
        # first: one chunk of the first an iteration (loop thread only)
        self._prefilling: list[tuple[int, _Slot]] = []
        # the decode step in flight: enqueued, its tokens not read (loop thread only)
        self._ahead: Optional[_Rows] = None
        # times the loop had to read and emit the step in flight before it
        # could act (always on), by what asked: "evict", "resume",
        # "continued_prefill", "stop", "failure"
        self.step_drains: collections.Counter = collections.Counter()
        self._queue: collections.deque[Request] = collections.deque()
        self._cond = threading.Condition()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._next_id = 0
        self.decode_steps = 0
        # wall and count of the loop iterations that admitted or stepped
        # (loop-thread only, always on, in the manner of stage_seconds)
        self.loop_seconds = 0.0
        self.loop_iterations = 0
        # the two parts of a step's iteration on either side of the engine
        # call: batch assembly, and emit through the last retire
        self.batch_seconds = 0.0
        self.emit_seconds = 0.0
        # where the request-ring spans of the next step emitted start: the end
        # of the emit before it while iterations follow one another
        self._t_window: Optional[float] = None
        # stats (mutated only by the loop thread; read racily for gauges)
        self.completed = 0
        self.rejected = 0
        self.failed = 0
        self.cancelled = 0
        self.shed = 0  # deadline-doomed requests dropped unserved
        self.total_new_tokens = 0
        # EWMA of completed-request latency: the wait estimate behind
        # Retry-After hints and the router's admission floor
        self._lat_ewma: Optional[float] = None
        self._latencies: collections.deque = collections.deque(maxlen=4096)
        self._ttfts: collections.deque = collections.deque(maxlen=4096)
        self.staleness_hist: collections.Counter = collections.Counter()
        self._rate_mark = (time.perf_counter(), 0)
        self.loop_error: Optional[str] = None
        # shared-prefix reuse accounting (live-slot ring copies + host
        # tier restores; host_prefix_hits is the tier subset)
        self.prefix_hits = 0
        self.prefix_tokens_saved = 0
        self.host_prefix_hits = 0
        # KV-tier state (loop thread only): paused requests FIFO by pause
        # time, page-outs whose D2H copy is still in flight, and prefix
        # snapshots waiting to be encoded into the tier
        self._paused: "collections.OrderedDict[int, _Paused]" = (
            collections.OrderedDict()
        )
        self._pending_evict: list = []
        self._pending_prefix: list = []
        self.evictions = 0
        self.resumes = 0
        self.paused_peak = 0

    # -- client API --------------------------------------------------------

    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: int = 16,
        eos_id: Optional[int] = None,
        priority: int = 0,
        deadline_ms: Optional[float] = None,
        trace: Optional[dict] = None,
    ) -> Request:
        """Queue a prompt; returns a Request whose ``wait()`` unblocks when
        generation completes (or it was rejected — check ``error``).

        ``deadline_ms`` is the remaining client budget: the scheduler
        orders the queue by (priority, deadline) and sheds a request
        whose deadline expires before it reaches a slot — the doomed
        never delay the in-SLO.

        ``trace`` is an optional request-trace context (schema
        TRACE_CTX_KEY shape) adopted into this process's reqtrace ring;
        every lifecycle stage the request passes — queue wait, prefill,
        decode steps, swaps, terminal — is recorded under it."""
        req = Request(
            prompt=[int(t) for t in prompt],
            max_new_tokens=int(max_new_tokens),
            eos_id=eos_id,
            priority=int(priority),
            t_deadline=(
                None
                if deadline_ms is None
                else time.monotonic() + float(deadline_ms) / 1e3
            ),
            t_submit=time.perf_counter(),
        )
        rt = reqtrace.ring()
        if rt is not None and trace is not None:
            req.trace = rt.adopt(
                trace, priority=req.priority, deadline_ms=deadline_ms
            )
        if req.t_deadline is not None and float(deadline_ms) <= 0:
            self.shed += 1
            obs.count("serve_shed", reason="deadline")
            req.finish("deadline exceeded")
            self._trace_terminal(req, "shed", "shed", reason="deadline")
            return req
        if not req.prompt:
            self.rejected += 1
            req.finish("empty prompt")
            self._trace_terminal(req, "retire", "failed", error=req.error)
            return req
        if not self.engine.prompt_fits(len(req.prompt)):
            self.rejected += 1
            req.finish(
                f"prompt length {len(req.prompt)} exceeds max prefill bucket"
            )
            self._trace_terminal(req, "retire", "failed", error=req.error)
            return req
        if req.max_new_tokens < 1:
            self.rejected += 1
            req.finish("max_new_tokens must be >= 1")
            self._trace_terminal(req, "retire", "failed", error=req.error)
            return req
        if (
            (self.engine.cfg.eva or self.engine.cfg.blocks)
            and len(req.prompt) + req.max_new_tokens > self.engine.max_context
        ):
            # a ring of rows slides past its context; EVA's pooled ring holds a
            # row per chunk of max_context positions and none wraps, and a
            # selection by blocks names a block by its rows from position 0
            self.rejected += 1
            req.finish(
                f"prompt length {len(req.prompt)} and {req.max_new_tokens} new "
                f"tokens exceed max_context {self.engine.max_context}"
            )
            self._trace_terminal(req, "retire", "failed", error=req.error)
            return req
        with self._cond:
            if self._stop.is_set():
                self.rejected += 1
                req.finish("server stopped")
                self._trace_terminal(req, "shed", "shed", reason="stopped")
                return req
            if len(self._queue) >= self.max_queue:
                self.rejected += 1
                req.finish("queue full")
                self._trace_terminal(req, "shed", "shed", reason="queue_full")
                return req
            req.id = self._next_id
            self._next_id += 1
            self._queue.append(req)
            self._cond.notify()
        return req

    @staticmethod
    def _trace_terminal(
        req: Request, stage: str, status: str, **attrs
    ) -> None:
        """Close ``req``'s trace with a zero-width terminal stage event."""
        if req.trace is None:
            return
        rt = reqtrace.ring()
        if rt is None:
            return
        rt.event(req.trace, stage, **attrs)
        rt.finish(req.trace, status, tokens=len(req.tokens), **attrs)

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> "ContinuousBatcher":
        self._thread = threading.Thread(
            target=self._run, name="odtp-serve-batcher", daemon=True
        )
        self._thread.start()
        return self

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=timeout)
        # fail whatever is still in flight so no client blocks forever
        with self._cond:
            pending = list(self._queue)
            self._queue.clear()
        for req in pending:
            self.failed += 1
            req.finish("server stopped")
            self._trace_terminal(req, "retire", "failed", error=req.error)
        for st in self._active.values():
            self.failed += 1
            st.req.finish("server stopped")
            self._trace_terminal(st.req, "retire", "failed", error=st.req.error)
        self._active.clear()
        self._fail_cold("server stopped")

    def _fail_cold(self, error: str) -> None:
        """Fail every tier-resident request (paused or mid-page-out) so no
        client blocks forever on teardown/loop death."""
        for st, _pk, _pv, _t0 in self._pending_evict:
            self.failed += 1
            st.req.finish(error)
            self._trace_terminal(st.req, "retire", "failed", error=error)
        self._pending_evict.clear()
        for p in self._paused.values():
            if self.kv_tier is not None:
                self.kv_tier.drop_paused(p.req.id)
            self.failed += 1
            p.req.finish(error)
            self._trace_terminal(p.req, "retire", "failed", error=error)
        self._paused.clear()

    def drain(self, timeout: float = 60.0) -> bool:
        """Block until queue, batch, and cold tier are empty and no decode
        step is in flight (bench teardown)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._cond:
                if (
                    not self._queue
                    and not self._active
                    and not self._paused
                    and not self._pending_evict
                    and self._ahead is None
                ):
                    return True
            time.sleep(0.01)
        return False

    # -- the decode loop ---------------------------------------------------

    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                # consecutive decode spans TILE: each starts where the
                # previous iteration's accounting ended, so everything an
                # inflight request sat through this iteration — sweeps,
                # queue checks, a co-tenant's admission prefill, retires,
                # gauges — is attributed to its decode residency and a
                # trace's stage sums reconcile with its e2e latency
                t_iter = time.perf_counter()
                if self._t_window is None:
                    self._t_window = t_iter
                steps = self.decode_steps
                self._sweep_cancelled()
                # page-outs started LAST iteration finalize here: their
                # D2H copies overlapped the decode step in between, so
                # the np materialization below is (near-)free
                self._finish_pageouts()
                admitted = self._admit()
                if self._prefilling:
                    admitted = self._prefill_chunk() or admitted
                stepped = self._decode()
                # a step counts when its tokens are emitted: at most one an
                # iteration, none in the one that enqueues a busy period's first
                if self.decode_steps > steps:
                    if self.decode_steps % self.swap_every_steps == 0:
                        self._maybe_swap()
                    if self.decode_steps % self.gauge_every_steps == 0:
                        self._publish_gauges()
                if admitted or stepped:
                    # the wall of every iteration that did work, beside the
                    # engine's stage seconds: their difference is what the
                    # loop itself costs (sweeps, admission, emit, retires)
                    t_end = time.perf_counter()
                    self.loop_seconds += t_end - t_iter
                    self.loop_iterations += 1
                    tr = obs.tracer()
                    if tr is not None:
                        tr.add_span(
                            "serve_iteration", t_iter, t_end,
                            admitted=bool(admitted), stepped=bool(stepped),
                        )
                if not stepped:
                    self._t_window = None
                if not admitted and not stepped:
                    # idle: still honor the staleness bound, then sleep
                    self._maybe_swap()
                    with self._cond:
                        if not self._queue and not self._stop.is_set():
                            self._cond.wait(timeout=0.05)
            self._drain("stop")
        except Exception as e:  # noqa: BLE001 — fail loudly, never hang clients
            self.loop_error = f"{type(e).__name__}: {e}"
            try:
                # what the step in flight finished is finished, where it can
                # still be read; everything else fails below
                self._drain("failure")
            except Exception:  # noqa: BLE001 — the step is lost with the rest
                self._ahead = None
            for slot, st in list(self._active.items()):
                self._retire(st, error=self.loop_error)
                self.slots.free(slot)
            self._active.clear()
            self._awaiting.clear()
            self._prefilling.clear()
            self._fail_cold(self.loop_error)
            with self._cond:
                pending = list(self._queue)
                self._queue.clear()
            for req in pending:
                self.failed += 1
                req.finish(self.loop_error)
                self._trace_terminal(req, "retire", "failed", error=req.error)

    def _maybe_swap(self) -> None:
        """Hot-swap check; a swap that actually happened is a pause every
        in-flight request sat through, so its duration is recorded as a
        ``swap`` span on every traced active request."""
        t0 = time.perf_counter()
        swapped = self.engine.maybe_swap()
        t1 = time.perf_counter()
        if not swapped:
            return
        if self.kv_tier is not None:
            # prefix K/V was computed under the old weights: entries at a
            # stale epoch must never serve (or be advertised) again
            self.kv_tier.purge_stale(self.engine.weights_epoch)
        rt = reqtrace.ring()
        if rt is None:
            return
        for st in self._active.values():
            if st.req.trace is not None:
                rt.span(
                    st.req.trace, "swap", t0, t1,
                    epoch=self.engine.weights_epoch,
                )

    def _sweep_cancelled(self) -> None:
        """Retire cancelled and deadline-expired requests: queued ones
        finish immediately, active ones free their slot before the next
        decode step (a client past its deadline is gone — decoding its
        remaining tokens only starves the in-SLO batch)."""
        now = time.monotonic()

        def expired(req: Request) -> bool:
            return req.t_deadline is not None and now > req.t_deadline

        with self._cond:
            if any(r.cancelled or expired(r) for r in self._queue):
                keep: collections.deque = collections.deque()
                for req in self._queue:
                    if req.cancelled:
                        self.cancelled += 1
                        req.finish("cancelled")
                        obs.count("serve_cancelled")
                        self._trace_terminal(req, "retire", "cancelled")
                    elif expired(req):
                        self.shed += 1
                        req.finish("deadline exceeded")
                        obs.count("serve_shed", reason="deadline")
                        self._trace_terminal(
                            req, "shed", "shed", reason="deadline"
                        )
                    else:
                        keep.append(req)
                self._queue = keep
        gone = [
            s
            for s, st in self._active.items()
            if st.req.cancelled or expired(st.req)
        ]
        for slot in gone:
            st = self._active.pop(slot)
            self.slots.free(slot)
            st.req.epoch = self.engine.weights_epoch
            if st.req.cancelled:
                self.cancelled += 1
                st.req.finish("cancelled")
                obs.count("serve_cancelled")
                self._trace_terminal(st.req, "retire", "cancelled")
            else:
                self.shed += 1
                st.req.finish("deadline exceeded")
                obs.count("serve_shed", reason="deadline")
                self._trace_terminal(st.req, "shed", "shed", reason="deadline")
        # paused (tier-resident) requests: same sweep, plus the tier page
        # is dropped — a dead client's cold state never pins host budget
        cold_gone = [
            rid
            for rid, p in self._paused.items()
            if p.req.cancelled or expired(p.req)
        ]
        for rid in cold_gone:
            p = self._paused.pop(rid)
            if self.kv_tier is not None:
                self.kv_tier.drop_paused(rid)
            if p.req.cancelled:
                self.cancelled += 1
                p.req.finish("cancelled")
                obs.count("serve_cancelled")
                self._trace_terminal(p.req, "retire", "cancelled")
            else:
                self.shed += 1
                p.req.finish("deadline exceeded")
                obs.count("serve_shed", reason="deadline")
                self._trace_terminal(p.req, "shed", "shed", reason="deadline")

    def _find_prefix(self, prompt: list) -> tuple[Optional[int], int]:
        """Longest usable shared prompt prefix among the live slots.

        A source qualifies while its ring has not wrapped (rows < plen
        still hold the prefix K/V), its next decode step's row included: that
        step must not wrap before the copy lands. The reused
        length is capped one short of the prompt so the suffix pass always
        has at least the final token to run (its logits seed decode)."""
        best_src, best = None, 0
        for slot, st in self._active.items():
            # the row a step in flight is writing counts as written
            if st.cache_len + self._rides(slot, st) >= self.engine.max_context:
                continue
            p = common_prefix_len(prompt, st.req.prompt)
            p = min(p, len(prompt) - 1)
            if p > best:
                best_src, best = slot, p
        if best >= MIN_PREFIX_TOKENS:
            return best_src, best
        return None, 0

    def _prefix_for(self, prompt: list) -> tuple:
        """-> (live source slot, reused length, host-tier pages): the prefix a
        continued prefill of ``prompt`` would start from, or (None, 0, None)."""
        src, plen, host = None, 0, None
        if self.prefix_cache:
            src, plen = self._find_prefix(prompt)
            if src is None and self.kv_tier is not None:
                host, plen = self._host_prefix_lookup(prompt)
        return src, plen, host

    def _pop_next(self) -> Optional[Request]:
        """Most urgent queued request: lowest priority tier first, then
        earliest deadline (deadline-free requests after deadlined ones of
        the same tier), then submit order. Linear scan — the queue is
        bounded and admit runs once per freed slot."""
        with self._cond:
            if not self._queue:
                return None
            best = min(
                self._queue,
                key=lambda r: (
                    r.priority,
                    r.t_deadline if r.t_deadline is not None else float("inf"),
                    r.id,
                ),
            )
            self._queue.remove(best)
            return best

    def _admit(self) -> bool:
        """Fill free slots, and under tiering MAKE slots when demand
        exists: resume the oldest paused request first (it already paid
        its TTFT — FIFO keeps completion latency bounded), then admit
        queued prompts; with the batch full, a queued request may
        displace the longest-resident slot (min_resident floor) and a
        paused one may displace a slot that has held its quantum —
        round-robin time-slicing over more sequences than the device
        ring holds."""
        admitted = False
        evictions = 0
        while True:
            if self.slots.num_free:
                if self._paused:
                    self._resume_one(self.slots.alloc())
                    admitted = True
                    continue
                req = self._pop_next()
                if req is None:
                    break
                self._admit_into(self.slots.alloc(), req)
                admitted = True
                continue
            if self.kv_tier is None or evictions >= EVICT_PER_PASS:
                break
            req = self._pop_next()
            if req is not None:
                # TTFT pressure: a never-started request is worth an
                # early eviction (the displaced sequence keeps its state
                # in the tier and rotates back in)
                if self._evict_one(self.tier_min_resident_steps):
                    evictions += 1
                    self._admit_into(self.slots.alloc(), req)
                    admitted = True
                    continue
                with self._cond:
                    self._queue.append(req)  # nothing evictable yet
                break
            if self._paused:
                # pure rotation: oldest paused displaces the slot that
                # has held the batch longest, once per quantum
                if self._evict_one(self.tier_quantum_steps):
                    evictions += 1
                    self._resume_one(self.slots.alloc())
                    admitted = True
                    continue
            break
        return admitted

    def _admit_into(self, slot: int, req: Request) -> None:
        """One admission path, whose first token is read at once or with the
        step that takes it on the device (an iteration after that step is
        enqueued), by the kind of admission and of step: a cold prefill's
        token stays on the device, where the step takes it, unless nothing
        will step it there (a request of one token needs no step: its read
        waits for the step in flight, which lies before it on the device, and
        no longer); a continued prefill (live prefix, host tier) reads as it
        always did, behind a drained step."""
        st = _Slot(
            req=req, cache_len=len(req.prompt), last_token=0,
            t_slot=time.perf_counter(),
        )
        src, plen, host = self._prefix_for(req.prompt)
        # a continued prefill copies a live slot's rows and is read at once:
        # behind a drained step, which may have retired the source
        if (src is not None or host is not None) and self._drain("continued_prefill"):
            src, plen, host = self._prefix_for(req.prompt)
        # in the batch before a program can raise: the loop's failure handler
        # then fails this request with the others
        self._active[slot] = st
        if src is not None:
            tok, _ = self.engine.admit(
                slot, req.prompt, prefix_src=src, prefix_len=plen
            )
            self.prefix_hits += 1
            self.prefix_tokens_saved += plen
            obs.count("serve_prefix_hits")
            obs.count("serve_prefix_tokens_saved", plen)
        elif host is not None:
            tok, _ = self.engine.admit(slot, req.prompt, host_prefix=host)
            self.prefix_hits += 1
            self.host_prefix_hits += 1
            self.prefix_tokens_saved += plen
            obs.count("serve_prefix_hits")
            obs.count("serve_host_prefix_hits")
            obs.count("serve_prefix_tokens_saved", plen)
        elif self.engine.needs_chunks(len(req.prompt)):
            # its chunks follow, one an iteration (``_prefill_chunk``)
            st.admission = self.engine.admit_begin(slot, req.prompt)
            self._prefilling.append((slot, st))
            return
        else:
            adm = self.engine.admit_enqueue(slot, req.prompt)
            self._maybe_store_prefix(slot, req.prompt)
            if req.max_new_tokens > 1:
                st.admission = adm
                self._awaiting.append((slot, st))
                return
            tok = self.engine.admit_resolve(adm)
        self._first_token(slot, st, tok, time.perf_counter(), plen)

    def _prefill_chunk(self) -> bool:
        """One chunk of the oldest prefilling slot's prompt, enqueued before this
        iteration's decode step -> whether there was one. With its last chunk
        the slot awaits its first token as a cold admission does: the next step
        feeds it on the device (a request of one token is read at once)."""
        while self._prefilling:
            slot, st = self._prefilling[0]
            if self._active.get(slot) is not st:  # cancelled or shed meanwhile
                self._prefilling.pop(0)
                continue
            if self.engine.admit_chunk(st.admission):
                self._prefilling.pop(0)
                if st.req.max_new_tokens > 1:
                    self._awaiting.append((slot, st))
                else:
                    tok = self.engine.admit_resolve(st.admission)
                    self._first_token(slot, st, tok, time.perf_counter())
            return True
        return False

    def _first_token(
        self, slot: int, st: _Slot, tok: int, t_first: float, prefix_reused: int = 0
    ) -> None:
        """``st``'s first token has reached the host at ``t_first``: stamp and
        append it; a request that it ends retires here, and its slot is free."""
        req = st.req
        req.t_first = t_first
        rt = reqtrace.ring()
        if rt is not None and req.trace is not None:
            rt.span(
                req.trace, "queue", req.t_submit, st.t_slot, slot=slot
            )
            rt.span(
                req.trace, "prefill", st.t_slot, req.t_first,
                tokens=len(req.prompt),
                bucket=pick_bucket(len(req.prompt), self.engine.prefill_buckets),
                prefix_reused=prefix_reused,
            )
        req.tokens.append(tok)
        st.last_token, st.admission = tok, None
        if self._finished(st):
            del self._active[slot]
            self._retire(st)
            self.slots.free(slot)

    # -- KV tiering (evict / restore / host prefix store) --------------------

    def _evict_one(self, min_resident: int) -> bool:
        """Page the coldest evictable slot out to the host tier and free
        it. Coldest = most decode steps since its tenancy began (every
        live slot decodes every step, so residency age IS the LRU order
        by last page-in); ``min_resident`` is the thrash guard. The D2H
        copy is only STARTED here — :meth:`_finish_pageouts` encodes it
        into the tier next iteration, after the transfer overlapped a
        decode step."""
        # in-flight page-outs land in the tier next iteration: count them
        # against the pin budget now or a 2-evict pass can overflow it
        if (
            self.kv_tier.paused_count + len(self._pending_evict)
            >= self.kv_tier.host_slots
        ):
            return False
        best_slot = self._coldest(min_resident)
        if best_slot is None:
            return False
        # a page-out reads the slot's rows and its newest token: both are the
        # step's in flight until that is read. What the step ended may have
        # made the room itself; else the choice is made again on what is left
        if self._drain("evict"):
            if self.slots.num_free:
                return True
            best_slot = self._coldest(min_resident)
            if best_slot is None:
                return False
        st = self._active.pop(best_slot)
        t0 = time.perf_counter()
        rows = ring_live_rows(st.cache_len, self.engine.max_context)
        pk, pv = self.engine.fetch_slot_pages(best_slot, rows)
        self._pending_evict.append((st, pk, pv, t0))
        self.slots.free(best_slot)
        self.evictions += 1
        obs.count("serve_tier_evictions")
        return True

    def _coldest(self, min_resident: int) -> Optional[int]:
        """The evictable slot longest in the batch (a step in flight counts
        as taken), or None."""
        best_slot, best = None, min_resident - 1
        for slot, st in self._active.items():
            rides = self._rides(slot, st)
            if st.admission is not None and not rides:
                continue  # its first token is nobody's to read yet
            resident = st.resident_steps + rides
            if resident > best:
                best_slot, best = slot, resident
        return best_slot

    def _finish_pageouts(self) -> None:
        if not self._pending_evict:
            self._finish_prefix_stores()
            return
        pending, self._pending_evict = self._pending_evict, []
        rt = reqtrace.ring()
        for st, pk, pv, t0 in pending:
            k, v = np.asarray(pk), np.asarray(pv)
            self.kv_tier.put_paused(st.req.id, k, v)
            self._paused[st.req.id] = _Paused(
                req=st.req, cache_len=st.cache_len, last_token=st.last_token
            )
            t1 = time.perf_counter()
            self.engine.stage_seconds["page_out"] += t1 - t0
            obs.count("serve_page_out_bytes", k.nbytes + v.nbytes)
            if rt is not None and st.req.trace is not None:
                rt.span(
                    st.req.trace, "page_out", t0, t1,
                    tokens=st.cache_len, bytes=k.nbytes + v.nbytes,
                )
        self.paused_peak = max(self.paused_peak, len(self._paused))
        self._finish_prefix_stores()

    def _resume_one(self, slot: int) -> None:
        """Page the oldest paused request back in and rejoin the batch
        exactly where it stopped (tokens, cache_len, last_token are the
        request's own; the ring rows come back from the tier)."""
        self._drain("resume")
        rid, p = self._paused.popitem(last=False)
        t0 = time.perf_counter()
        k, v = self.kv_tier.pop_paused(rid)
        self.engine.install_slot_pages(slot, k, v)
        t1 = time.perf_counter()
        self._active[slot] = _Slot(
            req=p.req, cache_len=p.cache_len, last_token=p.last_token
        )
        self.resumes += 1
        obs.count("serve_tier_resumes")
        obs.count("serve_page_in_bytes", k.nbytes + v.nbytes)
        rt = reqtrace.ring()
        if rt is not None and p.req.trace is not None:
            rt.span(
                p.req.trace, "page_in", t0, t1,
                tokens=p.cache_len, bytes=k.nbytes + v.nbytes,
            )

    def _host_prefix_lookup(self, prompt: list):
        """Longest grid-length prompt prefix resident in the host tier at
        the CURRENT weights epoch (stale-epoch entries never serve)."""
        epoch = self.engine.weights_epoch
        for glen in prefix_grid_lengths(len(prompt)):
            got = self.kv_tier.get_prefix(prefix_key(prompt, glen), glen, epoch)
            if got is not None:
                return (got[0], got[1], glen), glen
        return None, 0

    def _maybe_store_prefix(self, slot: int, prompt: list) -> None:
        """After a cold prefill, snapshot the prompt's longest grid-length
        prefix into the tier (async D2H; encoded next iteration). This is
        what makes prefix reuse survive slot churn and what the fleet
        directory advertises."""
        if self.kv_tier is None or not self.prefix_cache:
            return
        grid = prefix_grid_lengths(len(prompt))
        if not grid:
            return
        glen = grid[0]
        key = prefix_key(prompt, glen)
        epoch = self.engine.weights_epoch
        if self.kv_tier.has_prefix(key, glen, epoch):
            return
        t0 = time.perf_counter()
        pk, pv = self.engine.fetch_slot_pages(slot, glen)
        self._pending_prefix.append((key, glen, epoch, pk, pv, t0))

    def _finish_prefix_stores(self) -> None:
        if not self._pending_prefix:
            return
        pending, self._pending_prefix = self._pending_prefix, []
        for key, glen, epoch, pk, pv, t0 in pending:
            if epoch != self.engine.weights_epoch:
                continue  # weights swapped since the snapshot: stale, drop
            k, v = np.asarray(pk), np.asarray(pv)
            self.kv_tier.put_prefix(key, glen, epoch, k, v)
            self.engine.stage_seconds["page_out"] += time.perf_counter() - t0
            obs.count("serve_page_out_bytes", k.nbytes + v.nbytes)

    def resident_prefixes(self) -> list:
        """``[[key, glen], ...]`` the fleet health channel advertises —
        epoch-valid host-tier prefix entries (read racily off-thread;
        the tier's dict snapshot is safe under the GIL)."""
        if self.kv_tier is None:
            return []
        return self.kv_tier.resident_prefixes(self.engine.weights_epoch)

    def _rides(self, slot: int, st: _Slot) -> bool:
        """Whether the step in flight is computing a token for ``st``."""
        return self._ahead is not None and self._ahead.tenants.get(slot) is st

    def _decode(self) -> bool:
        """One iteration's decode work, a step ahead of the host: enqueue the
        next step, then read the one in flight (behind the first tokens of the
        prompts it fed, which lie before it on the device) and emit it
        -> whether there was any."""
        # the tenants whose prompts are still arriving in chunks ride no step
        # (none, and nothing made for them, but under learned sparse attention)
        prefilling = {id(st) for _, st in self._prefilling} if self._prefilling else ()
        if self._ahead is None and (not self._active or (
            prefilling and all(id(st) in prefilling for st in self._active.values())
        )):
            return False  # nobody decodes: no slot, or every prompt still arriving
        S = self.engine.num_slots
        t_batch = time.perf_counter()
        # who is in the next step, said before the tokens in flight are in
        # hand: a slot whose request that token ends by length is not (nothing
        # is computed for it); one that may end on an eos is, and its row is
        # dropped if it did. A slot's token is on the host, or the step's in
        # flight, or its admission's: the last two stay on the device
        tokens = np.zeros((S,), np.int32)
        lens = np.zeros((S,), np.int32)
        tenants = {}
        riding = self._ahead.tenants if self._ahead is not None else {}
        for slot, st in self._active.items():
            if id(st) in prefilling:
                continue  # rides no step until its last chunk is enqueued
            if riding.get(slot) is st:
                # its tokens so far: those on the host, a first token that the
                # step in flight took on the device, and that step's own
                have = len(st.req.tokens) + (st.admission is not None) + 1
                if have >= st.req.max_new_tokens:
                    continue
                tokens[slot], lens[slot] = PREV_TOKEN_ON_DEVICE, st.cache_len + 1
            else:
                tokens[slot], lens[slot] = st.last_token, st.cache_len
            tenants[slot] = st
        read, rows = self._ahead, None
        if tenants:
            # counted under the weights the step is enqueued with
            rows = _Rows(tenants, self._awaiting, self.engine.weights_epoch)
            self._awaiting = []
            self.staleness_hist[self.engine.staleness()] += 1
            next_tokens = self._step_ahead(tokens, lens)
            self._ahead = rows
        else:
            next_tokens = self._step_ahead()
        step_t0, step_t1 = self.engine.decode_bounds
        self.batch_seconds += step_t0 - t_batch
        tr = obs.tracer()
        if tr is not None:
            tr.add_span("serve_batch", t_batch, step_t0)
        if read is not None:
            self._emit(read, next_tokens, step_t1)
            if rows is None:
                self._ahead = None  # read, and emitted too: ``drain`` may return
        return True

    def _step_ahead(self, tokens=None, lens=None) -> Optional[np.ndarray]:
        """The engine's call. If it raises, the rows it was reading and those
        it was enqueuing are lost with the loop: nobody's to drain."""
        try:
            return self.engine.step_ahead(tokens, lens)
        except BaseException:
            self._ahead = None
            raise

    def _emit(self, step: _Rows, next_tokens: np.ndarray, t_read: float) -> None:
        """A step's tokens have reached the host (the engine's call returned at
        ``t_read``): each row goes to the tenant it was enqueued for, unless
        that one has left its slot since (cancelled, shed, or ended on the
        token before); finished requests retire here. The span covers batch
        assembly, the engine call, and token emit, so per-step scheduler time
        is attributed to the requests it served, and a trace's stage sums
        reconcile with its end-to-end latency."""
        t0 = self._t_window if self._t_window is not None else t_read
        # the prompts this step fed were read before it, each stamped with the
        # instant its first token reached the host. A request that token ends
        # is gone here, and the rows computed for its slot are dropped
        for slot, st in step.first:
            if self._active.get(slot) is st:
                self._first_token(slot, st, st.admission.token, st.admission.t_token)
        live = [
            (slot, st) for slot, st in step.tenants.items() if self._active.get(slot) is st
        ]
        self.decode_steps += 1
        obs.count("serve_tokens_generated", len(live))
        done = []
        for slot, st in live:
            tok = int(next_tokens[slot])
            st.req.tokens.append(tok)
            st.cache_len += 1
            st.last_token = tok
            st.resident_steps += 1
            self.total_new_tokens += 1
            if self._finished(st):
                done.append((slot, st))
        # the next step's window starts HERE, so span recording, retires,
        # and swap/gauge checks below are attributed to the step that pays
        # for them
        t1 = self._t_window = time.perf_counter()
        rt = reqtrace.ring()
        if rt is not None:
            for _, st in live:
                if st.req.trace is not None:
                    # a just-admitted slot's window starts where its own
                    # prefill ended, never before (no self double-count)
                    rt.span(
                        st.req.trace, "decode", max(t0, st.req.t_first), t1,
                        batch=len(live), tokens=1,
                        kernel=self.engine.decode_kernel,
                    )
        for slot, st in done:
            del self._active[slot]
            self.slots.free(slot)
            self._retire(st, epoch=step.epoch)
        t_emit = time.perf_counter()
        self.emit_seconds += t_emit - t_read
        tr = obs.tracer()
        if tr is not None:
            tr.add_span("serve_emit", t_read, t_emit)

    def _drain(self, reason: str) -> bool:
        """Read and emit the step in flight, for an operation that needs the
        newest tokens on the host or a cache no program is writing -> whether
        there was one (counted by ``reason``)."""
        if self._ahead is None:
            return False
        tokens = self._step_ahead()
        self._emit(self._ahead, tokens, self.engine.decode_bounds[1])
        self._ahead = None
        self.step_drains[reason] += 1
        obs.count("serve_step_drains", reason=reason)
        return True

    def _finished(self, st: _Slot) -> bool:
        req = st.req
        if len(req.tokens) >= req.max_new_tokens:
            return True
        return req.eos_id is not None and st.last_token == req.eos_id

    def _retire(
        self, st: _Slot, error: Optional[str] = None, epoch: Optional[int] = None
    ) -> None:
        """``epoch``: the weights its last step was enqueued under, where a
        step ended it (a swap may have come between that and the read)."""
        req = st.req
        if req.eos_id is not None and req.tokens and req.tokens[-1] == req.eos_id:
            req.tokens.pop()  # eos terminates, is not part of the text
        req.epoch = self.engine.weights_epoch if epoch is None else epoch
        req.finish(error)
        self._trace_terminal(
            req,
            "retire",
            "done" if error is None else "failed",
            epoch=req.epoch,
            **({} if error is None else {"error": error}),
        )
        if error is None:
            self.completed += 1
            self._latencies.append(req.latency_s)
            ewma = self._lat_ewma
            self._lat_ewma = (
                req.latency_s
                if ewma is None
                else 0.8 * ewma + 0.2 * req.latency_s
            )
            if req.ttft_s is not None:
                self._ttfts.append(req.ttft_s)
            obs.count("serve_requests_completed")
        else:
            self.failed += 1

    def estimate_wait_s(self) -> float:
        """Rough time a new request spends queued: queue length over slot
        parallelism, paced by the completed-latency EWMA. Feeds the 503
        Retry-After hint and the router's admission estimate — a hint,
        not a promise."""
        ewma = self._lat_ewma if self._lat_ewma is not None else 0.25
        with self._cond:
            depth = len(self._queue)
        return (depth / max(1, self.slots.num_slots)) * ewma

    # -- metrics -----------------------------------------------------------

    def _publish_gauges(self) -> None:
        staleness = self.engine.staleness()
        wd = obs.anomaly.watchdog()
        if wd is not None:
            # a breach here means maybe_swap() could NOT restore the bound
            # (e.g. the trainer stalled and no fresh snapshot exists): the
            # watchdog records it, serving continues on the stale snapshot
            wd.serve_staleness(
                staleness,
                self.engine.max_stale_rounds,
                exemplars=self._slo_exemplars(),
            )
        if obs.tracer() is None:
            # every number below is read by a gauge alone, and with no
            # tracer a gauge is nothing. A rate over the unarmed past would
            # be no rate of now: the next armed call starts the mark anew
            self._rate_mark = None
            return
        lat = np.asarray(self._latencies, np.float64)
        if lat.size:
            obs.gauge("serve_p50_ms", float(np.percentile(lat, 50)) * 1e3)
            obs.gauge("serve_p99_ms", float(np.percentile(lat, 99)) * 1e3)
        now = time.perf_counter()
        if self._rate_mark is not None:
            t0, n0 = self._rate_mark
            if now > t0:
                obs.gauge(
                    "serve_tokens_per_s", (self.total_new_tokens - n0) / (now - t0)
                )
        self._rate_mark = (now, self.total_new_tokens)
        obs.gauge(
            "serve_batch_occupancy", self.slots.num_active / self.slots.num_slots
        )
        obs.gauge("serve_snapshot_staleness", staleness)
        if self.kv_tier is not None:
            obs.gauge("serve_tier_occupancy", self.kv_tier.occupancy())
            obs.gauge("serve_tier_paused", len(self._paused))
            obs.gauge("serve_tier_prefix_entries", self.kv_tier.prefix_count)
            obs.gauge("serve_tier_stored_bytes", self.kv_tier.stored_bytes())
        with self._cond:
            obs.gauge("serve_queue_depth", len(self._queue))

    @staticmethod
    def _slo_exemplars(n: int = 3) -> list:
        """Trace ids of the slowest recently completed requests in this
        process's reqtrace ring — the evidence attached to staleness /
        SLO-breach watchdog trips and fleet health rows so a breach
        names the requests that caused it."""
        rt = reqtrace.ring()
        if rt is None:
            return []
        return [ex["id"] for ex in rt.exemplars(n)]

    def health(self) -> dict:
        """Compact load vector for the fleet health plane (push replies,
        overseer roll-ups, autoscaler): cheap enough to compute on every
        push-channel reply."""
        lat = np.asarray(self._latencies, np.float64)
        with self._cond:
            depth = len(self._queue)
        out = {
            "queue_depth": depth,
            "occupancy": round(
                self.slots.num_active / self.slots.num_slots, 4
            ),
            "p99_ms": (
                round(float(np.percentile(lat, 99)) * 1e3, 3)
                if lat.size
                else None
            ),
            "wait_estimate_s": round(self.estimate_wait_s(), 4),
            "completed": self.completed,
            "shed": self.shed,
        }
        if self.kv_tier is not None:
            out["tier_occupancy"] = round(self.kv_tier.occupancy(), 4)
            out["tier_paused"] = len(self._paused)
        exemplars = self._slo_exemplars()
        if exemplars:
            out["slo_exemplars"] = exemplars
        return out

    def stats(self) -> dict:
        """Point-in-time summary for the bench / health endpoint."""
        lat = np.asarray(self._latencies, np.float64) * 1e3
        ttft = np.asarray(self._ttfts, np.float64) * 1e3

        def pct(a, q):
            return float(np.percentile(a, q)) if a.size else None

        return {
            "completed": self.completed,
            "rejected": self.rejected,
            "failed": self.failed,
            "cancelled": self.cancelled,
            "shed": self.shed,
            "queued": len(self._queue),
            "active": self.slots.num_active,
            "decode_steps": self.decode_steps,
            "loop_iterations": self.loop_iterations,
            "loop_seconds": round(self.loop_seconds, 6),
            "batch_seconds": round(self.batch_seconds, 6),
            "emit_seconds": round(self.emit_seconds, 6),
            "new_tokens": self.total_new_tokens,
            "latency_ms": {
                "p50": pct(lat, 50),
                "p90": pct(lat, 90),
                "p99": pct(lat, 99),
                "mean": float(lat.mean()) if lat.size else None,
            },
            "ttft_ms": {"p50": pct(ttft, 50), "p99": pct(ttft, 99)},
            "weight_swaps": self.engine.swap_count,
            "weights_epoch": self.engine.weights_epoch,
            "staleness": self.engine.staleness(),
            # int keys in numeric order: json.dump(sort_keys=True) sorts
            # dict items BEFORE stringifying, so the artifact reads
            # 0, 1, 2, ... 10 instead of the lexicographic "0", "1", "10"
            "staleness_hist": {
                int(k): v for k, v in sorted(self.staleness_hist.items())
            },
            "stages_s": {
                k: round(v, 6) for k, v in self.engine.stage_seconds.items()
            },
            "phase_seconds": {
                stage: {k: round(v, 6) for k, v in phases.items()}
                for stage, phases in self.engine.phase_seconds.items()
            },
            "phase_calls": dict(self.engine.phase_calls),
            # of the cold admissions in phase_calls["prefill"], those whose
            # first token a decode step took on the device
            "admissions_deferred": self.engine.admissions_deferred,
            # of the steps in phase_calls["decode"], those enqueued while the
            # step before them was unread; and the times the loop had to read
            # the step in flight before it could act, by what asked
            "steps_ahead": self.engine.steps_ahead,
            "step_drains": dict(self.step_drains),
            # what a grid step of the decode kernel holds at the engine's shapes,
            # and the grid steps that makes a decode step (zeros: no such kernel)
            "decode_plan": {
                f"serve_{name}": int(value)
                for name, value in self.engine.decode_plan_stats().items()
            },
            # what the indexer and the attention under its selection did, and the
            # prompts admitted in chunks (zeros without learned sparse attention);
            # the form a chunk's grouped-query attention takes ("": no such chunk)
            # and a chunk's latent attention by kind of layer ({}: no such kinds)
            "dsa": {
                **{name: getattr(self.engine, name) for name in (
                    "dsa_rows_scored", "dsa_rows_selected", "dsa_index_bytes_read",
                    "dsa_kv_bytes_read", "index_cache_resident_bytes", "prefill_chunks",
                    "prefill_chunk_tokens", "chunk_form",
                )},
                "latent_chunk_forms": {
                    kind: forms["chunk"] for kind, forms in self.engine.latent_forms.items()
                },
            },
            # what latent attention did with its rings by kind of layer: the
            # full layers' (zeros without latent attention), the sliding layers'
            # (zeros without them), and which form each kind's programs take
            "latent": {
                **{name: getattr(self.engine, name) for name in (
                    "latent_rows_read", "latent_bytes_moved", "latent_cache_resident_bytes",
                    "swa_rows_read", "swa_bytes_moved", "swa_cache_resident_bytes",
                )},
                "forms": self.engine.latent_forms,
            },
            # what a grouped-query stack with sliding layers did with its rings
            # by kind (zeros without one), and which form each kind's decode
            # step and chunk take
            "kinds": {
                **{name: getattr(self.engine, name) for name in (
                    "full_rows_read", "swa_rows_read", "kinds_bytes_moved",
                )},
                "forms": self.engine.kind_forms,
            },
            # what the lightning mix and the attention under a selection by
            # blocks did (zeros without such a stack), what their state and
            # pooled ring hold, and which form the step and the chunk take
            "sala": {
                **{name: getattr(self.engine, name) for name in (
                    "lightning_tokens", "lightning_state_bytes_moved", "pooled_keys_scored",
                    "blocks_chosen", "block_rows_read", "block_tiles_read", "block_tiles_live",
                    "dense_len_calls", "lightning_state_resident_bytes",
                    "pooled_cache_resident_bytes",
                )},
                "forms": self.engine.block_forms,
            },
            # what the kda mixer did (zeros without it), what its states and
            # tails hold, and which form the step and the chunk take
            "kda": {
                **{name: getattr(self.engine, f"kda_{name}") for name in (
                    "step_tokens", "chunk_tokens", "blocks_solved", "state_bytes_moved",
                    "state_resident_bytes", "tail_resident_bytes",
                )},
                "forms": self.engine.kda_forms,
            },
            # what EVA attention did with its two rings (zeros without it)
            "eva": {
                **{name: getattr(self.engine, f"eva_{name}") for name in (
                    "local_rows_read", "pooled_rows_read", "chunks_pooled",
                    "window_restarts", "cache_bytes_moved", "cache_resident_bytes",
                )},
                "forms": self.engine.eva_forms,
            },
            # which form each bucket's whole-prompt causal attention takes, and
            # the cold admissions that went through the flash forward kernel
            "prefill": {
                "forms": {str(b): form for b, form in self.engine.prefill_forms.items()},
                "flash_admissions": self.engine.prefill_flash_admissions,
            },
            "prefix": {
                "hits": self.prefix_hits,
                "host_hits": self.host_prefix_hits,
                "tokens_saved": self.prefix_tokens_saved,
            },
            "tier": (
                {
                    **self.kv_tier.stats(),
                    "evictions": self.evictions,
                    "resumes": self.resumes,
                    "paused": len(self._paused),
                    "paused_peak": self.paused_peak,
                }
                if self.kv_tier is not None
                else None
            ),
            "loop_error": self.loop_error,
        }
