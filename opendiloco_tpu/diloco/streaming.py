"""Streaming eager outer sync: staggered in-phase fragment all-reduce.

Composes the Streaming DiLoCo fragment schedule (arxiv 2501.18512) with
Eager Updates overlap (arxiv 2502.12996): instead of one bulk exchange at
the epoch boundary (or one fragment per boundary, as the blocking
streaming path does), EVERY fragment syncs EVERY epoch, launched
mid-inner-phase on a staggered inner-step schedule — fragment k's
all-reduce opens at inner step ``min(H, int(k*stagger*H/N)+1)`` and lands
whenever the swarm completes it, while inner training keeps stepping.
The boundary itself becomes bookkeeping: no barrier, no wire traffic, no
params rewrite.

Per fragment round:

  launch (training thread, trainer post-dispatch hook):
    pg    = master_frag - params_frag          (the fragment's own clock:
                                                its "boundary" is its
                                                launch step)
    eager: params_frag += est(pg) - master_frag  first-step estimate from
                                                 the LOCAL pseudo-gradient
    comm thread opens all_reduce(tag=f"frag{k}", epoch=e)

  land (training thread, next hook tick after the future resolves):
    true  = outer_sgd(master_frag, avg)
    eager: params_frag += true - est           telescopes with the launch
                                               delta to exactly true - pg
                                               boundary — same rewrite as
                                               blocking, split in two
    delayed: params_frag += true - boundary
    master_frag <- true                        (rebind; never mutated in
                                                place — serve snapshots
                                                stay bit-stable)

The master is therefore *fragment-mixed* while rounds are in flight: each
fragment's master sits at its own landing clock. That is the Streaming
DiLoCo contract — an onboarding peer adopting a mixed master re-syncs
fragment-by-fragment within one epoch. A failed round (elastic swarm,
timeout) is dropped with a warning: the eager estimate simply stays
applied and the fragment's next pseudo-gradient (master - params)
re-captures it, so nothing needs unwinding.

Cross-peer determinism: the launch schedule is a pure function of
(local_steps, n_fragments, stream_stagger) and the fragment partition is
derived from the shared schema, so every peer opens round
``frag{k}-epoch-{e}`` with identically-shaped arrays and no coordination.
Single-process only (the device plane is not collective-aware); the
optimizer falls back to blocking fragment sync under multihost.
"""

from __future__ import annotations

import concurrent.futures
import threading
import time
from typing import Any, Optional

import jax
import numpy as np

from opendiloco_tpu import native, obs
from opendiloco_tpu.diloco.backend import AllReduceError
from opendiloco_tpu.diloco.outer_optimizer import OuterSGD, noloco_step
from opendiloco_tpu.utils.logger import get_text_logger

log = get_text_logger(__name__)


def launch_schedule(
    local_steps: int, n_fragments: int, stagger: float
) -> list[int]:
    """Inner-step launch slots for each fragment (1-based: slot s fires
    right after the s-th inner step of the epoch dispatches). Pure
    function of shared config — every peer derives the identical
    schedule, which is what keys fragment k's all-reduce to the same
    round on every worker. ``stagger=1.0`` spreads launches evenly
    across the phase; smaller values front-load them (more landing
    slack, less inner compute hidden behind each round)."""
    h, n = int(local_steps), int(n_fragments)
    return [min(h, int(k * stagger * h / n) + 1) for k in range(n)]


class StreamScheduler:
    """Per-fragment round scheduler: N concurrent in-flight all-reduces
    replacing the optimizer's at-most-one ``_pending`` slot. All entry
    points run on the training thread (launch/land math is either numpy
    on host placement or fused jit on device placement); only the
    all-reduce itself rides a daemon comm thread per round."""

    def __init__(self, opt):
        self.opt = opt
        self.n = len(opt._fragments)
        self.schedule = launch_schedule(
            opt.cfg.local_steps, self.n, opt.cfg.stream_stagger
        )
        # at most ONE in-flight round per fragment: a relaunch block-lands
        # its predecessor (same-fragment rounds are ordered; concurrency
        # is across fragments)
        self._inflight: dict[int, dict[str, Any]] = {}
        self._launched: set[int] = set()

    # -- heartbeat ---------------------------------------------------------

    def tick(self, state: dict, step: int) -> dict:
        """One scheduler heartbeat, invoked from the trainer's
        post-dispatch hook after every inner step: land whatever rounds
        have resolved (freeing their fragments), then open the rounds
        whose slot has come up. ``<=`` (not ``==``) self-heals a missed
        slot after a mid-epoch restore."""
        for k in list(self._inflight):
            if self._inflight[k]["future"].done():
                state = self._land(state, k)
        for k in range(self.n):
            if k not in self._launched and self.schedule[k] <= step:
                state = self._launch(state, k)
        return state

    def boundary(self, state: dict) -> tuple[dict, dict]:
        """The epoch boundary, reduced to bookkeeping: no barrier, no
        wire traffic, no params rewrite — in-flight rounds keep flying
        across it (they carry their launch epoch in the round key). Only
        defensive work happens here: fragments whose slot never fired
        (elastic inner-phase truncation) launch now."""
        t0 = time.monotonic()
        tr = obs.tracer()
        t0p = time.perf_counter() if tr is not None else 0.0
        for k in range(self.n):
            if k not in self._launched:
                state = self._launch(state, k)
        for k in list(self._inflight):
            if self._inflight[k]["future"].done():
                state = self._land(state, k)
        opt = self.opt
        with opt._serve_lock:
            opt.epoch += 1
            opt.local_step = 0
            opt.samples_in_epoch = 0
        self._launched.clear()
        opt._epoch_t0 = time.monotonic()
        metrics = {
            "outer_step_s": time.monotonic() - t0,
            "outer_overlapped": 1,
            "outer_streaming_fragments": self.n,
            "outer_inflight_fragments": len(self._inflight),
        }
        if tr is not None:
            tr.add_span(
                "outer/launch", t0p, time.perf_counter(), epoch=opt.epoch - 1
            )
            tr.gauge("outer_inflight_fragments", len(self._inflight))
        opt.last_outer_metrics = metrics
        return state, metrics

    def flush(self, state: dict) -> dict:
        """Block-land every in-flight round (checkpoint/shutdown: the
        master must reflect every launched round)."""
        for k in list(self._inflight):
            state = self._land(state, k, block=True)
        return state

    def drop_all(self) -> None:
        """Abandon all in-flight rounds (state adoption supersedes them).
        Running reduces can't be cancelled, but each round owns its
        fragment-sized buffers outright, so abandonment needs no drain —
        the records are simply forgotten."""
        for rec in self._inflight.values():
            rec["future"].cancel()
        self._inflight.clear()
        self._launched.clear()

    def wait_inflight(self, timeout: float = 60.0) -> None:
        """Test helper: wait until every in-flight future resolved
        WITHOUT landing it (landing needs the training thread's state)."""
        deadline = time.monotonic() + timeout
        for rec in list(self._inflight.values()):
            remaining = max(deadline - time.monotonic(), 0.001)
            concurrent.futures.wait([rec["future"]], timeout=remaining)

    # -- launch ------------------------------------------------------------

    def _launch(self, state: dict, k: int) -> dict:
        opt = self.opt
        if k in self._inflight:
            # predecessor round still flying at this fragment's next
            # slot: land it first (the one place streaming ever blocks)
            state = self._land(state, k, block=True)
        frag = opt._fragments[k]
        epoch = opt.epoch
        eager = opt.cfg.overlap_comm == "eager"
        tr = obs.tracer()
        t0p = time.perf_counter() if tr is not None else 0.0
        rec: dict[str, Any] = {
            "frag": frag,
            "epoch": epoch,
            "eager": eager,
            "t_launch": time.monotonic(),
            "round": f"frag{k}-epoch-{epoch}",
        }
        leaves = jax.tree.leaves(state["params"])
        if opt._plane is not None:
            # fused launch: pg + wire cast + eager estimate in one
            # dispatch, nothing donated, plane NOT rebound (stays
            # pre-round for this fragment until the landing)
            wire, delta, retained = opt._plane.stream_launch(
                leaves, frag, eager=eager
            )
            rec["placement"] = "device"
            rec["retained"] = retained
            if opt._gossip is not None:
                # the pair exchange carries (master, momentum) alongside
                # the pseudo-gradient; capture the live refs now —
                # stream_launch never rebinds, so these stay the
                # pre-round values until this round's own landing
                with opt._plane.lock:
                    rec["m_refs"] = opt._plane._sel(opt._plane.masters, frag)
                    rec["b_refs"] = (
                        opt._plane._sel(opt._plane.bufs, frag)
                        if opt._plane.bufs is not None
                        else None
                    )
            if eager:
                state = opt._apply_frag_delta(state, frag, delta)
            fut = self._spawn(k, epoch, wire=wire, ef_rec=rec)
        else:
            # host placement: own the boundary bytes NOW, on the training
            # thread — the next train_step donates these param buffers,
            # and a comm-thread device_get would read freed memory
            bh = [
                np.array(x, np.float32)
                for x in jax.device_get([leaves[i] for i in frag])
            ]
            pg = [native.sub(opt.master[i], b) for i, b in zip(frag, bh)]
            if opt._ef is not None:
                # residual folded in before BOTH the wire send and the
                # eager estimate below (the estimate must match what the
                # swarm will average); the fragment's roundtrip error
                # stages pending until this round lands
                opt._ef.prepare(rec["round"], frag, pg)
            rec["placement"] = "host"
            oo = opt.outer_opt
            if opt._gossip is not None:
                # clone-then-rebind discipline: master/buf entries are
                # never mutated in place, so these refs stay the
                # pre-round values for the comm thread
                rec["m_refs"] = [opt.master[i] for i in frag]
                rec["b_refs"] = (
                    None if oo.bufs is None else [oo.bufs[i] for i in frag]
                )
            if eager:
                est_opt = OuterSGD(
                    lr=oo.lr, momentum=oo.momentum, nesterov=oo.nesterov
                )
                est_opt.bufs = (
                    None if oo.bufs is None
                    else [oo.bufs[i].copy() for i in frag]
                )
                est_m = [opt.master[i].copy() for i in frag]
                est_opt.step(est_m, pg)
                state = opt._apply_frag_delta(
                    state, frag, [e - b for e, b in zip(est_m, bh)]
                )
                rec["est_m"] = est_m
            else:
                rec["boundary"] = bh
            fut = self._spawn(k, epoch, pg=pg, ef_rec=rec)
        rec["future"] = fut
        self._inflight[k] = rec
        self._launched.add(k)
        if tr is not None:
            tr.add_span(
                "outer/fragment_launch", t0p, time.perf_counter(),
                frag=k, epoch=epoch, round=rec["round"],
            )
            tr.gauge("outer_inflight_fragments", len(self._inflight))
            tr.count("outer_fragment_rounds")
        return state

    def _spawn(
        self,
        k: int,
        epoch: int,
        *,
        pg: Optional[list] = None,
        wire: Optional[list] = None,
        ef_rec: Optional[dict] = None,
    ):
        """Open fragment k's all-reduce on a daemon comm thread. Device
        placement hands over the (never-donated) wire jit outputs and the
        comm thread does the D2H itself — the training thread never waits
        on the fetch. The result is copied out of pooled backend buffers
        before resolving the future (the next same-tag round reclaims
        them)."""
        opt = self.opt
        fut: concurrent.futures.Future = concurrent.futures.Future()

        def _run():
            if not fut.set_running_or_notify_cancel():
                return
            try:
                arrays = pg
                if arrays is None:
                    fetched, _ = opt._plane.fetch(wire, ef_rec["frag"])
                    arrays = [
                        x if x.dtype == np.float32 else x.astype(np.float32)
                        for x in fetched
                    ]
                    if opt._ef is not None and ef_rec is not None:
                        # device placement: the plane's jit already added
                        # the residual; stage this fragment's roundtrip
                        # error here on the comm thread, where the host pg
                        # first exists (ErrorFeedback's pending map is
                        # lock-guarded — fragment rounds prepare
                        # concurrently)
                        opt._ef.prepare(
                            ef_rec["round"], ef_rec["frag"], arrays
                        )
                if opt._gossip is not None:
                    m_refs = ef_rec["m_refs"]
                    b_refs = ef_rec["b_refs"]
                    if ef_rec["placement"] == "device":
                        m_np = [
                            np.array(x, np.float32)
                            for x in jax.device_get(m_refs)
                        ]
                        b_np = (
                            None
                            if b_refs is None
                            else [
                                np.array(x, np.float32)
                                for x in jax.device_get(b_refs)
                            ]
                        )
                    else:
                        m_np = [np.array(x, np.float32) for x in m_refs]
                        b_np = (
                            None
                            if b_refs is None
                            else [np.array(x, np.float32) for x in b_refs]
                        )
                    if b_np is None and opt.cfg.outer_momentum != 0.0:
                        b_np = [np.zeros_like(m) for m in m_np]
                    # lockstep: pairs on the shared (epoch, frag) key.
                    # Async (ODTP_ASYNC_STALENESS > 0): matches any
                    # in-window partner on fragment k — every fragment
                    # syncs every epoch here, so ANY epoch distance
                    # aligns fragment-wise; a patience miss comes back
                    # as a self-round (n=1) and lands like a pair
                    res = opt._gossip.exchange(
                        epoch=epoch,
                        frag_id=k,
                        idxs=ef_rec["frag"],
                        masters=m_np,
                        bufs=b_np,
                        pgs=arrays,
                        timeout=opt.cfg.averaging_timeout,
                    )
                    if res is None:
                        # rides the existing dropped-round path; the
                        # per-partner EF was already aborted in exchange
                        raise AllReduceError(
                            f"gossip pair round dropped "
                            f"(frag {k} epoch {epoch})"
                        )
                    mix_m, mix_b, avg_g, _partner, n = res
                    new_m, new_b = noloco_step(
                        mix_m,
                        mix_b,
                        avg_g,
                        lr=opt.cfg.outer_lr,
                        momentum=opt.cfg.outer_momentum,
                        nesterov=opt.cfg.outer_nesterov,
                    )
                    fut.set_result(((new_m, new_b), n))
                    return
                avg, n = opt.backend.all_reduce(
                    arrays,
                    timeout=opt.cfg.averaging_timeout,
                    tag=f"frag{k}",
                    epoch=epoch,
                )
                fut.set_result(
                    ([np.array(a, np.float32) for a in avg], n)
                )
            except BaseException as e:  # surfaced via fut.result()
                fut.set_exception(e)

        threading.Thread(
            target=_run, name=f"odtp-stream-frag{k}", daemon=True
        ).start()
        return fut

    # -- land --------------------------------------------------------------

    def _land(self, state: dict, k: int, *, block: bool = False) -> dict:
        opt = self.opt
        rec = self._inflight.pop(k)
        tr = obs.tracer()
        t0p = time.perf_counter() if tr is not None else 0.0
        try:
            avg, group = rec["future"].result(
                timeout=(opt.cfg.averaging_timeout + 60) if block else 0
            )
        except BaseException as e:
            # elastic drop: the eager estimate stays applied and the
            # fragment's next pseudo-gradient (master - params) simply
            # re-captures it — nothing to unwind
            log.warning(
                "fragment %d round (epoch %d) dropped: %s", k, rec["epoch"], e
            )
            if opt._ef is not None:
                # discard the staged error; the retained residual is
                # neither lost nor double-counted (the next fragment
                # pseudo-gradient re-captures the dropped update)
                opt._ef.abort(rec["round"])
            if tr is not None:
                tr.count("outer_fragment_rounds_dropped")
                tr.gauge("outer_inflight_fragments", len(self._inflight))
            return state
        if opt._gossip is None:
            opt._check_group_size(group)
        if opt._ef is not None:
            opt._ef.commit(rec["round"])
        frag = rec["frag"]
        if opt._gossip is not None:
            # gossip round: the comm thread already ran the NoLoCo step —
            # the future carries the new (master, momentum) fragment, not
            # a raw average. Land it exactly like the all-reduce true
            # step: delta vs the retained estimate/boundary, then rebind.
            new_m, new_b = avg
            if rec["placement"] == "device":
                delta = opt._plane.gossip_land(
                    frag, new_m, new_b, base=rec["retained"]
                )
                state = opt._apply_frag_delta(state, frag, delta)
            else:
                if rec["eager"]:
                    delta = [t - e for t, e in zip(new_m, rec["est_m"])]
                else:
                    delta = [t - b for t, b in zip(new_m, rec["boundary"])]
                state = opt._apply_frag_delta(state, frag, delta)
                oo = opt.outer_opt
                new_master = list(opt.master)
                for j, i in enumerate(frag):
                    new_master[i] = np.asarray(new_m[j], np.float32)
                new_opt = OuterSGD(
                    lr=oo.lr, momentum=oo.momentum, nesterov=oo.nesterov
                )
                if oo.momentum != 0.0:
                    base = (
                        [np.zeros_like(p) for p in opt.master]
                        if oo.bufs is None
                        else list(oo.bufs)
                    )
                    if new_b is not None:
                        for j, i in enumerate(frag):
                            base[i] = np.asarray(new_b[j], np.float32)
                    new_opt.bufs = base
                with opt._serve_lock:
                    opt.master = new_master
                    opt.outer_opt = new_opt
        elif rec["placement"] == "device":
            if rec["eager"]:
                delta = opt._plane.stream_land(
                    frag, avg, est_m=rec["retained"]
                )
            else:
                delta = opt._plane.stream_land(
                    frag, avg, boundary=rec["retained"]
                )
            state = opt._apply_frag_delta(state, frag, delta)
        else:
            # true fragment outer step on copies of the live (still
            # pre-round for this fragment) master/momentum, then the
            # clone-then-rebind publication the host path lives by
            oo = opt.outer_opt
            true_opt = OuterSGD(
                lr=oo.lr, momentum=oo.momentum, nesterov=oo.nesterov
            )
            true_opt.bufs = (
                None if oo.bufs is None
                else [oo.bufs[i].copy() for i in frag]
            )
            true_m = [opt.master[i].copy() for i in frag]
            true_opt.step(true_m, avg)
            if rec["eager"]:
                delta = [t - e for t, e in zip(true_m, rec["est_m"])]
            else:
                delta = [t - b for t, b in zip(true_m, rec["boundary"])]
            state = opt._apply_frag_delta(state, frag, delta)
            new_master = list(opt.master)
            for j, i in enumerate(frag):
                new_master[i] = true_m[j]
            new_opt = OuterSGD(
                lr=oo.lr, momentum=oo.momentum, nesterov=oo.nesterov
            )
            if oo.momentum != 0.0:
                base = (
                    [np.zeros_like(p) for p in opt.master]
                    if oo.bufs is None
                    else list(oo.bufs)
                )
                for j, i in enumerate(frag):
                    base[i] = true_opt.bufs[j]
                new_opt.bufs = base
            with opt._serve_lock:
                opt.master = new_master
                opt.outer_opt = new_opt
        landed_s = time.monotonic() - rec["t_launch"]
        lm = opt._landed_metrics or {}
        lm.update(
            {
                "outer_allreduce_s": landed_s,
                "num_peers": group,
                **opt._round_health_metrics(),
            }
        )
        lm["outer_fragments_landed"] = lm.get("outer_fragments_landed", 0) + 1
        opt._landed_metrics = lm
        opt.last_outer_metrics = dict(lm)
        if tr is not None:
            tr.add_span(
                "outer/fragment_land", t0p, time.perf_counter(),
                frag=k, epoch=rec["epoch"], round=rec["round"], group=group,
                landed_s=round(landed_s, 6),
            )
            tr.gauge("outer_inflight_fragments", len(self._inflight))
            tr.gauge("outer_allreduce_s", landed_s)
        log.info(
            "fragment %d (epoch %d): all-reduce over %d peers landed "
            "after %.3fs",
            k,
            rec["epoch"],
            group,
            landed_s,
        )
        return state
