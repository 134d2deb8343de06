"""The loop one decode step ahead of the host (ISSUE 48): an iteration enqueues
step k + 1, fed step k's tokens on the device, before it reads step k's. What
comes out is, token for token, what blocking ``engine.admit`` +
``engine.decode_step`` give when driven by hand; a row goes to the tenant it
was enqueued for and to no other; whatever needs the newest tokens on the host
drains the step in flight first; and a failure anywhere leaves no client
waiting."""

import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import test_evabyte
import test_serve_deferred_admit as deferred
from opendiloco_tpu.serve import ContinuousBatcher, ServeServer
from opendiloco_tpu.serve.engine import PREV_TOKEN_ON_DEVICE
from opendiloco_tpu.serve.kvcache import HostKVTier

SLOTS = deferred.SLOTS
by_hand, _serve, _prompts = deferred.by_hand, deferred._serve, deferred._prompts


def _eva(_):
    return test_evabyte.model()[1:]


KINDS = {**deferred.KINDS, "eva": _eva}


def _engine(kind, cfg, params, **kw):
    if kind == "eva":  # a window of 16: prompts and outputs cross its edge
        kw = {"max_context": 5 * test_evabyte.WINDOW, "prefill_buckets": (32, 48), **kw}
    return deferred._engine(cfg, params, **kw)


def _pair(kind, tiny_cfg, **kw):
    cfg, params = KINDS[kind](tiny_cfg)
    return cfg, _engine(kind, cfg, params, **kw), _engine(kind, cfg, params, **kw)


class Steps:
    """What the loop enqueued, step by step, from outside the engine."""

    def __init__(self, engine, after=None):
        self.tokens, self.lens, self.reads = [], [], 0
        # the steps enqueued with no step in flight (a busy period's first), and
        # the chunks of prompts that go in chunks enqueued before each step
        self.firsts, self.chunks, self.chunks_before = 0, [], []
        call, chunk = engine.step_ahead, engine.admit_chunk

        def admit_chunk(adm):
            self.chunks.append((adm.slot, len(self.lens)))
            return chunk(adm)

        engine.admit_chunk = admit_chunk

        def step_ahead(tokens=None, lens=None):
            if tokens is not None:
                self.firsts += engine._ahead is None
                self.chunks_before.append(len(self.chunks))
                self.tokens.append(np.array(tokens))
                self.lens.append(np.array(lens))
            out = call(tokens, lens)
            self.reads += out is not None
            if after is not None:
                after(self)
            return out

        engine.step_ahead = step_ahead

    @property
    def rows(self) -> int:
        return int(sum(np.count_nonzero(lens) for lens in self.lens))


def _wait(until, seconds=120):
    deadline = time.monotonic() + seconds
    while not until():
        assert time.monotonic() < deadline, "the loop never got there"
        time.sleep(0.002)


def _ending(second, cfg, n, seed):
    """-> (a prompt, its ``n`` tokens by hand, at): the ``at``-th token is the
    first of its value, so as ``eos_id`` it ends the request there, with a token
    still to go by length (``at`` <= n - 2): at a step where some prompt's
    output changes (``at`` >= 1), else at the admission's read, behind which
    the slot rides a step all the same (the tiny hybrid says one token)."""
    outputs = [(prompt, by_hand(second, prompt, n)) for prompt in _prompts(cfg, 12, seed=seed)]
    for first in (1, 0):
        for prompt, whole in outputs:
            for at in range(first, n - 1):
                if whole[at] not in whole[:at]:
                    return prompt, whole, at


@pytest.mark.parametrize("kind", list(KINDS))
def test_outputs_equal_blocking_calls_and_every_step_but_the_first_is_ahead(tiny_cfg, kind):
    cfg, engine, second = _pair(kind, tiny_cfg)
    lengths = [5, 2, 7, 3, 4, 6, 9]  # more requests than slots, which free at different steps
    prompts = _prompts(cfg, len(lengths))
    batcher, steps = ContinuousBatcher(engine), Steps(engine)
    reqs = _serve(batcher, [((p, n), {}) for p, n in zip(prompts, lengths)])
    assert batcher.loop_error is None
    for i, (req, prompt, n) in enumerate(zip(reqs, prompts, lengths)):
        assert req.error is None
        assert req.tokens == by_hand(second, prompt, n, slot=i % SLOTS), (kind, i)
        assert req.t_submit < req.t_first < req.t_done
    # one busy period and nothing that drains: every step but its first was
    # enqueued while the step before it was unread (prompts that go in chunks
    # may leave nobody decoding for a while: a busy period more, each with its
    # first)
    assert batcher.decode_steps == len(steps.lens) == steps.reads > 1
    assert engine.steps_ahead == batcher.decode_steps - steps.firsts
    assert steps.firsts == 1 or kind == "sparse"
    assert engine.phase_calls["decode"] == batcher.decode_steps
    stats = batcher.stats()
    assert stats["steps_ahead"] == engine.steps_ahead and stats["step_drains"] == {}
    # a request ended by length is in no step after its last: no row is
    # computed that is not some request's token
    assert steps.rows == batcher.total_new_tokens == sum(n - 1 for n in lengths)
    assert any((t == PREV_TOKEN_ON_DEVICE).any() for t in steps.tokens)
    assert engine._ahead is None and batcher._ahead is None and not engine._unread
    # the same work counted on both sides, by one decode program
    for name in ("moe_pairs", "ssm_tokens", "cca_tokens", "latent_rows_read",
                 "eva_local_rows_read", "eva_pooled_rows_read", "dsa_rows_scored",
                 "prefill_chunks"):
        assert getattr(engine, name) == getattr(second, name), name
    assert engine._decode._cache_size() == second._decode._cache_size() == 1
    if kind == "sparse":  # a chunk an iteration at most, and one program for all of them
        assert engine.prefill_chunks == len(steps.chunks) > len(lengths)
        assert engine._chunk._cache_size() == 1


def test_a_prefilling_slot_rides_no_step_and_its_first_token_is_fed_on_the_device(tiny_cfg):
    """ISSUE 49: a prompt of five chunks arrives while another slot decodes.
    Between two of its chunks lies a step; until its last chunk is enqueued its
    slot is in no step (no token of it is emitted, no row of its prompt is
    overwritten); the step behind its last chunk takes its first token on the
    device, and what it then decodes is what blocking calls give."""
    cfg, engine, second = _pair("sparse", tiny_cfg)
    rng = np.random.default_rng(11)
    short, long_ = rng.integers(3, cfg.vocab_size, 5).tolist(), rng.integers(3, cfg.vocab_size, 37).tolist()
    batcher, steps = ContinuousBatcher(engine), Steps(engine)
    first = batcher.submit(short, 40)
    batcher.start()
    try:
        _wait(lambda: len(first.tokens) >= 2)
        late = batcher.submit(long_, 6)
        for r in (first, late):
            assert r.wait(300) and r.error is None, r.error
    finally:
        batcher.stop()
    assert batcher.loop_error is None and batcher.stats()["step_drains"] == {}
    assert first.tokens == by_hand(second, short, 40, slot=0)
    assert late.tokens == by_hand(second, long_, 6, slot=1)
    slot = next(s for s, _ in steps.chunks)
    mine = [at for s, at in steps.chunks if s == slot]
    assert len(mine) == 5 and all(b - a == 1 for a, b in zip(mine, mine[1:]))  # a step between two chunks
    for done, lens in zip(steps.chunks_before, steps.lens):
        if 0 < done < 5:  # prefilling: in no step, while the other slot decodes on
            assert lens[slot] == 0 and np.count_nonzero(lens) == 1
    rode = next(lens for done, lens in zip(steps.chunks_before, steps.lens) if done == 5)
    assert rode[slot] == len(long_)  # the step behind the last chunk takes it along
    assert engine.admissions_deferred == 2 and engine.prefill_chunks == 5
    assert late.t_first > first.t_first


@pytest.mark.parametrize("kind", ["dense", "hybrid", "cca", "eva"])
def test_an_eos_in_flight_drops_one_row_and_the_next_tenant_reads_a_clean_slot(tiny_cfg, kind):
    cfg, engine, second = _pair(kind, tiny_cfg)
    ending, whole, at = _ending(second, cfg, 8, seed=79)
    prompts = [ending] + _prompts(cfg, SLOTS + 1, seed=41)
    submits = [((prompts[0], 8), {"eos_id": whole[at]})] + [((p, 6), {}) for p in prompts[1:]]
    batcher, steps = ContinuousBatcher(engine), Steps(engine)
    reqs = _serve(batcher, submits)
    assert batcher.loop_error is None and batcher.completed == len(reqs)
    assert reqs[0].tokens == whole[:at]  # eos ends, is not text
    for i, (req, prompt) in enumerate(zip(reqs[1:], prompts[1:]), 1):
        # the two behind take the slots as they free: one of them the slot
        # whose last tenant rode a step past its end
        assert req.error is None and req.tokens == by_hand(second, prompt, 6, slot=i % SLOTS), i
    # the rows enqueued: every token a step emitted (the eos among them), and
    # the one the request rode past its eos (two past a first token's: the
    # step that was fed it, and the one enqueued before that step was read)
    assert steps.rows == batcher.total_new_tokens + (1 if at else 2)
    assert batcher.total_new_tokens == at + 5 * (len(reqs) - 1)
    assert batcher.stats()["step_drains"] == {}


@pytest.mark.parametrize("n", [1, 2])
def test_requests_of_one_and_two_tokens(tiny_cfg, n):
    cfg, engine, second = _pair("dense", tiny_cfg)
    prompts = _prompts(cfg, SLOTS + 2, seed=43)
    batcher, steps = ContinuousBatcher(engine), Steps(engine)
    reqs = _serve(batcher, [((p, n), {}) for p in prompts])
    for req, prompt in zip(reqs, prompts):
        assert req.error is None and req.tokens == by_hand(second, prompt, n)
    # one token needs no step; a second is the one step's, and no slot rides another
    assert steps.rows == batcher.total_new_tokens == (n - 1) * len(prompts)
    assert batcher.decode_steps == engine.phase_calls["decode"] == (0 if n == 1 else 2)
    assert engine.steps_ahead == 0  # a busy period of one step each, the second's behind the first's read


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_a_slot_re_let_while_its_row_is_in_flight_hands_the_new_tenant_nothing(tiny_cfg, how):
    cfg, engine, second = _pair("dense", tiny_cfg)
    prompts = _prompts(cfg, SLOTS + 1, seed=47)
    victim = []

    def leave(steps):
        # a step holding the victim's row is enqueued and unread: the sweep
        # frees the slot next iteration and the queued request takes it
        if len(steps.lens) == 3 and steps.reads == 2:
            if how == "cancel":
                victim[0].cancel()
            else:
                victim[0].t_deadline = time.monotonic() - 1.0

    batcher, steps = ContinuousBatcher(engine), Steps(engine, after=leave)
    reqs = [batcher.submit(p, 8) for p in prompts]
    victim.append(reqs[1])
    batcher.start()
    try:
        for r in reqs:
            assert r.wait(300), "a client was left waiting"
    finally:
        batcher.stop()
    assert batcher.loop_error is None and batcher.slots.num_active == 0
    assert reqs[1].error == ("cancelled" if how == "cancel" else "deadline exceeded")
    assert (batcher.cancelled, batcher.shed) == ((1, 0) if how == "cancel" else (0, 1))
    # what it had before it left is the blocking calls' too; the row in flight went nowhere
    assert reqs[1].tokens == by_hand(second, prompts[1], 8, slot=1)[: len(reqs[1].tokens)]
    assert len(reqs[1].tokens) == 3
    for i in (0, 2, 3):
        assert reqs[i].error is None and reqs[i].tokens == by_hand(second, prompts[i], 8, slot=i)
    # the last one got the slot, behind the step that still held the old row
    assert reqs[4].error is None and reqs[4].tokens == by_hand(second, prompts[4], 8, slot=1)
    assert steps.rows == batcher.total_new_tokens + 1


def test_eviction_and_resume_drain_the_step_in_flight_first(tiny_cfg):
    cfg, engine, _ = _pair("dense", tiny_cfg, num_slots=2)
    second = _engine("dense", *KINDS["dense"](tiny_cfg))
    prompts = _prompts(cfg, 6, seed=53)
    tier = HostKVTier(host_slots=8, codec="none")
    batcher = ContinuousBatcher(
        engine, kv_tier=tier, tier_quantum_steps=2, tier_min_resident_steps=1
    )
    reqs = _serve(batcher, [((p, 7), {}) for p in prompts])
    for req, prompt in zip(reqs, prompts):
        assert req.error is None and req.tokens == by_hand(second, prompt, 7)
    stats = batcher.stats()
    assert stats["tier"]["evictions"] == stats["tier"]["resumes"] > 0
    # each page-out read rows and a token that were the step's in flight
    assert 0 < stats["step_drains"]["evict"] <= stats["tier"]["evictions"]
    assert set(stats["step_drains"]) <= {"evict", "resume"}
    # a drained step is followed by one that has no step before it to be ahead of
    assert engine.steps_ahead < batcher.decode_steps - sum(stats["step_drains"].values())
    assert engine.phase_calls["decode"] == batcher.decode_steps


def test_a_continued_prefill_drains_the_step_in_flight_first(tiny_cfg):
    cfg, engine, second = _pair("dense", tiny_cfg)
    shared = _prompts(cfg, 1, seed=59)[0][:3] + [9, 8, 7, 6, 5, 4, 3, 9, 8]
    prompts = [shared + [11, 12], shared + [13, 14, 15]]
    batcher = ContinuousBatcher(engine, prefix_cache=True).start()
    try:
        first = batcher.submit(prompts[0], 40)
        _wait(lambda: batcher.decode_steps >= 2)
        # the source is stepping, a step ahead: the copy of its rows and the
        # read of the suffix's token come behind a drained step
        second_req = batcher.submit(prompts[1], 5)
        assert second_req.wait(300) and first.wait(300)
    finally:
        batcher.stop()
    assert batcher.prefix_hits == 1 and batcher.stats()["step_drains"] == {"continued_prefill": 1}
    assert first.tokens == by_hand(second, prompts[0], 40)
    assert second_req.tokens == by_hand(second, prompts[1], 5, slot=1)


def test_stop_emits_the_step_in_flight_and_drain_waits_for_it(tiny_cfg):
    cfg, engine, second = _pair("dense", tiny_cfg)
    prompts = _prompts(cfg, 3, seed=61)
    batcher = ContinuousBatcher(engine).start()
    try:
        ending, whole, at = _ending(second, cfg, 6, seed=83)
        # ends on an eos with a step in flight behind it: the batch is empty
        # one iteration before the chip is
        done = batcher.submit(ending, 6, eos_id=whole[at])
        assert done.wait(120) and batcher.drain(120) and done.tokens == whole[:at]
        assert batcher._ahead is None and engine._ahead is None
        assert engine.phase_calls["decode"] == batcher.decode_steps == at + 1
        assert batcher.stats()["step_drains"] == {}
        long = batcher.submit(prompts[1], 100)
        _wait(lambda: batcher.decode_steps >= 8)
    finally:
        batcher.stop()
    # the step in flight was read and emitted before the rest was failed
    assert batcher.stats()["step_drains"] == {"stop": 1}
    assert long.error == "server stopped" and batcher._ahead is None and engine._ahead is None
    assert engine.phase_calls["decode"] == batcher.decode_steps
    assert long.tokens == by_hand(second, prompts[1], len(long.tokens))


@pytest.mark.parametrize("where", ["dispatch", "read"])
def test_a_step_that_raises_fails_every_request_and_hangs_none(tiny_cfg, where):
    cfg, engine, _ = _pair("dense", tiny_cfg)
    prompts = _prompts(cfg, SLOTS + 2, seed=67)
    calls = {"n": 0}

    def third_time(call):
        def boom(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 3:
                raise RuntimeError("decode blew up")
            return call(*a, **kw)
        return boom

    if where == "dispatch":
        engine._enqueue_step = third_time(engine._enqueue_step)
    else:
        engine._finish_step = third_time(engine._finish_step)
    batcher = ContinuousBatcher(engine)
    reqs = [batcher.submit(p, 9) for p in prompts]
    batcher.start()
    try:
        for r in reqs:  # those in the step, those it was enqueued behind, the queue
            assert r.wait(60), "a client was left waiting on a dead loop"
            assert r.error == "RuntimeError: decode blew up"
    finally:
        batcher.stop()
    assert batcher.loop_error == "RuntimeError: decode blew up"
    assert batcher.failed == len(reqs) and batcher.slots.num_active == 0
    assert batcher._ahead is None and not batcher._awaiting


def test_a_swap_between_a_steps_enqueue_and_its_read_is_not_that_steps_epoch(tiny_cfg):
    cfg, engine, _ = _pair("dense", tiny_cfg)
    params = KINDS["dense"](tiny_cfg)[1]
    box = {"epoch": 0}
    engine.epoch_fn = lambda: box["epoch"]

    def snapshot():
        return box["epoch"], None, "fp16"

    engine.snapshot_fn = snapshot
    engine.install_wire = lambda epoch, blobs, codec: engine.install_params(epoch, params)
    batcher = ContinuousBatcher(engine, swap_every_steps=1)
    steps = Steps(engine, after=lambda s: box.update(epoch=1) if s.reads == 2 else None)
    reqs = _serve(batcher, [((p, 4), {}) for p in _prompts(cfg, 2, seed=71)])
    # the trainer's round landed after two steps were read: the third, the
    # requests' last, was enqueued by then under the old weights, and the swap
    # that followed did not wait for it
    assert engine.weights_epoch == 1 and batcher.stats()["step_drains"] == {}
    assert [r.epoch for r in reqs] == [0, 0]
    assert batcher.staleness_hist == {0: 3}


def test_the_blocking_step_and_the_step_ahead_are_one_program(tiny_cfg):
    """``step_ahead`` by hand beside ``decode_step``: a slot's token from the
    host, from ``first`` and from ``prev`` in one and the same step."""
    cfg, engine, second = _pair("dense", tiny_cfg)
    prompts = _prompts(cfg, 3, seed=73)
    want = [by_hand(second, p, 4, slot=i) for i, p in enumerate(prompts)]
    tokens, lens = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    tokens[0], _ = engine.admit(0, prompts[0])
    lens[0] = len(prompts[0])
    engine.admit_enqueue(1, prompts[1])
    lens[1] = len(prompts[1])
    fed = engine._unread[0]
    assert engine.step_ahead(tokens, lens) is None  # enqueued; nothing was waiting
    assert fed.token is None and fed.fed
    # the second step: slots 0 and 1 ride on, slot 2 joins from an admission
    # that nothing has read, and slot 3 is fed by the host
    adm = engine.admit_enqueue(2, prompts[2])
    tokens[:2], lens[:2] = PREV_TOKEN_ON_DEVICE, lens[:2] + 1
    lens[2] = len(prompts[2])
    tokens[3], lens[3] = 5, 1
    first = engine.step_ahead(tokens, lens)
    # the call read the first step, behind the admission that step was fed
    assert fed.token == want[1][0] and engine._unread == [adm]
    assert [int(first[0]), int(first[1])] == [want[0][1], want[1][1]]
    assert adm.token is None  # behind the step just read: the next call's
    second_out = engine.step_ahead()
    assert adm.token == want[2][0] and adm.t_token >= adm.t_dispatch and not engine._unread
    assert [int(second_out[i]) for i in range(3)] == [want[0][2], want[1][2], want[2][1]]
    assert engine.step_ahead() is None and engine._ahead is None
    assert engine.steps_ahead == 1 and engine.phase_calls["decode"] == 2
    # and on by the blocking call, fed from the host: the same program
    tokens[:3], lens[:3] = second_out[:3], lens[:3] + 1
    nxt, logits = engine.decode_step(tokens, lens)
    assert [int(nxt[0]), int(nxt[1])] == [want[0][3], want[1][3]]
    assert logits.shape == (SLOTS, cfg.vocab_size)
    assert engine._decode._cache_size() == 1


def test_stats_of_a_closed_loop_count_every_step_ahead_but_a_busy_periods_first(tiny_cfg):
    """The benchmark's traffic in small over the socket front: as many clients
    as slots, each sending its next request when the last came back, constant
    outputs, an ``eos_id`` that no token is (``/generate`` passes one). ``GET
    /stats``: every step but the first of each busy period was enqueued ahead,
    and nothing drained."""
    cfg, engine, _ = _pair("dense", tiny_cfg)
    batcher, steps = ContinuousBatcher(engine), Steps(engine)
    batcher.start()
    srv = ServeServer(batcher, port=0)
    rng = np.random.default_rng(89)
    prompts = [rng.integers(3, cfg.vocab_size, int(rng.integers(3, 30))).tolist() for _ in range(24)]
    failures = []

    def client(mine):
        for prompt in mine:
            body = json.dumps({"prompt": prompt, "max_new_tokens": 7, "eos_id": -1}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/generate", data=body,
                headers={"Content-Type": "application/json"},
            )
            with urllib.request.urlopen(req, timeout=120) as r:
                out = json.loads(r.read())
            if len(out.get("tokens", ())) != 7:
                failures.append(out)

    clients = [threading.Thread(target=client, args=(prompts[i::SLOTS],)) for i in range(SLOTS)]
    try:
        for c in clients:
            c.start()
        for c in clients:
            c.join(300)
        assert not any(c.is_alive() for c in clients) and not failures
        assert batcher.drain(60)
        with urllib.request.urlopen(f"http://127.0.0.1:{srv.port}/stats", timeout=10) as r:
            stats = json.loads(r.read())
    finally:
        srv.stop()
        batcher.stop()
    assert stats["completed"] == len(prompts) and stats["failed"] == 0
    # a call that enqueues and reads nothing starts a busy period
    starts = len(steps.lens) - engine.steps_ahead
    assert 1 <= starts <= len(prompts)
    assert stats["steps_ahead"] == stats["decode_steps"] - starts == engine.steps_ahead
    assert stats["step_drains"] == {} and stats["phase_calls"]["decode"] == stats["decode_steps"]
    # an eos that never comes rides every step to the request's length: no row dropped
    assert steps.rows == stats["new_tokens"] == 6 * len(prompts)
    assert engine._decode._cache_size() == 1
