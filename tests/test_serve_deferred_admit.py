"""A cold admission that does not block (ISSUE 38): the batcher enqueues an
iteration's prefills and its decode step before it reads any of them, and the
step takes each admission's first token from the device. What comes out is,
token for token, what blocking ``engine.admit`` + ``engine.decode_step`` give
when driven by hand; what still blocks does so by the kind of admission and of
step, and a failure anywhere leaves no client waiting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_glm_flash
import test_granite_hybrid
import test_keye
import test_olmoe
import test_zaya
from opendiloco_tpu.models.llama import init_params
from opendiloco_tpu.serve import ContinuousBatcher, ServeEngine

SLOTS = 4
BUCKETS = (16, 32)


def _dense(tiny_cfg):
    return tiny_cfg, init_params(jax.random.key(0), tiny_cfg)


# the tiny configurations the model suites build: (configuration, parameters)
KINDS = {
    "dense": _dense,
    "routed": lambda _: test_olmoe.model(2)[1:],
    "hybrid": lambda _: test_granite_hybrid.model()[1:],
    "latent": lambda _: test_glm_flash.model()[1:],
    "cca": lambda _: test_zaya.model()[1:],
    # learned sparse attention: a prompt past the one bucket of 8 goes in chunks
    # of 8, its slot prefilling meanwhile (ISSUE 49)
    "sparse": lambda _: test_keye.model()[1:],
}


def _engine(cfg, params, **kw):
    kw = {"num_slots": SLOTS, "max_context": 128,
          "prefill_buckets": (8,) if cfg.sparse else BUCKETS,
          "compute_dtype": jnp.float32, "decode_kernel": "xla", **kw}
    return ServeEngine(cfg, params, **kw)


def _prompts(cfg, n, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, cfg.vocab_size, int(rng.integers(3, 30))).tolist() for _ in range(n)]


def by_hand(engine, prompt, max_new_tokens, eos_id=None, slot=0):
    """One request through the blocking calls, alone in ``slot`` -> its tokens
    as the batcher would hand them back (an ending ``eos_id`` dropped)."""
    tokens, lens = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    tok, _ = engine.admit(slot, prompt)
    out, n = [tok], len(prompt)
    while len(out) < max_new_tokens and out[-1] != eos_id:
        tokens[slot], lens[slot] = out[-1], n
        nxt, _ = engine.decode_step(tokens, lens)
        out.append(int(nxt[slot]))
        n += 1
    return out[:-1] if out[-1] == eos_id else out


def _serve(batcher, submits, timeout=300):
    """Queue every request, then start the loop: its first iteration admits
    as many as there are slots, the later ones beside slots that are running."""
    reqs = [batcher.submit(*a, **kw) for a, kw in submits]
    batcher.start()
    try:
        for r in reqs:
            assert r.wait(timeout), "a client was left waiting"
    finally:
        batcher.stop()
    return reqs


@pytest.mark.parametrize("kind", list(KINDS))
def test_batcher_outputs_equal_blocking_calls_driven_by_hand(tiny_cfg, kind):
    cfg, params = KINDS[kind](tiny_cfg)
    engine, second = _engine(cfg, params), _engine(cfg, params)
    lengths = [5, 2, 7, 3, 4, 6]  # slots free at different steps
    prompts = _prompts(cfg, len(lengths))
    batcher = ContinuousBatcher(engine)
    reqs = _serve(batcher, [((p, n), {}) for p, n in zip(prompts, lengths)])
    assert batcher.loop_error is None
    for i, (req, prompt, n) in enumerate(zip(reqs, prompts, lengths)):
        assert req.error is None
        assert req.tokens == by_hand(second, prompt, n, slot=i % SLOTS), (kind, i)
        assert req.t_submit < req.t_first < req.t_done
    # every admission was cold and was stepped: each token was fed on the device
    assert engine.admissions_deferred == engine.phase_calls["prefill"] == len(reqs)
    assert batcher.stats()["admissions_deferred"] == len(reqs)
    assert not engine._unread and not batcher._awaiting
    # the same work counted on both sides
    for name in ("moe_pairs", "ssm_tokens", "cca_tokens", "latent_rows_read",
                 "dsa_rows_scored", "dsa_rows_selected", "prefill_chunks"):
        assert getattr(engine, name) == getattr(second, name), name
    own = {"routed": "moe_pairs", "hybrid": "ssm_tokens", "latent": "latent_rows_read",
           "cca": "cca_tokens", "sparse": "prefill_chunks"}
    assert kind == "dense" or getattr(engine, own[kind]) > 0


def test_a_first_token_that_is_eos_retires_at_the_read(tiny_cfg):
    cfg, params = _dense(tiny_cfg)
    engine, second = _engine(cfg, params), _engine(cfg, params)
    prompts = _prompts(cfg, 6, seed=11)
    first = by_hand(second, prompts[0], 1)[0]
    # the first request ends on its first token, beside three that go on; the
    # two behind them take the slots as they free
    submits = [((prompts[0], 5), {"eos_id": first})] + [((p, 4), {}) for p in prompts[1:]]
    batcher = ContinuousBatcher(engine)
    reqs = _serve(batcher, submits)
    assert reqs[0].error is None and reqs[0].tokens == []  # eos ends, is not text
    assert reqs[0].t_first is not None and reqs[0].t_first <= reqs[0].t_done
    for req, prompt in zip(reqs[1:], prompts[1:]):
        assert req.error is None and req.tokens == by_hand(second, prompt, 4)
    assert engine.admissions_deferred == 6 and batcher.completed == 6
    assert batcher.slots.num_active == 0
    # the dropped row counted as no token of the request's
    assert batcher.total_new_tokens == sum(len(r.tokens) - 1 for r in reqs[1:])


def test_a_request_of_one_token_is_read_at_once_and_needs_no_step(tiny_cfg):
    cfg, params = _dense(tiny_cfg)
    engine, second = _engine(cfg, params), _engine(cfg, params)
    prompts = _prompts(cfg, 3, seed=13)
    batcher = ContinuousBatcher(engine)
    reqs = _serve(batcher, [((p, 1), {}) for p in prompts])
    for req, prompt in zip(reqs, prompts):
        assert req.error is None and req.tokens == by_hand(second, prompt, 1)
    assert engine.admissions_deferred == 0 and engine.phase_calls["prefill"] == 3
    assert batcher.decode_steps == 0 and engine.phase_calls["decode"] == 0


def test_a_request_cancelled_while_its_token_is_pending_frees_its_slot(tiny_cfg):
    cfg, params = _dense(tiny_cfg)
    engine, second = _engine(cfg, params), _engine(cfg, params)
    prompts = _prompts(cfg, SLOTS + 1, seed=17)
    batcher = ContinuousBatcher(engine)
    enqueue = engine.admit_enqueue
    victim = []

    def enqueue_then_cancel(slot, prompt):
        adm = enqueue(slot, prompt)
        if list(prompt) == prompts[1]:  # its programs are enqueued, nothing is read
            victim[0].cancel()
        return adm

    engine.admit_enqueue = enqueue_then_cancel
    reqs = [batcher.submit(p, 6) for p in prompts]
    victim.append(reqs[1])
    batcher.start()
    try:
        for r in reqs:
            assert r.wait(300)
    finally:
        batcher.stop()
    assert reqs[1].error == "cancelled" and batcher.cancelled == 1
    # the step was fed its first token on the device; the sweep took it before
    # that step was read (the loop is a step behind), so nothing reached it
    assert reqs[1].t_first is None and reqs[1].tokens == []
    for i in (0, 2, 3, 4):  # the last one got the cancelled request's slot
        assert reqs[i].error is None and reqs[i].tokens == by_hand(second, prompts[i], 6)
    assert batcher.loop_error is None and batcher.slots.num_active == 0


@pytest.mark.parametrize("where", ["enqueue", "read"])
def test_a_prefill_that_raises_fails_the_loop_loudly(tiny_cfg, where):
    cfg, params = _dense(tiny_cfg)
    engine = _engine(cfg, params)
    prompts = _prompts(cfg, SLOTS + 2, seed=19)

    def boom(*a, **kw):
        raise RuntimeError("prefill blew up")

    if where == "enqueue":
        engine._prefill = boom
    else:
        engine._read = boom
    batcher = ContinuousBatcher(engine)
    reqs = [batcher.submit(p, 4) for p in prompts]
    batcher.start()
    try:
        for r in reqs:  # the one in the engine's hands, its batch-mates, the queue
            assert r.wait(60), "a client was left waiting on a dead loop"
            assert r.error == "RuntimeError: prefill blew up"
    finally:
        batcher.stop()
    assert batcher.loop_error == "RuntimeError: prefill blew up"
    assert batcher.failed == len(reqs) and batcher.slots.num_active == 0


def test_a_continued_prefill_is_read_at_once_beside_a_cold_one_that_is_not(tiny_cfg):
    cfg, params = _dense(tiny_cfg)
    engine, second = _engine(cfg, params), _engine(cfg, params)
    shared = _prompts(cfg, 1, seed=29)[0][:3] + [9, 8, 7, 6, 5, 4, 3, 9, 8]
    prompts = [shared + [11, 12], shared + [13, 14, 15], shared + [16]]
    batcher = ContinuousBatcher(engine, prefix_cache=True)
    # all three in one iteration: the second and third continue the first's
    # rows, which its insert is still writing when their copies are enqueued
    reqs = _serve(batcher, [((p, 5), {}) for p in prompts])
    for req, prompt in zip(reqs, prompts):
        assert req.error is None and req.tokens == by_hand(second, prompt, 5)
    assert batcher.prefix_hits == 2
    # the one cold admission was fed on the device; the continued ones have
    # no phases and no deferral: they read as they always did
    assert engine.admissions_deferred == engine.phase_calls["prefill"] == 1


def test_resolving_by_hand_hands_the_token_over_and_the_step_reads_the_host(tiny_cfg):
    cfg, params = _dense(tiny_cfg)
    engine, second = _engine(cfg, params), _engine(cfg, params)
    prompt = _prompts(cfg, 1, seed=31)[0]
    want = by_hand(second, prompt, 3)
    adm = engine.admit_enqueue(1, prompt)
    assert adm.token is None and engine._unread == [adm]
    assert engine.admit_resolve(adm) == want[0] == adm.token
    assert adm.t_dispatch <= adm.t_token and not engine._unread
    tokens, lens = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    tokens[1], lens[1] = adm.token, len(prompt)
    nxt, _ = engine.decode_step(tokens, lens)
    assert int(nxt[1]) == want[1] and engine.admissions_deferred == 0
    # and unresolved: the step finds the token itself, whatever the host passes
    adm = engine.admit_enqueue(2, prompt)
    tokens[:], lens[:] = 0, 0
    tokens[2], lens[2] = 5, len(prompt)  # not the token
    nxt, _ = engine.decode_step(tokens, lens)
    assert adm.token == want[0] and int(nxt[2]) == want[1]
    assert engine.admissions_deferred == 1 and tokens[2] == 5  # the caller's array is its own


def test_each_program_compiles_once_whichever_way_it_is_reached(tiny_cfg):
    """Iterations with and without admissions through the batcher, then the
    blocking calls by hand beside them: one decode program, one prefill and
    one insert a bucket."""
    cfg, params = _dense(tiny_cfg)
    engine = _engine(cfg, params)
    rng = np.random.default_rng(37)
    short = [rng.integers(3, 256, 9).tolist() for _ in range(5)]  # bucket 16
    long = [rng.integers(3, 256, 21).tolist() for _ in range(3)]  # bucket 32
    batcher = ContinuousBatcher(engine)
    reqs = _serve(batcher, [((p, 6), {}) for p in short + long])
    assert all(r.error is None for r in reqs)
    assert batcher.decode_steps > batcher.loop_iterations - batcher.decode_steps >= 0
    assert engine.admissions_deferred == len(reqs)
    sizes = lambda: (engine._decode._cache_size(), engine._prefill._cache_size(),
                     engine._admit_insert._cache_size())
    assert sizes() == (1, len(BUCKETS), len(BUCKETS))
    for prompt in (short[0], long[0]):
        by_hand(engine, prompt, 3)
    assert sizes() == (1, len(BUCKETS), len(BUCKETS))
