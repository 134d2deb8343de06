"""Serving-plane tests: decode parity, continuous batching, weight hot-swap.

Oracles:
- incremental decode (prefill + token-by-token with the ring KV cache)
  reproduces the full training-mode forward logits bit-for-bit on the
  greedy f32 path — the cache is an optimization, never an approximation
- continuous batching changes scheduling, not results: a request decoded
  alongside strangers matches the same request decoded alone
- a weight hot-swap between decode steps flips the logits source but
  leaves every in-flight KV cache byte unchanged and drops no request
- master_snapshot_wire rides the fp16 state codec: half-width payloads,
  ODTP_STATE_CODEC override honored, epoch-consistent tags
- one obs registry serves trainer AND server gauges; port collisions
  downgrade to ephemeral instead of killing the process

Fast-decode oracles (PR 11):
- self-speculative decode is token-bit-exact vs the one-token loop —
  across prefill buckets, across ring wrap, and under an adversarial
  draft that is ALWAYS wrong (acceptance floors at the verify token)
- w4-resident weights change bytes at rest, not behavior: logits track
  the fp32-resident engine to quantization tolerance, and the packed
  bits are identical whether the native kernel or the numpy fallback
  produced them
- prefix reuse writes the SAME prefix K/V bytes a cold prefill writes
"""
import json
import socket
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opendiloco_tpu import obs
from opendiloco_tpu.config import DilocoConfig, ServeConfig
from opendiloco_tpu.models.llama import forward, init_params
from opendiloco_tpu.models.ring_cache import fetch_pages
from opendiloco_tpu.serve import (
    ContinuousBatcher,
    ServeEngine,
    ServeServer,
    SlotAllocator,
    build_serving,
    pick_bucket,
)


def make_engine(tiny_cfg, seed=0, **kw):
    params = init_params(jax.random.PRNGKey(seed), tiny_cfg)
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_context", 64)
    kw.setdefault("prefill_buckets", (8, 16, 32))
    kw.setdefault("compute_dtype", jnp.float32)
    return ServeEngine(tiny_cfg, params, **kw), params


def greedy_generate(engine, prompt, n, slot=0):
    """Drive the engine directly: prefill + n-1 decode steps, one slot."""
    tok, logits = engine.admit(slot, prompt)
    toks, logit_rows = [tok], [logits]
    cache_len = len(prompt)
    S = engine.num_slots
    for _ in range(n - 1):
        tokens = np.zeros((S,), np.int32)
        lens = np.zeros((S,), np.int32)
        tokens[slot], lens[slot] = toks[-1], cache_len
        nxt, step_logits = engine.decode_step(tokens, lens)
        toks.append(int(nxt[slot]))
        logit_rows.append(np.asarray(step_logits[slot]))
        cache_len += 1
    return toks, np.stack(logit_rows)


# ---------------------------------------------------------------------------
# decode parity (satellite 1)
# ---------------------------------------------------------------------------


def test_decode_parity_greedy(tiny_cfg):
    """Prefill + incremental decode == full training-mode forward on the
    greedy f32 path: the token stream is bit-for-bit identical, and every
    per-step logit row matches to 1 ulp. (The logit rows are mathematically
    identical — masked softmax terms are exact zeros — but XLA fuses the
    cached-decode and full-forward graphs differently, so the last bit of
    a dot-product reduction may differ; exactly-equal tokens are the
    invariant the greedy path guarantees.)"""
    engine, params = make_engine(tiny_cfg)
    prompt = [3, 7, 11, 2, 9, 250]
    n_new = 8
    toks, step_logits = greedy_generate(engine, prompt, n_new, slot=1)

    full = np.asarray(prompt + toks[:-1], np.int32)
    ref = np.asarray(
        forward(params, jnp.asarray(full)[None], tiny_cfg,
                compute_dtype=jnp.float32, remat=False)[0]
    )
    ref_rows = ref[len(prompt) - 1 : len(prompt) - 1 + n_new]
    ref_toks = [int(np.argmax(r)) for r in ref_rows]
    assert toks == ref_toks
    np.testing.assert_allclose(step_logits, ref_rows, atol=2e-6, rtol=2e-5)


def test_decode_parity_across_prefill_buckets(tiny_cfg):
    """Bucket padding must not leak into results: the same prompt padded
    to different prefill buckets yields identical generations."""
    outs = []
    for buckets in [(8,), (32,)]:
        engine, _ = make_engine(tiny_cfg, prefill_buckets=buckets)
        outs.append(greedy_generate(engine, [5, 1, 4, 1, 5], 6)[0])
    assert outs[0] == outs[1]


def test_ring_wrap_keeps_decoding(tiny_cfg):
    """A sequence outgrowing its KV page slides the window and keeps
    producing finite logits (ring semantics, not a crash or NaN)."""
    engine, _ = make_engine(tiny_cfg, max_context=16, prefill_buckets=(8,))
    toks, logits = greedy_generate(engine, [1, 2, 3], 24)  # 3 + 24 >> 16
    assert len(toks) == 24
    assert np.isfinite(logits).all()


# ---------------------------------------------------------------------------
# KV bookkeeping units
# ---------------------------------------------------------------------------


def test_slot_allocator_and_buckets():
    a = SlotAllocator(3)
    s = [a.alloc(), a.alloc(), a.alloc()]
    assert sorted(s) == [0, 1, 2] and a.alloc() is None
    assert (a.num_free, a.num_active) == (0, 3)
    a.free(1)
    assert a.alloc() == 1
    with pytest.raises(ValueError):
        a.free(99)
    a.free(2)
    with pytest.raises(ValueError):
        a.free(2)  # double free
    assert pick_bucket(5, [8, 16]) == 8
    assert pick_bucket(9, [16, 8]) == 16  # unsorted input
    assert pick_bucket(17, [8, 16]) is None


# ---------------------------------------------------------------------------
# continuous batching: join/retire (satellite 1)
# ---------------------------------------------------------------------------


def test_batch_join_retire_matches_isolated(tiny_cfg):
    """Requests joining/leaving a shared batch get the same tokens as the
    same requests run alone: batching is a throughput trick, not a model
    change. Two slots + five staggered requests forces queueing, joins
    mid-flight, and slot reuse."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, 256, int(n)).tolist() for n in (3, 7, 5, 12, 4)]
    lengths = [6, 3, 9, 5, 7]

    engine, params = make_engine(tiny_cfg, num_slots=2)
    batcher = ContinuousBatcher(engine).start()
    try:
        reqs = []
        for p, n in zip(prompts, lengths):
            reqs.append(batcher.submit(p, max_new_tokens=n))
            time.sleep(0.01)
        for r in reqs:
            assert r.wait(60) and r.error is None
    finally:
        batcher.stop()

    for req, p, n in zip(reqs, prompts, lengths):
        solo_engine = ServeEngine(
            tiny_cfg, params, num_slots=1, max_context=64,
            prefill_buckets=(8, 16, 32), compute_dtype=jnp.float32,
        )
        assert req.tokens == greedy_generate(solo_engine, p, n)[0]
    assert batcher.completed == len(prompts)
    assert batcher.failed == 0 and batcher.rejected == 0


def test_eos_and_reject_paths(tiny_cfg):
    engine, _ = make_engine(tiny_cfg)
    batcher = ContinuousBatcher(engine).start()
    try:
        # find a token the model actually produces, then use it as eos
        probe = batcher.submit([1, 2, 3], max_new_tokens=4)
        assert probe.wait(60) and probe.error is None
        eos = probe.tokens[0]
        r = batcher.submit([1, 2, 3], max_new_tokens=10, eos_id=eos)
        assert r.wait(60) and r.error is None
        assert len(r.tokens) == 0  # first token was eos; terminator dropped

        bad = batcher.submit([], max_new_tokens=2)
        assert bad.error == "empty prompt"
        long = batcher.submit(list(range(100)), max_new_tokens=2)
        assert "exceeds" in long.error
        assert batcher.rejected == 2
    finally:
        batcher.stop()


# ---------------------------------------------------------------------------
# weight hot-swap (tentpole + satellite 2 regression)
# ---------------------------------------------------------------------------


def _wire_blobs(params, codec_name="fp16"):
    from opendiloco_tpu.diloco.compression import get_codec

    codec = get_codec(codec_name)
    blobs = []
    for leaf in jax.tree.leaves(params):
        a = np.asarray(leaf, np.float32).reshape(-1)
        payload, meta = codec.encode(a)
        blobs.append((payload, meta, tuple(np.shape(leaf))))
    return blobs


def test_swap_mid_decode_changes_no_kv_entries(tiny_cfg):
    """Regression (satellite 2): installing a snapshot between decode
    steps must leave every in-flight KV cache byte unchanged — and the
    generation continues under the new weights without error."""
    engine, _ = make_engine(tiny_cfg)
    _, params2 = make_engine(tiny_cfg, seed=123)

    tok, _ = engine.admit(0, [4, 8, 15, 16])
    tokens = np.zeros((engine.num_slots,), np.int32)
    lens = np.zeros((engine.num_slots,), np.int32)
    tokens[0], lens[0] = tok, 4
    nxt, _ = engine.decode_step(tokens, lens)

    ck_before = np.asarray(engine.cache_k)
    cv_before = np.asarray(engine.cache_v)
    old = engine.params
    engine.install_wire(1, _wire_blobs(params2), "fp16")
    assert engine.weights_epoch == 1 and engine.swap_count == 1
    np.testing.assert_array_equal(np.asarray(engine.cache_k), ck_before)
    np.testing.assert_array_equal(np.asarray(engine.cache_v), cv_before)
    # the weights actually changed (swap is not a no-op)
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree.leaves(old), jax.tree.leaves(engine.params))
    )
    tokens[0], lens[0] = int(nxt[0]), 5
    nxt2, logits2 = engine.decode_step(tokens, lens)
    assert np.isfinite(np.asarray(logits2[0])).all()


def test_hot_swap_under_load_drops_nothing(tiny_cfg):
    """Swaps fire while requests are in flight; every request completes
    and the engine ends on the newest epoch."""
    engine, params = make_engine(tiny_cfg)
    epoch_box = {"epoch": 0}
    engine.epoch_fn = lambda: epoch_box["epoch"]
    engine.snapshot_fn = lambda: (
        epoch_box["epoch"], _wire_blobs(params), "fp16"
    )
    batcher = ContinuousBatcher(engine, swap_every_steps=2).start()
    try:
        rng = np.random.default_rng(3)
        reqs = []
        for i in range(8):
            reqs.append(
                batcher.submit(rng.integers(1, 256, 5).tolist(), max_new_tokens=6)
            )
            if i in (2, 5):
                epoch_box["epoch"] += 1  # trainer finishes an outer round
            time.sleep(0.01)
        for r in reqs:
            assert r.wait(60) and r.error is None
    finally:
        batcher.stop()
    assert batcher.failed == 0
    assert engine.swap_count >= 1
    assert engine.weights_epoch == epoch_box["epoch"]
    assert batcher.staleness_hist  # distribution was sampled


# ---------------------------------------------------------------------------
# snapshot export rides the fp16 state codec (satellite 2)
# ---------------------------------------------------------------------------


def _make_opt(tiny_cfg, monkeypatch=None, placement="host", local_steps=2):
    from opendiloco_tpu.diloco import DiLoCoOptimizer, LoopbackWorld
    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

    tc = TrainerConfig(
        lr=1e-3, warmup_steps=2, total_steps=100, precision="fp32", remat=False
    )
    plan = build_mesh("NO_SHARD", devices=[jax.devices()[0]])
    trainer = InnerTrainer(tiny_cfg, tc, plan)
    params = init_params(jax.random.PRNGKey(0), tiny_cfg)
    state = trainer.init_state(jax.random.key(1), params)
    cfg = DilocoConfig(
        local_steps=local_steps, backend="loopback", outer_placement=placement
    )
    backend = LoopbackWorld(1).make_backends()[0]
    opt = DiLoCoOptimizer(trainer, backend, cfg, state, batch_size=8)
    return opt, trainer, state


@pytest.mark.parametrize("placement", ["host", "device"])
def test_master_snapshot_wire_fp16(tiny_cfg, placement):
    opt, _, _ = _make_opt(tiny_cfg, placement=placement)
    assert opt.placement == placement
    epoch, blobs, codec_name = opt.master_snapshot_wire()
    assert codec_name == "fp16" and epoch == 0
    _, masters = opt.master_snapshot()
    assert len(blobs) == len(masters)
    for (payload, meta, shape), m in zip(blobs, masters):
        size = int(np.prod(shape)) if shape else 1
        # half-width payload: the whole point of riding the state codec
        assert len(payload) == 2 * size
        from opendiloco_tpu.diloco.compression import get_codec

        dec = get_codec(codec_name).decode(payload, (size,), meta)
        np.testing.assert_allclose(
            dec.reshape(shape), np.asarray(m, np.float32), atol=1e-3, rtol=1e-3
        )


def test_master_snapshot_wire_codec_override(tiny_cfg, monkeypatch):
    monkeypatch.setenv("ODTP_STATE_CODEC", "none")
    opt, _, _ = _make_opt(tiny_cfg)
    _, blobs, codec_name = opt.master_snapshot_wire()
    assert codec_name == "none"
    _, masters = opt.master_snapshot()
    for (payload, _, shape), m in zip(blobs, masters):
        assert len(payload) == 4 * int(np.prod(shape))  # full-width f32
        np.testing.assert_array_equal(
            np.frombuffer(payload, np.float32).reshape(shape), m
        )


def test_snapshot_feeds_engine_swap(tiny_cfg):
    """The optimizer's wire snapshot installs cleanly into the engine and
    the engine's weights then match the masters to fp16 precision, as the
    engine holds them: in its compute dtype (float32 here)."""
    opt, _, state = _make_opt(tiny_cfg)
    engine, _ = make_engine(tiny_cfg, seed=9)
    epoch, blobs, codec_name = opt.master_snapshot_wire()
    engine.install_wire(epoch + 1, blobs, codec_name)
    _, masters = opt.master_snapshot()
    got = jax.tree.leaves(engine.params)
    for g, m in zip(got, masters):
        assert g.dtype == engine.compute_dtype
        np.testing.assert_allclose(
            np.asarray(g, np.float32),
            np.asarray(jnp.asarray(m).astype(engine.compute_dtype), np.float32),
            atol=2e-3, rtol=2e-3,
        )


# ---------------------------------------------------------------------------
# one obs registry + port-collision guards (satellite 3)
# ---------------------------------------------------------------------------


@pytest.fixture
def _obs_armed(monkeypatch):
    monkeypatch.delenv("ODTP_OBS_DIR", raising=False)
    monkeypatch.delenv("ODTP_OBS_PROM_PORT", raising=False)
    monkeypatch.setenv("ODTP_OBS", "test-serve")
    obs.reset()
    yield obs.tracer()
    monkeypatch.delenv("ODTP_OBS", raising=False)
    obs.reset()


def _http_get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=10
    ) as r:
        return r.read().decode()


def test_one_registry_serves_trainer_and_server_gauges(tiny_cfg, _obs_armed):
    from opendiloco_tpu.obs import prom

    tr = _obs_armed
    tr.gauge("inner_loss", 1.25)  # trainer-side metric
    srv = prom.get_or_start(0, tr)
    assert prom.get_or_start(0, tr) is srv  # one endpoint per process

    engine, _ = make_engine(tiny_cfg)
    batcher = ContinuousBatcher(engine, gauge_every_steps=1).start()
    try:
        r = batcher.submit([1, 2, 3], max_new_tokens=4)
        assert r.wait(60) and r.error is None
        deadline = time.monotonic() + 10
        text = ""
        while time.monotonic() < deadline:
            text = _http_get(srv.port, "/metrics")
            if "serve_batch_occupancy" in text:
                break
            time.sleep(0.05)
    finally:
        batcher.stop()
        srv.stop()
        tr.prom = None
    # both planes' series on the SAME endpoint
    assert "inner_loss" in text
    assert "serve_batch_occupancy" in text
    assert "serve_requests_completed" in text


def test_prom_port_collision_falls_back(_obs_armed):
    from opendiloco_tpu.obs import prom

    blocker = socket.socket()
    blocker.bind(("0.0.0.0", 0))
    blocker.listen(1)
    taken = blocker.getsockname()[1]
    try:
        srv = prom.PromServer(taken, _obs_armed)
        assert srv.port != taken  # downgraded, not dead
        srv.stop()
    finally:
        blocker.close()


def test_serve_port_collision_falls_back(tiny_cfg):
    engine, _ = make_engine(tiny_cfg)
    batcher = ContinuousBatcher(engine).start()
    blocker = socket.socket()
    blocker.bind(("127.0.0.1", 0))
    blocker.listen(1)
    taken = blocker.getsockname()[1]
    try:
        srv = ServeServer(batcher, port=taken)
        assert srv.port != taken
        srv.stop()
    finally:
        blocker.close()
        batcher.stop()


# ---------------------------------------------------------------------------
# socket front-end
# ---------------------------------------------------------------------------


def test_http_and_jsonl_frontend(tiny_cfg):
    engine, params = make_engine(tiny_cfg)
    batcher = ContinuousBatcher(engine).start()
    srv = ServeServer(batcher, port=0)
    try:
        body = json.dumps({"prompt": [5, 6, 7], "max_new_tokens": 4}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/generate",
            data=body,
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            out = json.loads(r.read())
        assert len(out["tokens"]) == 4 and "error" not in out

        # the HTTP answer matches the engine driven directly
        solo = ServeEngine(
            tiny_cfg, params, num_slots=1, max_context=64,
            prefill_buckets=(8, 16, 32), compute_dtype=jnp.float32,
        )
        assert out["tokens"] == greedy_generate(solo, [5, 6, 7], 4)[0]

        health = json.loads(_http_get(srv.port, "/healthz"))
        assert health["ok"] is True
        assert (health["platform"], health["device_kind"]) == ("cpu", "cpu")
        assert health["decode_kernel"] == "xla"
        stats = json.loads(_http_get(srv.port, "/stats"))
        assert stats["completed"] >= 1 and stats["failed"] == 0

        # JSONL on the same port: two pipelined lines, ids echoed
        conn = socket.create_connection(("127.0.0.1", srv.port), timeout=30)
        for i in range(2):
            conn.sendall(
                (json.dumps({"prompt": [9, i], "max_new_tokens": 2, "id": i})
                 + "\n").encode()
            )
        buf = b""
        while buf.count(b"\n") < 2:
            chunk = conn.recv(4096)
            assert chunk, "connection closed early"
            buf += chunk
        lines = [json.loads(x) for x in buf.decode().splitlines()]
        assert [x["id"] for x in lines] == [0, 1]
        assert all(len(x["tokens"]) == 2 for x in lines)
        conn.close()
    finally:
        srv.stop()
        batcher.stop()


# ---------------------------------------------------------------------------
# fast decode, leg a: self-speculative parity (PR 11 tentpole)
# ---------------------------------------------------------------------------


def spec_generate(engine, prompt, n, slot=0):
    """Drive the spec engine directly: admit + spec rounds, one slot.
    Returns the first n greedy tokens."""
    tok, _ = engine.admit(slot, prompt)
    toks = [tok]
    S = engine.num_slots
    lens = np.zeros((S,), np.int32)
    cur = np.zeros((S,), np.int32)
    lens[slot], cur[slot] = len(prompt), tok
    while len(toks) < n:
        # fresh arrays every round, as the scheduler hands them over: on the
        # CPU backend ``jnp.asarray`` aliases a host array, and spec_step
        # returns with its accepted-tail insert still in flight, so updating
        # ``lens`` in place below moved that insert's ring rows under it
        # (the whole of what ROADMAP called near-tie argmax flakes)
        g, m = engine.spec_step(cur.copy(), lens.copy())
        take = int(m[slot]) + 1
        toks.extend(int(t) for t in g[slot, :take])
        lens[slot] += take
        cur[slot] = toks[-1]
    return toks[:n]


@pytest.mark.parametrize("buckets", [(8,), (32,)])
def test_spec_decode_token_parity(tiny_cfg, buckets):
    """Spec decode emits the exact token stream of the plain loop, for
    every draft width, regardless of prefill bucket padding."""
    plain, _ = make_engine(tiny_cfg, prefill_buckets=buckets)
    ref = greedy_generate(plain, [5, 1, 4, 1, 5], 20)[0]
    for k in (1, 3):
        spec, _ = make_engine(tiny_cfg, prefill_buckets=buckets, spec_k=k)
        assert spec_generate(spec, [5, 1, 4, 1, 5], 20) == ref


def test_spec_decode_parity_across_ring_wrap(tiny_cfg):
    """Parity holds while the ring wraps (3 + 24 tokens on a 16-wide
    page): draft/verify tail K/V never touches the ring before
    acceptance, and the tail-aware eviction mask reproduces the sliding
    window the one-token loop sees."""
    plain, _ = make_engine(tiny_cfg, max_context=16, prefill_buckets=(8,))
    ref = greedy_generate(plain, [1, 2, 3], 24)[0]
    spec, _ = make_engine(
        tiny_cfg, max_context=16, prefill_buckets=(8,), spec_k=3
    )
    assert spec_generate(spec, [1, 2, 3], 24) == ref


def test_spec_zero_acceptance_adversarial(tiny_cfg):
    """A draft that is ALWAYS wrong: every proposal disagrees with the
    full model's greedy choice, so every round accepts zero drafts and
    emits exactly the verify pass's corrected token. Output stays
    token-identical — a bad draft can cost throughput, never change the
    stream (rejected tokens never enter the ring)."""
    prompt, n = [2, 4, 6], 12
    plain, _ = make_engine(tiny_cfg)
    ref = greedy_generate(plain, prompt, n)[0]

    spec, _ = make_engine(tiny_cfg, spec_k=2)
    V = tiny_cfg.vocab_size
    count = {"emitted": 1}  # admit already produced ref[0]

    def adversary(tokens, lens):
        # ref[emitted] is the true greedy next token; propose anything else
        wrong = (ref[count["emitted"]] + 1) % V
        return np.full((spec.num_slots, spec.spec_k), wrong, np.int32)

    spec.propose_fn = adversary
    tok, _ = spec.admit(0, prompt)
    assert tok == ref[0]
    toks = [tok]
    lens = np.zeros((spec.num_slots,), np.int32)
    cur = np.zeros((spec.num_slots,), np.int32)
    lens[0], cur[0] = len(prompt), tok
    while len(toks) < n:
        g, m = spec.spec_step(cur.copy(), lens.copy())  # see spec_generate
        assert int(m[0]) == 0  # nothing agreed; verify floor
        toks.append(int(g[0, 0]))
        count["emitted"] += 1
        lens[0] += 1
        cur[0] = toks[-1]
    assert toks == ref


def test_spec_batcher_matches_isolated(tiny_cfg):
    """Continuous batching + spec decode: staggered requests sharing two
    slots still match the same requests decoded alone and plain."""
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, 256, int(n)).tolist() for n in (3, 7, 5, 12)]
    lengths = [6, 9, 4, 7]
    engine, params = make_engine(tiny_cfg, num_slots=2, spec_k=3)
    batcher = ContinuousBatcher(engine).start()
    try:
        reqs = []
        for p, n in zip(prompts, lengths):
            reqs.append(batcher.submit(p, max_new_tokens=n))
            time.sleep(0.01)
        for r in reqs:
            assert r.wait(60) and r.error is None
    finally:
        batcher.stop()
    for req, p, n in zip(reqs, prompts, lengths):
        solo = ServeEngine(
            tiny_cfg, params, num_slots=1, max_context=64,
            prefill_buckets=(8, 16, 32), compute_dtype=jnp.float32,
        )
        assert req.tokens == greedy_generate(solo, p, n)[0]
    assert batcher.spec_proposed > 0
    assert 0 <= batcher.spec_accepted <= batcher.spec_proposed
    assert batcher.failed == 0


# ---------------------------------------------------------------------------
# fast decode, leg b: 4-bit-resident replica weights (PR 11 tentpole)
# ---------------------------------------------------------------------------


def _packed_leaves(engine):
    from opendiloco_tpu.models.llama import PackedW4

    return [
        x
        for x in jax.tree.leaves(
            engine.params, is_leaf=lambda x: isinstance(x, PackedW4)
        )
        if isinstance(x, PackedW4)
    ]


def test_w4_resident_logits_track_fp32(tiny_cfg):
    """w4 residency is a storage change, not a model change: the stacked
    matmul leaves really are packed (uint8 nibbles + uint16 scales), and
    the in-jit per-block dequant reproduces an fp32-resident engine
    running the SAME quantized values — identical tokens, logits equal
    to reduction-order noise. (How far quant(W) drifts from W is the
    codec's accuracy contract, pinned by the PR 8 compression tests.)"""
    from opendiloco_tpu.models.llama import dequant_w4

    w4, params = make_engine(tiny_cfg, weight_format="w4")

    packed = _packed_leaves(w4)
    assert packed  # the residency actually engaged
    assert all(
        p.q.dtype == jnp.uint8 and p.s.dtype == jnp.uint16 for p in packed
    )
    # norms ([L, D]) / embeddings / lm head stayed f32
    assert any(
        not hasattr(x, "q") and x.dtype == jnp.float32
        for x in jax.tree.leaves(w4.params)
    )

    # fp32 engine over the explicitly-dequantized weights = the oracle
    ref_params = jax.tree.map(
        lambda x: (
            np.stack([
                np.asarray(dequant_w4(x.q[i], x.s[i], x.shape, jnp.float32))
                for i in range(x.q.shape[0])
            ])
            if hasattr(x, "q")
            else x
        ),
        w4.params,
        is_leaf=lambda x: hasattr(x, "q"),
    )
    plain, _ = make_engine(tiny_cfg)
    plain.install_params(0, ref_params)

    ref_toks, ref_logits = greedy_generate(plain, [3, 1, 4, 1], 6)
    toks, logits = greedy_generate(w4, [3, 1, 4, 1], 6)
    assert toks == ref_toks
    np.testing.assert_allclose(logits, ref_logits, atol=2e-5, rtol=2e-4)


def test_w4_pack_native_and_numpy_fallback_agree(tiny_cfg, monkeypatch):
    """The packed-at-rest bits are the codec's bits: quantizing through
    the native kernel and through the numpy fallback yields identical
    payloads, so a w4 engine is reproducible across hosts with and
    without the built library."""
    from opendiloco_tpu import native

    w4_native, params = make_engine(tiny_cfg, weight_format="w4")
    monkeypatch.setattr(native, "get_lib", lambda: None)
    w4_np = ServeEngine(
        tiny_cfg, params, num_slots=4, max_context=64,
        prefill_buckets=(8, 16, 32), compute_dtype=jnp.float32,
        weight_format="w4",
    )
    pn, pf = _packed_leaves(w4_native), _packed_leaves(w4_np)
    assert pn and len(pn) == len(pf)
    for a, b in zip(pn, pf):
        np.testing.assert_array_equal(np.asarray(a.q), np.asarray(b.q))
        np.testing.assert_array_equal(np.asarray(a.s), np.asarray(b.s))
    # same bits at rest -> same tokens out
    assert (
        greedy_generate(w4_np, [7, 6, 5], 5)[0]
        == greedy_generate(w4_native, [7, 6, 5], 5)[0]
    )


def test_install_wire_w4_fast_path(tiny_cfg):
    """A blockwise4bit snapshot installs into a w4 engine without a
    dequant/requantize round trip where the codec's whole-leaf block
    grid lands on layer boundaries: the resident packed leaves dequant
    to EXACTLY the codec's own reconstruction."""
    from opendiloco_tpu.diloco.compression import get_codec
    from opendiloco_tpu.models.llama import W4_BLOCK, dequant_w4

    engine, _ = make_engine(tiny_cfg, weight_format="w4")
    _, params2 = make_engine(tiny_cfg, seed=77)
    blobs = _wire_blobs(params2, "blockwise4bit")
    engine.install_wire(1, blobs, "blockwise4bit")
    assert engine.weights_epoch == 1

    codec = get_codec("blockwise4bit")
    leaves = jax.tree.leaves(
        engine.params, is_leaf=lambda x: hasattr(x, "q")
    )
    aligned = 0
    for leaf, (payload, meta, shape) in zip(leaves, blobs):
        if not hasattr(leaf, "q"):
            continue
        size = int(np.prod(shape))
        per_layer = size // shape[0]
        want = codec.decode(payload, (size,), meta).reshape(shape)
        got = np.stack([
            np.asarray(dequant_w4(leaf.q[i], leaf.s[i], leaf.shape, jnp.float32))
            for i in range(shape[0])
        ])
        if per_layer % W4_BLOCK == 0:
            aligned += 1
            np.testing.assert_array_equal(got, want)  # re-sliced, bit-exact
        else:
            # fallback repack: one extra quantization of grid values
            np.testing.assert_allclose(got, want, atol=2e-2, rtol=0)
    assert aligned  # the fast path actually ran on this geometry
    toks, logits = greedy_generate(engine, [1, 2, 3], 4)
    assert np.isfinite(logits).all()


# ---------------------------------------------------------------------------
# fast decode, leg c: shared-prefix KV reuse (PR 11 tentpole)
# ---------------------------------------------------------------------------


def test_prefix_reuse_kv_bytes_identical(tiny_cfg):
    """Reusing a live slot's prefix writes the SAME K/V bytes a cold
    prefill writes (causal attention makes prefix rows independent of
    the suffix), the suffix rows agree to float tolerance, and the
    generated stream is token-identical to a cold admit."""
    engine, params = make_engine(tiny_cfg)
    sysp = [9, 8, 7, 6, 5, 4]
    p2 = sysp + [20, 21, 22]
    plen, n_new = len(sysp), 8

    cold = ServeEngine(
        tiny_cfg, params, num_slots=4, max_context=64,
        prefill_buckets=(8, 16, 32), compute_dtype=jnp.float32,
    )
    cold_toks, _ = greedy_generate(cold, p2, n_new, slot=1)

    engine.admit(0, sysp + [30, 31])  # the live source slot
    tok, _ = engine.admit(1, p2, prefix_src=0, prefix_len=plen)
    assert tok == cold_toks[0]
    # slot 1's rows [L, len(p2), Nkv, Dh], read through the cache module
    rows = lambda e: fetch_pages(e.cache_k, e.cache_v, jnp.int32(1), len(p2))
    for warm, ref in zip(rows(engine), rows(cold)):
        warm, ref = np.asarray(warm), np.asarray(ref)
        np.testing.assert_array_equal(warm[:, :plen], ref[:, :plen])
        np.testing.assert_allclose(
            warm[:, plen:], ref[:, plen:], atol=2e-6, rtol=2e-5
        )

    toks = [tok]  # and the continuation matches token-for-token
    lens = np.zeros((engine.num_slots,), np.int32)
    cur = np.zeros((engine.num_slots,), np.int32)
    lens[1], cur[1] = len(p2), tok
    for _ in range(n_new - 1):
        nxt, _ = engine.decode_step(cur, lens)
        toks.append(int(nxt[1]))
        lens[1] += 1
        cur[1] = toks[-1]
    assert toks == cold_toks


def test_prefix_batcher_hits_and_parity(tiny_cfg):
    """The batcher detects a shared system prompt across queued
    requests, reuses the live slot's prefix K/V, and the second request
    still gets its isolated-greedy tokens."""
    engine, params = make_engine(tiny_cfg)
    batcher = ContinuousBatcher(engine, prefix_cache=True).start()
    sysp = list(range(1, 9))
    p1, p2 = sysp + [30, 31], sysp + [40]
    try:
        r1 = batcher.submit(p1, max_new_tokens=12)
        r2 = batcher.submit(p2, max_new_tokens=4)
        assert r1.wait(60) and r1.error is None
        assert r2.wait(60) and r2.error is None
    finally:
        batcher.stop()
    for req, p, n in ((r1, p1, 12), (r2, p2, 4)):
        solo = ServeEngine(
            tiny_cfg, params, num_slots=1, max_context=64,
            prefill_buckets=(8, 16, 32), compute_dtype=jnp.float32,
        )
        assert req.tokens == greedy_generate(solo, p, n)[0]
    assert batcher.prefix_hits >= 1
    assert batcher.prefix_tokens_saved >= len(sysp)


def test_build_serving_with_diloco_swaps_live(tiny_cfg):
    """build_serving end-to-end: training advances outer epochs in a
    thread while the serving plane completes requests and hot-swaps —
    the shared-process contract train.py relies on."""
    opt, trainer, state = _make_opt(tiny_cfg, local_steps=2)
    scfg = ServeConfig(
        enabled=True, max_batch=2, max_context=64,
        prefill_buckets=[16], swap_every_steps=1,
    )
    plane = build_serving(
        scfg, tiny_cfg, state["params"], opt, compute_dtype=jnp.float32,
        start_server=False,
    )
    try:
        rng = np.random.default_rng(0)

        def train_loop():
            s = state
            for _ in range(4):  # 2 outer epochs
                ids = rng.integers(0, 256, (8, 16)).astype(np.int32)
                batch = trainer.shard_batch(ids, ids.copy(), 1)
                s, _ = opt.step(s, batch)

        t = threading.Thread(target=train_loop)
        t.start()
        reqs = [
            plane.batcher.submit(rng.integers(1, 256, 4).tolist(), max_new_tokens=5)
            for _ in range(6)
        ]
        t.join()
        # keep serving after training stops until a swap catches the tail
        for r in reqs:
            assert r.wait(120) and r.error is None
        extra = plane.batcher.submit([1, 2, 3], max_new_tokens=3)
        assert extra.wait(60) and extra.error is None
    finally:
        plane.stop()
    assert opt.epoch == 2
    assert plane.engine.swap_count >= 1
    assert plane.batcher.failed == 0


# ---------------------------------------------------------------------------
# admission control: priority tiers, deadlines, structured backpressure
# ---------------------------------------------------------------------------


def test_queue_orders_by_priority_then_deadline(tiny_cfg):
    """_pop_next: lower tier first; within a tier, earliest deadline;
    deadline-free requests after deadlined ones; submit order last."""
    engine, _ = make_engine(tiny_cfg)
    batcher = ContinuousBatcher(engine=engine)  # loop never started
    r_bulk = batcher.submit([1, 2, 3], priority=1)
    r_slow = batcher.submit([1, 2, 3], priority=0, deadline_ms=60000)
    r_soon = batcher.submit([1, 2, 3], priority=0, deadline_ms=5000)
    r_free = batcher.submit([1, 2, 3], priority=0)
    order = [batcher._pop_next() for _ in range(4)]
    assert order == [r_soon, r_slow, r_free, r_bulk]
    assert batcher._pop_next() is None


def test_submit_sheds_spent_deadline(tiny_cfg):
    """deadline_ms <= 0 means the client's budget is already gone: shed
    at submit, never queued, never decoded."""
    engine, _ = make_engine(tiny_cfg)
    batcher = ContinuousBatcher(engine=engine)
    req = batcher.submit([1, 2, 3], deadline_ms=0)
    assert req.wait(0) and req.error == "deadline exceeded"
    assert batcher.shed == 1 and len(batcher._queue) == 0


def test_sweep_sheds_expired_queued_request(tiny_cfg):
    """A queued request whose deadline lapses is retired by the sweep
    with 'deadline exceeded' — it never occupies a slot."""
    engine, _ = make_engine(tiny_cfg)
    batcher = ContinuousBatcher(engine=engine)
    doomed = batcher.submit([1, 2, 3], deadline_ms=10)
    safe = batcher.submit([1, 2, 3], deadline_ms=60000)
    time.sleep(0.05)
    batcher._sweep_cancelled()
    assert doomed.wait(0) and doomed.error == "deadline exceeded"
    assert not safe.wait(0)
    assert batcher.shed == 1 and list(batcher._queue) == [safe]


def test_health_vector_and_wait_estimate(tiny_cfg):
    engine, _ = make_engine(tiny_cfg, num_slots=2)
    batcher = ContinuousBatcher(engine=engine)
    h = batcher.health()
    assert h["queue_depth"] == 0 and h["p99_ms"] is None
    assert h["occupancy"] == 0.0 and h["shed"] == 0
    for _ in range(8):
        batcher.submit([1, 2, 3])
    # 8 queued over 2 slots at the 0.25s default EWMA -> 1s estimate
    assert batcher.estimate_wait_s() == pytest.approx(1.0)
    assert batcher.health()["queue_depth"] == 8


def test_server_queue_full_is_structured_503(tiny_cfg):
    """A full batcher queue answers HTTP 503 + Retry-After with a JSON
    body carrying retry_after_s, and /stats counts the reject."""
    engine, _ = make_engine(tiny_cfg)
    batcher = ContinuousBatcher(engine=engine, max_queue=0)  # always full
    srv = ServeServer(batcher, port=0)
    try:
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/generate",
            data=json.dumps({"prompt": [1, 2, 3]}).encode(),
            method="POST",
        )
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 503
        assert float(ei.value.headers["Retry-After"]) >= 0.1
        body = json.loads(ei.value.read())
        assert body["error"] == "queue full"
        assert body["retry_after_s"] >= 0.1
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/stats", timeout=10
        ) as r:
            stats = json.loads(r.read())
        assert stats["rejected_total"] == 1
    finally:
        srv.stop()


def test_bind_retry_takes_over_released_port():
    """Satellite: a respawn at a known address retries the explicit bind
    while the dying predecessor tears down, instead of falling back to
    an ephemeral port nobody dials."""
    from opendiloco_tpu.serve.server import bind_with_fallback

    holder = socket.socket()
    holder.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    holder.bind(("127.0.0.1", 0))
    holder.listen(1)
    port = holder.getsockname()[1]

    threading.Timer(0.3, holder.close).start()
    sock = bind_with_fallback("127.0.0.1", port, "test", retry_s=5.0)
    try:
        assert sock.getsockname()[1] == port  # same address, not ephemeral
    finally:
        sock.close()

    # without retry budget the old behavior stands: immediate fallback
    holder2 = socket.socket()
    holder2.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    holder2.bind(("127.0.0.1", 0))
    holder2.listen(1)
    port2 = holder2.getsockname()[1]
    try:
        sock2 = bind_with_fallback("127.0.0.1", port2, "test", retry_s=0.0)
        try:
            assert sock2.getsockname()[1] != port2
        finally:
            sock2.close()
    finally:
        holder2.close()
