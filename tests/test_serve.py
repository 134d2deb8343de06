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

Prefix-reuse oracles:
- prefix reuse writes the SAME prefix K/V bytes a cold prefill writes,
  whichever bucket the suffix pads to (to a rounding only where another
  bucket's program prefilled the source slot), and the stream stays the
  cold admission's while the ring wraps
- the continued prefill is the one-token loop: over a tail behind a
  prefix of any length, padded to a bucket that may pass the ring's end,
  its logits and the rows it leaves are the decode steps'
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


WIRE_CODECS = (
    "none", "fp16", "scaled-fp16", "uniform8bit", "quantile8bit", "blockwise8bit",
    "blockwise4bit", "topk",
)


@pytest.mark.parametrize("codec_name", WIRE_CODECS)
def test_swap_mid_decode_changes_no_kv_entries(tiny_cfg, codec_name):
    """Regression (satellite 2): installing a snapshot between decode
    steps must leave every in-flight KV cache byte unchanged — and the
    generation continues under the new weights without error. Whatever
    codec the snapshot rides (``ODTP_STATE_CODEC`` may name any the outer
    plane has), ``install_wire`` binds the tree that codec decodes to: the
    weights and the next step's logits are those of ``install_params`` of
    the decoded tree, bit for bit."""
    from opendiloco_tpu.diloco.compression import _CODECS, compress_roundtrip, get_codec

    assert set(WIRE_CODECS) == set(_CODECS)  # a new codec gets its case here
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
    # a twin in the same state, to take the decoded tree uncompressed
    twin, _ = make_engine(tiny_cfg)
    twin.admit(0, [4, 8, 15, 16])
    twin.decode_step(tokens, lens)
    engine.install_wire(1, _wire_blobs(params2, codec_name), codec_name)
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
    codec = get_codec(codec_name)
    twin.install_params(1, jax.tree.map(
        lambda x: compress_roundtrip(np.asarray(x, np.float32).reshape(-1), codec).reshape(x.shape),
        params2,
    ))
    for got, want in zip(jax.tree.leaves(engine.params), jax.tree.leaves(twin.params)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(logits2[0]), np.asarray(twin.decode_step(tokens, lens)[1][0])
    )


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
# shared-prefix KV reuse: the continued prefill
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
@pytest.mark.parametrize(
    "suffix, src_tail, max_context, n_new, exact",
    [
        (3, 2, 64, 8, True),
        (12, 12, 64, 8, True),
        (20, 20, 64, 8, True),
        (3, 2, 16, 14, True),
        (20, 2, 64, 8, False),
    ],
    ids=[
        "suffix-bucket-8", "suffix-bucket-16", "suffix-bucket-32", "ring-wraps",
        "source-of-another-bucket",
    ],
)
def test_prefix_reuse_kv_bytes_identical(
    tiny_cfg, suffix, src_tail, max_context, n_new, exact, kernel, monkeypatch
):
    """Reusing a live slot's prefix writes the SAME K/V bytes a cold
    prefill writes (causal attention makes prefix rows independent of
    the suffix), the suffix rows agree to float tolerance, and the
    generated stream is token-identical to a cold admit: for a suffix
    padded to each prefill bucket, and while the slot's ring wraps under
    the decode steps that follow (9 + 14 tokens on 16 rows). Under
    ``pallas`` the decode steps run their kernel (interpreted, the ring in
    tiles of 8 rows); the continued prefill is XLA's under either. The one case
    that is not ``exact`` has the source slot's prompt prefilled by the
    bucket-8 program and the cold prompt by the bucket-32 one: the copy is
    still the source's bytes, and those are the cold rows to a rounding."""
    monkeypatch.setenv("ODTP_DECODE_BLOCK_T", "8")
    buckets = tuple(b for b in (8, 16, 32) if b <= max_context)
    engine, params = make_engine(
        tiny_cfg, max_context=max_context, prefill_buckets=buckets, decode_kernel=kernel
    )
    sysp = [9, 8, 7, 6, 5, 4]
    p2 = sysp + list(range(20, 20 + suffix))
    plen = len(sysp)
    assert pick_bucket(suffix, buckets) == {3: 8, 12: 16, 20: 32}[suffix]

    cold = ServeEngine(
        tiny_cfg, params, num_slots=4, max_context=max_context,
        prefill_buckets=buckets, compute_dtype=jnp.float32, decode_kernel=kernel,
    )
    cold.admit(1, p2)
    # slot 1's rows [L, len(p2), Nkv, Dh], read through the cache module
    rows = lambda e, slot=1, n=len(p2): [
        np.asarray(x) for x in fetch_pages(e.cache_k, e.cache_v, jnp.int32(slot), n)
    ]
    cold_rows = rows(cold)  # before its decode steps wrap the ring over them
    cold_toks, _ = greedy_generate(cold, p2, n_new, slot=1)

    engine.admit(0, sysp + list(range(30, 30 + src_tail)))  # the live source slot
    tok, _ = engine.admit(1, p2, prefix_src=0, prefix_len=plen)
    assert tok == cold_toks[0]
    for warm, src, ref in zip(rows(engine), rows(engine, 0, plen), cold_rows):
        np.testing.assert_array_equal(warm[:, :plen], src)
        if exact:
            np.testing.assert_array_equal(warm[:, :plen], ref[:, :plen])
        np.testing.assert_allclose(warm, ref, atol=2e-6, rtol=2e-5)

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


@pytest.mark.parametrize("start, bucket", [(0, 8), (5, 8), (9, 8), (10, 6)])
def test_continued_prefill_is_the_one_token_loop_up_to_the_rings_end(tiny_cfg, start, bucket):
    """The continued prefill over a tail of 6 tokens from position ``start``,
    padded to ``bucket``, gives the logits of the 6 decode steps over the same
    ring of 16 rows, one count of real tokens at a time, and leaves the rows
    they leave: from an empty slot, inside the ring, with the bucket's padding
    passing the ring's end (rows 9-14 of a block that would be 9-16: the block
    is the ring's last 8 and the tail's rows move down in it), and with the
    tail ending on the ring's last row. A padding row is never written, and
    another slot's rows are never touched."""
    from opendiloco_tpu.models.llama import chunk_prefill_forward, decode_forward, init_kv_cache

    T, K, f32 = 16, 6, dict(compute_dtype=jnp.float32)
    params = init_params(jax.random.PRNGKey(3), tiny_cfg)
    ids = np.random.default_rng(start).integers(1, tiny_cfg.vocab_size, start + K)
    step = jax.jit(lambda tok, pos, ck, cv: decode_forward(
        params, jnp.asarray([0, tok], jnp.int32), jnp.asarray([0, pos], jnp.int32),
        ck, cv, tiny_cfg, **f32))
    cache = init_kv_cache(tiny_cfg, 2, T, jnp.float32)
    ck, cv = cache["k"] + 7.0, cache["v"] - 7.0  # what a padding row must not overwrite
    for pos in range(start):  # slot 1 holds the sequence, slot 0 idles
        _, ck, cv = step(ids[pos], pos, ck, cv)
    want, sk, sv = [], ck, cv
    for i in range(K):
        logits, sk, sv = step(ids[start + i], start + i, sk, sv)
        want.append(np.asarray(logits[1]))
    tail = np.zeros((1, bucket), np.int32)
    tail[0, :K] = ids[start:]
    run = jax.jit(lambda count: chunk_prefill_forward(
        params, jnp.asarray(tail), start, count, 1, ck, cv, None, tiny_cfg, **f32))
    for count in range(1, K + 1):
        got, gk, gv, _ = run(count)
        np.testing.assert_allclose(np.asarray(got[0]), want[count - 1], atol=2e-5, rtol=2e-4)
        assert int(np.argmax(got[0])) == int(np.argmax(want[count - 1]))
        for mine, loops, before in ((gk, sk, ck), (gv, sv, cv)):
            np.testing.assert_allclose(
                mine[:, 1, ..., start : start + count], loops[:, 1, ..., start : start + count],
                atol=2e-5, rtol=2e-4)
            np.testing.assert_array_equal(mine[:, 1, ..., start + count :], before[:, 1, ..., start + count :])
            np.testing.assert_array_equal(mine[:, 1, ..., :start], before[:, 1, ..., :start])
            np.testing.assert_array_equal(mine[:, 0], before[:, 0])


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


@pytest.mark.parametrize("where, name", [
    ("config", "spec_decode_k"), ("config", "draft_layers"), ("config", "weight_format"),
    ("engine", "spec_k"), ("engine", "draft_layers"), ("engine", "weight_format"),
    ("env", "ODTP_SPEC_K"), ("env", "ODTP_DECODE_WEIGHT_FORMAT"),
    ("config", "decode_kernel"), ("env", "ODTP_DECODE_KERNEL"),
])
def test_the_removed_decode_options_are_unknown_names(tiny_cfg, where, name):
    """Speculative decode and 4-bit resident weights went with their eight
    settable values (PR 44), the decode kernel's option and environment name
    with PR 58 (the platform chooses): each is refused as any unknown key or
    argument is, and the environment variables are declared and read nowhere."""
    import pathlib

    import pydantic

    import opendiloco_tpu
    from opendiloco_tpu.analysis import knobs

    if where == "config":
        with pytest.raises(pydantic.ValidationError, match=name):
            ServeConfig(**{name: {"weight_format": "fp32", "decode_kernel": "xla"}.get(name, 0)})
    elif where == "engine":
        with pytest.raises(TypeError, match=name):
            make_engine(tiny_cfg, **{name: 0 if name != "weight_format" else "fp32"})
    else:
        assert name not in {k.name for k in knobs.KNOBS}
        package = pathlib.Path(opendiloco_tpu.__file__).parent
        assert not [p for p in package.rglob("*.py") if name in p.read_text()]


def test_the_engine_takes_a_kernel_by_name_or_the_platforms(tiny_cfg):
    """``decode_kernel`` is the tests' seam: "pallas" | "xla" by name, None for
    what the platform runs (off the TPU, the XLA forms); "auto" went with the
    option that spelled it."""
    assert make_engine(tiny_cfg)[0].decode_kernel == "xla"
    assert make_engine(tiny_cfg, decode_kernel="pallas")[0].decode_kernel == "pallas"
    with pytest.raises(ValueError, match="unknown decode kernel 'auto'"):
        make_engine(tiny_cfg, decode_kernel="auto")


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
