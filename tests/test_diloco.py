"""DiLoCo algorithm tests against the loopback backend.

Oracles (mirroring the reference's test strategy, SURVEY.md §4, and the
normative algorithm of train_diloco_torch.py:336-353):
- outer SGD matches torch.optim.SGD(nesterov) numerically
- single-worker DiLoCo with identity outer step == plain inner training
- multi-worker workers re-synchronize exactly at each outer boundary
- codecs round-trip within their precision
- state_dict round-trips
"""

import threading
import time

import jax
import numpy as np
import pytest

from opendiloco_tpu.config import DilocoConfig
from opendiloco_tpu.diloco import (
    DiLoCoOptimizer,
    LoopbackWorld,
    OuterSGD,
    get_codec,
)
from opendiloco_tpu.diloco.compression import compress_roundtrip
from opendiloco_tpu.diloco.optimizer import _piece_tag
from opendiloco_tpu.parallel.mesh import build_mesh
from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig


_next_dev = iter(range(10**9))


def make_trainer(tiny_cfg, devices=None, strategy="NO_SHARD"):
    tc = TrainerConfig(
        lr=1e-3, warmup_steps=2, total_steps=200, precision="fp32", remat=False
    )
    if devices is None:
        # one distinct single-device mesh per trainer: this file runs
        # multiple workers as threads, and concurrent multi-device XLA
        # executions deadlock on the CPU client (same pattern as
        # test_galaxy_smoke's per-worker meshes)
        all_dev = jax.devices()
        devices = [all_dev[next(_next_dev) % len(all_dev)]]
    plan = build_mesh(strategy, devices=devices)
    return InnerTrainer(tiny_cfg, tc, plan)


def batches(seed, vocab, n, global_bs=8, seq=16):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        starts = rng.integers(0, vocab, (global_bs, 1))
        ids = ((starts + np.arange(seq)) % vocab).astype(np.int32)
        yield ids, ids.copy()


# ---------------------------------------------------------------------------
# codecs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "name,tol",
    [
        ("none", 0),
        ("fp16", 1e-3),
        ("scaled-fp16", 1e-3),
        ("uniform8bit", 2e-2),
        ("quantile8bit", 2e-1),  # tail buckets are coarse by design
        ("blockwise8bit", 2e-2),
    ],
)
def test_codec_roundtrip(name, tol):
    rng = np.random.default_rng(0)
    arr = rng.normal(scale=0.1, size=(333, 17)).astype(np.float32)
    out = compress_roundtrip(arr, get_codec(name))
    assert out.shape == arr.shape and out.dtype == np.float32
    scale = np.abs(arr).max()
    assert np.abs(out - arr).max() <= tol * scale + 1e-8
    assert np.abs(out - arr).mean() <= 1e-2 * scale + 1e-8


def test_codec_sizes():
    arr = np.zeros((4096,), np.float32)
    assert len(get_codec("fp16").encode(arr)[0]) == arr.nbytes // 2
    # blockwise payload = 1 block scale (4B) + 4096 int8
    assert len(get_codec("blockwise8bit").encode(arr)[0]) == arr.nbytes // 4 + 4


def test_codec_meta_is_json_serializable():
    """meta rides the JSON frame header (wire.py) -- bytes would crash."""
    import json

    rng = np.random.default_rng(0)
    arr = rng.normal(size=(1000,)).astype(np.float32)
    for name in ["none", "fp16", "scaled-fp16", "uniform8bit", "quantile8bit", "blockwise8bit"]:
        _, meta = get_codec(name).encode(arr)
        json.dumps(meta)  # must not raise


@pytest.mark.parametrize(
    "name",
    ["none", "fp16", "scaled-fp16", "uniform8bit", "quantile8bit", "blockwise8bit"],
)
def test_codec_decode_accumulate_matches_decode(name):
    rng = np.random.default_rng(1)
    arr = rng.normal(scale=0.1, size=(5000,)).astype(np.float32)
    codec = get_codec(name)
    payload, meta = codec.encode(arr)
    base = rng.normal(size=arr.shape).astype(np.float32)
    expected = base + codec.decode(payload, arr.shape, meta)
    got = base.copy()
    codec.decode_accumulate(payload, meta, got)
    np.testing.assert_allclose(got, expected, rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# outer optimizer vs torch oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nesterov", [True, False])
def test_outer_sgd_matches_torch(nesterov):
    torch = pytest.importorskip("torch")
    rng = np.random.default_rng(1)
    p0 = rng.normal(size=(13, 7)).astype(np.float32)

    tp = torch.nn.Parameter(torch.tensor(p0.copy()))
    topt = torch.optim.SGD([tp], lr=0.7, momentum=0.9, nesterov=nesterov)

    ours = OuterSGD(lr=0.7, momentum=0.9, nesterov=nesterov)
    p = [p0.copy()]
    for i in range(5):
        g = rng.normal(size=p0.shape).astype(np.float32)
        tp.grad = torch.tensor(g.copy())
        topt.step()
        ours.step(p, [g])
        np.testing.assert_allclose(p[0], tp.detach().numpy(), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# DiLoCo algorithm
# ---------------------------------------------------------------------------


def run_plain(tiny_cfg, n_steps, seed=0):
    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(7))
    losses = []
    for ids, labels in batches(seed, tiny_cfg.vocab_size, n_steps):
        batch = trainer.shard_batch(ids, labels, accum=1)
        state, m = trainer.train_step(state, batch)
        losses.append(float(m["loss"]))
    return np.array(losses), jax.device_get(state["params"])


def run_diloco_single(tiny_cfg, n_steps, local_steps, outer_lr, momentum, seed=0):
    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(7))
    world = LoopbackWorld(1)
    (backend,) = world.make_backends()
    cfg = DilocoConfig(
        outer_lr=outer_lr,
        outer_momentum=momentum,
        outer_nesterov=False,
        local_steps=local_steps,
        backend="loopback",
    )
    opt = DiLoCoOptimizer(trainer, backend, cfg, state, batch_size=8)
    losses = []
    for ids, labels in batches(seed, tiny_cfg.vocab_size, n_steps):
        batch = trainer.shard_batch(ids, labels, accum=1)
        state, m = opt.step(state, batch)
        losses.append(float(m["loss"]))
    return np.array(losses), jax.device_get(state["params"]), opt


def test_identity_outer_step_equals_plain_training(tiny_cfg):
    """outer_lr=1, momentum=0, single worker: outer update writes back
    exactly the inner params -> trajectory identical to plain training."""
    ref_losses, ref_params = run_plain(tiny_cfg, 8)
    got_losses, got_params, _ = run_diloco_single(
        tiny_cfg, 8, local_steps=4, outer_lr=1.0, momentum=0.0
    )
    np.testing.assert_allclose(got_losses, ref_losses, rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        got_params,
        ref_params,
    )


def test_diloco_epoch_accounting(tiny_cfg):
    _, _, opt = run_diloco_single(
        tiny_cfg, 10, local_steps=4, outer_lr=0.7, momentum=0.9
    )
    assert opt.epoch == 2
    assert opt.local_step == 2


def run_diloco_workers(tiny_cfg, n_workers, n_steps, local_steps, compression="none"):
    """N worker threads sharing a LoopbackWorld; returns per-worker params."""
    world = LoopbackWorld(n_workers, compression=compression)
    backends = world.make_backends()
    results = [None] * n_workers
    errors = []

    def worker(rank):
        try:
            trainer = make_trainer(tiny_cfg)
            state = trainer.init_state(jax.random.key(7))  # same init everywhere
            cfg = DilocoConfig(
                local_steps=local_steps,
                outer_nesterov=True,
                backend="loopback",
                timeout_waiting_for_peers=30.0,
                averaging_timeout=60.0,
            )
            opt = DiLoCoOptimizer(
                trainer, backends[rank], cfg, state, batch_size=8
            )
            losses = []
            for ids, labels in batches(
                1000 + rank, tiny_cfg.vocab_size, n_steps
            ):  # different data shard per worker
                batch = trainer.shard_batch(ids, labels, accum=1)
                state, m = opt.step(state, batch)
                losses.append(float(m["loss"]))
            results[rank] = (np.array(losses), jax.device_get(state["params"]))
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(r,)) for r in range(n_workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert all(r is not None for r in results)
    return results


def test_streaming_fragments_sync_one_fragment_per_boundary(tiny_cfg):
    """Streaming DiLoCo fragment sync (arxiv 2501.18512): each outer
    boundary all-reduces ONE size-balanced leaf fragment (epoch mod N).
    Asserts the three defining properties over 4 boundaries x 2 workers:
    masters stay identical across workers (every master update is an
    all-reduced fragment update), each boundary's wire traffic is ~1/N of
    the model, and after the final boundary the just-synced fragment's
    device leaves equal the master while the other fragment's leaves kept
    diverging local progress."""
    n_workers, local_steps, n_steps = 2, 4, 16  # 4 boundaries
    world = LoopbackWorld(n_workers)
    backends = world.make_backends()
    results = [None] * n_workers
    # bytes a round (keyed by its epoch), in however many pieces it crossed
    wire_bytes: list[dict[int, int]] = [{} for _ in range(n_workers)]
    errors = []

    def worker(rank):
        try:
            trainer = make_trainer(tiny_cfg)
            state = trainer.init_state(jax.random.key(7))
            cfg = DilocoConfig(
                local_steps=local_steps,
                outer_nesterov=True,
                backend="loopback",
                timeout_waiting_for_peers=30.0,
                averaging_timeout=60.0,
                streaming_fragments=2,
            )
            be = backends[rank]
            inner_all_reduce = be.all_reduce

            def spy_all_reduce(arrays, **kw):
                seen = wire_bytes[rank]
                seen[kw["epoch"]] = seen.get(kw["epoch"], 0) + sum(
                    a.nbytes for a in arrays
                )
                return inner_all_reduce(arrays, **kw)

            be.all_reduce = spy_all_reduce
            opt = DiLoCoOptimizer(trainer, be, cfg, state, batch_size=8)
            for ids, labels in batches(1000 + rank, tiny_cfg.vocab_size, n_steps):
                state, m = opt.step(
                    state, trainer.shard_batch(ids, labels, accum=1)
                )
                assert np.isfinite(m["loss"])
            results[rank] = (
                opt,
                [
                    np.asarray(x, np.float32)
                    for x in jax.tree.leaves(jax.device_get(state["params"]))
                ],
            )
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [
        threading.Thread(target=worker, args=(r,)) for r in range(n_workers)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors

    (opt0, dev0), (opt1, dev1) = results
    frags = opt0._fragments
    assert frags == opt1._fragments and len(frags) == 2
    total = sum(m.size for m in opt0.master)
    sizes = [sum(opt0.master[i].size for i in f) for f in frags]
    assert all(0.2 * total < s < 0.8 * total for s in sizes), sizes

    # masters never diverge: every update is an all-reduced fragment step
    for a, b in zip(opt0.master, opt1.master):
        np.testing.assert_array_equal(a, b)

    # each boundary moved ~one fragment, not the model: per-round wire
    # bytes match the fragment sizes exactly, alternating 0,1,0,1
    frag_bytes = [
        sum(opt0.master[i].nbytes for i in f) for f in frags
    ]
    for rank in range(n_workers):
        assert wire_bytes[rank] == {
            e: frag_bytes[e % 2] for e in range(4)
        }, wire_bytes[rank]

    # final boundary (epoch 3) synced fragment 1: those device leaves sit
    # exactly on the shared master; fragment 0's leaves kept local progress
    # since their epoch-2 reset and so differ across workers
    for i in frags[1]:
        np.testing.assert_array_equal(dev0[i], opt0.master[i])
        np.testing.assert_array_equal(dev1[i], opt1.master[i])
    assert any(
        not np.array_equal(dev0[i], dev1[i]) for i in frags[0]
    ), "un-synced fragment should carry diverging local progress"


def test_streaming_fragments_config_constraints():
    # streaming x gossip composes now: keyed per-fragment pair rounds
    DilocoConfig(streaming_fragments=2, outer_mode="gossip")
    with pytest.raises(Exception, match="average_state_every"):
        DilocoConfig(streaming_fragments=2, average_state_every=4)
    with pytest.raises(Exception, match="stream_stagger"):
        DilocoConfig(stream_stagger=0.0)
    with pytest.raises(Exception, match="stream_stagger"):
        DilocoConfig(stream_stagger=1.5)
    DilocoConfig(streaming_fragments=4)  # valid
    # streaming x overlap composes now (staggered in-phase fragment rounds)
    DilocoConfig(streaming_fragments=2, overlap_comm="delayed")
    DilocoConfig(
        streaming_fragments=4, overlap_comm="eager", stream_stagger=0.5
    )


def test_two_workers_resync_and_learn(tiny_cfg):
    results = run_diloco_workers(tiny_cfg, 2, n_steps=8, local_steps=4)
    (l0, p0), (l1, p1) = results
    # workers end exactly at an outer boundary -> identical params
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7), p0, p1
    )
    assert np.all(np.isfinite(l0)) and np.all(np.isfinite(l1))


def test_two_workers_with_compression(tiny_cfg):
    results = run_diloco_workers(
        tiny_cfg, 2, n_steps=4, local_steps=4, compression="scaled-fp16"
    )
    (l0, p0), (l1, p1) = results
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7), p0, p1
    )


@pytest.mark.slow
def test_diloco_converges_within_band_of_ddp(tiny_cfg):
    """THE DiLoCo claim (reference README: ~same perplexity at 500x less
    communication): 2 workers x 25 local steps between outer syncs must land
    within a loss band of fully-synchronous DDP at the SAME total sample
    count. Normative loop: train_diloco_torch.py:336-353; SURVEY §4 addendum.
    """
    n_steps, local_steps = 50, 25
    results = run_diloco_workers(
        tiny_cfg, 2, n_steps=n_steps, local_steps=local_steps
    )
    (l0, p0), (l1, p1) = results

    # DDP at equal total batch: one worker, global_bs=16, same data -- each
    # step's batch is the two workers' shard batches concatenated
    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(7))  # same init
    shard0 = batches(1000, tiny_cfg.vocab_size, n_steps)
    shard1 = batches(1001, tiny_cfg.vocab_size, n_steps)
    ddp_losses = []
    for (ids0, lab0), (ids1, lab1) in zip(shard0, shard1):
        batch = trainer.shard_batch(
            np.concatenate([ids0, ids1]), np.concatenate([lab0, lab1]), accum=1
        )
        state, m = trainer.train_step(state, batch)
        ddp_losses.append(float(m["loss"]))
    ddp_params = state["params"]

    # held-out eval: same fresh batch for all three parameter sets
    eval_ids, eval_labels = next(batches(9999, tiny_cfg.vocab_size, 1, global_bs=32))
    ev = {
        "ddp": trainer.eval_loss(ddp_params, eval_ids, eval_labels),
        "diloco_w0": trainer.eval_loss(
            jax.device_put(p0, trainer.state_shardings["params"]),
            eval_ids,
            eval_labels,
        ),
    }
    # workers ended on an outer boundary: p0 == p1 (resync oracle covers
    # this); both runs must have actually learned the pattern
    init_loss = float(np.log(tiny_cfg.vocab_size))
    assert ev["ddp"] < init_loss - 1.0, ev
    assert ev["diloco_w0"] < init_loss - 1.0, ev
    # the band: DiLoCo within 15% relative of same-total-batch DDP
    assert ev["diloco_w0"] <= ev["ddp"] * 1.15 + 0.05, ev


def test_onboarding_fetch_never_sees_torn_master(tiny_cfg):
    """Hammer _state_for_peers concurrently with blocking outer steps: every
    fetched (epoch, master) must equal exactly the pre- or post-round state,
    never a mix (the serve thread races the in-place OuterSGD update;
    hivemind's load_state_from_peers always returns a consistent epoch
    snapshot, hivemind_diloco.py:528-531)."""
    import time as _time

    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(7))
    world = LoopbackWorld(1)
    (backend,) = world.make_backends()
    cfg = DilocoConfig(
        outer_lr=0.7, outer_momentum=0.0, local_steps=2, backend="loopback"
    )
    opt = DiLoCoOptimizer(trainer, backend, cfg, state, batch_size=8)

    class SlowSGD(OuterSGD):
        """Widens the race window: sleeps between in-place leaf updates."""

        def step(self, params, grads):
            for p, g in zip(params, grads):
                p -= self.lr * g
                _time.sleep(0.001)

    opt.outer_opt = SlowSGD(lr=0.7, momentum=0.0)

    expected = {0: [m.copy() for m in opt.master]}  # epoch -> master
    mismatches: list[str] = []
    deferred: list[tuple[int, list]] = []  # fetched before epoch recorded
    seen_epochs: set[int] = set()
    done = threading.Event()

    def hammer():
        while not done.is_set():
            s = opt._state_for_peers()
            e = int(s["epoch"])
            seen_epochs.add(e)
            want = expected.get(e)
            if want is None:
                if len(deferred) < 64:
                    deferred.append((e, s["master"]))
                continue
            if not all(
                np.array_equal(a, b) for a, b in zip(want, s["master"])
            ):
                mismatches.append(f"torn master at epoch {e}")
                return

    threads = [threading.Thread(target=hammer) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        n_rounds = 4
        for ids, labels in batches(
            11, tiny_cfg.vocab_size, n_rounds * cfg.local_steps
        ):
            batch = trainer.shard_batch(ids, labels, accum=1)
            state, _ = opt.step(state, batch)
            if opt.epoch not in expected:
                expected[opt.epoch] = [m.copy() for m in opt.master]
    finally:
        done.set()
        for t in threads:
            t.join()

    for e, master in deferred:
        assert e in expected, f"fetched state at unknown epoch {e}"
        assert all(
            np.array_equal(a, b) for a, b in zip(expected[e], master)
        ), f"torn master at epoch {e} (deferred)"
    assert not mismatches, mismatches
    # sanity: the hammer actually overlapped multiple rounds
    assert len(seen_epochs) >= 2, seen_epochs


def test_state_dict_roundtrip(tiny_cfg):
    _, _, opt = run_diloco_single(
        tiny_cfg, 6, local_steps=4, outer_lr=0.7, momentum=0.9
    )
    sd = opt.state_dict()
    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(9))
    world = LoopbackWorld(1)
    (backend,) = world.make_backends()
    opt2 = DiLoCoOptimizer(
        trainer, backend, DilocoConfig(local_steps=4, backend="loopback"), state, 8
    )
    opt2.load_state_dict(sd)
    assert opt2.epoch == opt.epoch and opt2.local_step == opt.local_step
    assert opt2.samples_in_epoch == opt.samples_in_epoch == 2 * 8
    for a, b in zip(opt2.master, opt.master):
        np.testing.assert_array_equal(a, b)
    # legacy checkpoints (no samples_in_epoch key) reconstruct mid-epoch
    # progress from local_step so boundary reports don't under-count
    legacy = {k: v for k, v in sd.items() if k != "samples_in_epoch"}
    opt2.load_state_dict(legacy)
    assert opt2.samples_in_epoch == opt2.local_step * 8


def test_mid_epoch_resume_reports_full_progress(tiny_cfg):
    """Resume from a mid-epoch checkpoint (ckpt interval not a multiple of
    local_steps): the boundary progress report must count the pre-resume
    samples, or peers' WAIT_FOR_ALL stalls until timeout."""
    _, _, opt = run_diloco_single(
        tiny_cfg, 6, local_steps=4, outer_lr=0.7, momentum=0.9
    )
    sd = opt.state_dict()  # epoch 1, local_step 2 -> mid-epoch

    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(9))
    world = LoopbackWorld(1)
    (backend,) = world.make_backends()
    opt2 = DiLoCoOptimizer(
        trainer, backend, DilocoConfig(local_steps=4, backend="loopback"), state, 8
    )
    opt2.load_state_dict(sd)
    for ids, labels in batches(5, tiny_cfg.vocab_size, 2):
        state, m = opt2.step(state, trainer.shard_batch(ids, labels, accum=1))
    assert opt2.epoch == 2  # boundary reached after only 2 post-resume steps
    reported = world.progress[backend.peer_id]
    assert reported.samples == 4 * 8  # full epoch, not just 2*8


def test_peer_drop_elastic(tiny_cfg):
    """A worker that closes stops blocking the group; survivors complete
    with a smaller group and drop detection fires (train_fsdp.py:452-457)."""
    world = LoopbackWorld(2)
    b0, b1 = world.make_backends()

    # round 1: both contribute
    import numpy as np

    def peer1():
        b1.all_reduce([np.full(4, 2.0, np.float32)], timeout=30)
        b1.close()  # drop out after round 1

    t = threading.Thread(target=peer1)
    t.start()
    out, group = b0.all_reduce([np.zeros(4, np.float32)], timeout=30)
    assert group == 2
    np.testing.assert_allclose(out[0], 1.0)
    t.join(timeout=30)

    # round 2: survivor alone completes immediately with group 1
    out, group = b0.all_reduce([np.full(4, 3.0, np.float32)], timeout=5)
    assert group == 1
    np.testing.assert_allclose(out[0], 3.0)
    assert b0.num_peers() == 1


def test_fail_rank_drop_raises(tiny_cfg):
    from opendiloco_tpu.diloco import PeerDropError

    world = LoopbackWorld(2)
    b0, b1 = world.make_backends()
    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(7))
    cfg = DilocoConfig(
        local_steps=2,
        backend="loopback",
        fail_rank_drop=True,
        all_reduce_strategy="no_wait",
        averaging_timeout=30.0,
    )
    opt = DiLoCoOptimizer(trainer, b0, cfg, state, batch_size=8)

    def peer1_one_round():
        pieces = opt._pieces(None)
        for k, piece in enumerate(pieces):
            b1.all_reduce(
                [np.zeros_like(opt.master[j]) for j in piece],
                tag=_piece_tag(k, len(pieces)), epoch=0, timeout=30,
            )
        b1.close()

    t = threading.Thread(target=peer1_one_round)
    t.start()
    data = list(batches(5, tiny_cfg.vocab_size, 4))
    for ids, labels in data[:2]:
        state, m = opt.step(state, trainer.shard_batch(ids, labels, accum=1))
    t.join(timeout=30)
    assert opt.max_num_peers == 2
    with pytest.raises(PeerDropError):
        for ids, labels in data[2:]:
            state, m = opt.step(state, trainer.shard_batch(ids, labels, accum=1))


class _FakeProgressBackend:
    """Scripted backend for deterministic straggler-policy tests (the
    reference's equivalent test is skipped as flaky,
    test_diloco_hivemind.py:154-156)."""

    peer_id = "me"

    def __init__(self, script):
        self.script = script  # list of progress snapshots, popped per poll
        self.polls = 0

    def peer_progress(self):
        self.polls += 1
        snap = self.script[min(self.polls - 1, len(self.script) - 1)]
        return snap


def test_wait_for_all_waits_until_peer_catches_up():
    import time as _time

    from opendiloco_tpu.diloco.backend import PeerProgress, wait_for_peers

    behind = [PeerProgress("slow", 0, 10, samples_per_second=100.0, timestamp=0)]
    done = [PeerProgress("slow", 0, 100, samples_per_second=100.0, timestamp=0)]
    backend = _FakeProgressBackend([behind] * 3 + [done])
    t0 = _time.monotonic()
    wait_for_peers(
        backend,
        target_samples=100,
        own_epoch=0,
        strategy="wait_for_all",
        timeout_waiting_for_peers=30.0,
    )
    assert backend.polls >= 4  # polled until the peer caught up
    assert _time.monotonic() - t0 < 5.0


def test_no_wait_returns_immediately():
    from opendiloco_tpu.diloco.backend import PeerProgress, wait_for_peers

    behind = [PeerProgress("slow", 0, 0, samples_per_second=0.0, timestamp=0)]
    backend = _FakeProgressBackend([behind])
    wait_for_peers(
        backend,
        target_samples=100,
        own_epoch=0,
        strategy="no_wait",
        timeout_waiting_for_peers=30.0,
    )
    assert backend.polls == 0


def test_wait_for_all_times_out_and_proceeds():
    import time as _time

    from opendiloco_tpu.diloco.backend import PeerProgress, wait_for_peers

    stuck = [PeerProgress("dead", 0, 0, samples_per_second=0.0, timestamp=0)]
    backend = _FakeProgressBackend([stuck])
    t0 = _time.monotonic()
    wait_for_peers(
        backend,
        target_samples=100,
        own_epoch=0,
        strategy="wait_for_all",
        timeout_waiting_for_peers=1.0,
    )
    dt = _time.monotonic() - t0
    assert 0.9 <= dt < 3.0  # gave up at the timeout, did not hang


def test_no_piece_is_reduced_before_the_straggler_wait_returns(tiny_cfg, monkeypatch):
    """Device placement: the fetch starts before ``wait_for_peers`` and
    overlaps it, but no piece's all-reduce is entered until it has returned,
    however early the first pieces are in hand."""
    from opendiloco_tpu.diloco import optimizer as optimizer_mod
    from opendiloco_tpu.diloco.backend import PeerProgress

    world = LoopbackWorld(2)
    mine, slow = world.make_backends()
    entered, returned = [], []
    real_all_reduce, real_wait = mine.all_reduce, optimizer_mod.wait_for_peers

    def all_reduce(arrays, **kw):
        entered.append((kw["tag"], time.monotonic()))
        return real_all_reduce(arrays, **kw)

    at_the_wait = threading.Event()

    def wait_for_peers(*a, **kw):
        at_the_wait.set()
        real_wait(*a, **kw)
        returned.append(time.monotonic())

    mine.all_reduce = all_reduce
    monkeypatch.setattr(optimizer_mod, "wait_for_peers", wait_for_peers)
    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(7))
    opt = DiLoCoOptimizer(
        trainer, mine,
        DilocoConfig(local_steps=1, backend="loopback", outer_placement="device",
                     skip_load_from_peers=True, timeout_waiting_for_peers=30.0,
                     averaging_timeout=30.0),
        state, batch_size=8,
    )
    pieces = opt._pieces(None)
    shapes = [m.shape for m in opt._plane.masters]

    def slow_peer():
        # behind for a while, then at the boundary and into every piece's round
        assert at_the_wait.wait(60.0)
        time.sleep(0.4)
        slow.report_progress(PeerProgress(slow.peer_id, 0, 8, 1.0, time.time()))
        for k, piece in enumerate(pieces):
            slow.all_reduce([np.zeros(shapes[j], np.float32) for j in piece],
                            tag=_piece_tag(k, len(pieces)), epoch=0, timeout=30.0)

    slow.report_progress(PeerProgress(slow.peer_id, 0, 0, 1.0, time.time()))
    peer = threading.Thread(target=slow_peer)
    peer.start()
    (ids, labels), = batches(0, tiny_cfg.vocab_size, 1)
    state, row = opt.step(state, trainer.shard_batch(ids, labels, accum=1))
    peer.join(timeout=30.0)
    assert not peer.is_alive()
    assert row["num_peers"] == 2 and row["outer_pieces"] == len(pieces) > 2
    assert row["outer_wait_s"] >= 0.3  # it did wait for the peer
    assert [t for t, _ in entered] == [f"grads-p{k}" for k in range(len(pieces))]
    assert min(when for _, when in entered) >= returned[0]


class _InRankOrder:
    """A loopback backend that contributes to a round only once every lower
    rank has: the mean's sum runs in arrival order, so pinned arrivals make
    a galaxy's bits repeatable."""

    def __init__(self, backend, rank):
        self._b, self._rank = backend, rank

    def __getattr__(self, name):
        return getattr(self._b, name)

    def all_reduce(self, arrays, *, tag="grads", epoch=None, **kw):
        world, key = self._b.world, f"{tag}-epoch-{epoch}"
        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            with world.lock:
                arrived = len(world._rounds.get(key, {}).get("contrib", {}))
            if arrived >= self._rank:
                break
            time.sleep(0.0005)
        return self._b.all_reduce(arrays, tag=tag, epoch=epoch, **kw)


@pytest.mark.parametrize("frags", [0, 2], ids=["whole-model", "fragments"])
def test_three_peers_pipelined_rounds_leave_the_one_piece_rounds_bits(
    tiny_cfg, frags, monkeypatch
):
    """Three workers on their own data over three rounds with momentum: each
    one's parameters, masters and momentum are bit for bit those of the same
    galaxy held to one piece a round."""
    from opendiloco_tpu.diloco import outer_device

    trainers = [make_trainer(tiny_cfg) for _ in range(3)]

    def galaxy():
        world = LoopbackWorld(3)
        backends = [_InRankOrder(b, r) for r, b in enumerate(world.make_backends())]
        results, errors = [None] * 3, []

        def worker(rank):
            try:
                trainer = trainers[rank]
                state = trainer.init_state(jax.random.key(7))
                opt = DiLoCoOptimizer(
                    trainer, backends[rank],
                    DilocoConfig(local_steps=1, backend="loopback",
                                 outer_placement="device", streaming_fragments=frags,
                                 timeout_waiting_for_peers=30.0, averaging_timeout=60.0),
                    state, batch_size=8,
                )
                pieces = []
                for ids, labels in batches(1000 + rank, tiny_cfg.vocab_size, 3):
                    state, m = opt.step(state, trainer.shard_batch(ids, labels, accum=1))
                    pieces.append(m["outer_pieces"])
                masters, bufs = opt._plane.host_state()
                results[rank] = (
                    masters + bufs + jax.device_get(jax.tree.leaves(state["params"])),
                    pieces,
                )
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(r,)) for r in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errors, errors
        assert all(r is not None for r in results)
        return results, world

    pipelined, world = galaxy()
    assert world._outputs.keep == 6
    monkeypatch.setattr(
        outer_device, "cut_pieces", lambda nbytes: [list(range(len(nbytes)))]
    )
    one_piece, _ = galaxy()
    for (got, pieces), (want, pieces_1) in zip(pipelined, one_piece):
        assert pieces_1 == [1, 1, 1] and min(pieces) > 2
        assert len(got) == len(want)
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


class _Killed(Exception):
    pass


@pytest.mark.parametrize("case", ["healthy", "a-peer-dies-between-pieces"])
def test_device_rounds_over_tcp(tiny_cfg, case, monkeypatch):
    """The boundary's pieces over the wire a deployment runs: real sockets, a
    rendezvous, a matchmaking a piece. Healthy (two workers, two rounds):
    every piece finds both, the health row says nothing happened, and each
    worker is left the bits of the same galaxy held to one piece -- which
    also says that no piece's result (a view of a buffer the backend reclaims
    by tag) was written while the way back still read it. A peer that dies
    after the first piece of a round (three workers): the survivors finish
    the round with the smaller group for the later pieces, as the elastic
    round it is, bit-equal to each other, and run the next round as two."""
    from opendiloco_tpu.diloco import outer_device
    from opendiloco_tpu.diloco.rendezvous import RendezvousServer
    from opendiloco_tpu.diloco.tcp import TcpBackend

    dies = case != "healthy"
    n = 3 if dies else 2
    trainers = [make_trainer(tiny_cfg) for _ in range(n)]

    def galaxy():
        server = RendezvousServer(host="127.0.0.1", port=0).start_in_thread()
        backends = [
            TcpBackend([server.address], peer_id=f"worker-{i}", matchmaking_time=2.0)
            for i in range(n)
        ]
        results, errors = [None] * n, []

        def worker(rank):
            backend = backends[rank]
            try:
                if dies and rank == n - 1:
                    all_reduce, calls = backend.all_reduce, []

                    def mortal(arrays, **kw):
                        if calls:  # the first piece went through
                            backend.close()
                            raise _Killed()
                        calls.append(kw["tag"])
                        return all_reduce(arrays, **kw)

                    backend.all_reduce = mortal
                trainer = trainers[rank]
                state = trainer.init_state(jax.random.key(7))
                opt = DiLoCoOptimizer(
                    trainer, backend,
                    DilocoConfig(local_steps=1, backend="tcp",
                                 outer_placement="device", skip_load_from_peers=True,
                                 timeout_waiting_for_peers=30.0, averaging_timeout=30.0),
                    state, batch_size=8,
                )
                rows = []
                for ids, labels in batches(1000 + rank, tiny_cfg.vocab_size, 2):
                    state, m = opt.step(state, trainer.shard_batch(ids, labels, accum=1))
                    rows.append(m)
                masters, bufs = opt._plane.host_state()
                results[rank] = (
                    masters + bufs + jax.device_get(jax.tree.leaves(state["params"])),
                    rows, list(backend.round_ledger), opt.epoch,
                )
            except _Killed:
                results[rank] = "killed"
            except Exception as e:  # pragma: no cover
                errors.append(e)
            finally:
                backend.close()

        threads = [threading.Thread(target=worker, args=(r,)) for r in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        server.stop()
        assert not errors, errors
        assert all(r is not None for r in results)
        return results

    cut = galaxy()
    n_pieces = cut[0][1][0]["outer_pieces"]
    assert n_pieces > 2
    tags = [_piece_tag(k, n_pieces) for k in range(n_pieces)]
    if dies:
        assert cut[-1] == "killed"
        (bits_a, rows_a, ledger_a, epoch_a), (bits_b, rows_b, _, epoch_b) = cut[:2]
        assert epoch_a == epoch_b == 2
        for rows in (rows_a, rows_b):
            assert [r["num_peers"] for r in rows] == [2, 2]
            assert rows[0]["elastic"] is True and rows[0]["expected_peers"] == 3
        # the round the peer died in: three for the first piece, two after
        assert [h["group_size"] for h in ledger_a[:n_pieces]] == [3] + [2] * (n_pieces - 1)
        for a, b in zip(bits_a, bits_b):
            assert a.tobytes() == b.tobytes()
        return
    for _, rows, ledger, epoch in cut:
        assert epoch == 2
        assert [r["num_peers"] for r in rows] == [2, 2]
        assert not any(r.get("elastic") or r.get("round_retries") for r in rows)
        # a matchmaking a piece, each under the piece's own tag
        assert [h["round"] for h in ledger] == [
            f"{t}-epoch-{e}" for e in range(2) for t in tags
        ]
    monkeypatch.setattr(
        outer_device, "cut_pieces", lambda nbytes: [list(range(len(nbytes)))]
    )
    whole = galaxy()
    for (got, _, _, _), (want, rows, ledger, _) in zip(cut, whole):
        assert [r["outer_pieces"] for r in rows] == [1, 1]
        assert [h["round"] for h in ledger] == ["grads-epoch-0", "grads-epoch-1"]
        for a, b in zip(got, want):
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


def test_hash_pytree_and_schema():
    from opendiloco_tpu.utils.debug import hash_pytree, schema_fingerprint

    t1 = {"a": np.arange(4, dtype=np.float32), "b": [np.ones(2)]}
    t2 = {"a": np.arange(4, dtype=np.float32), "b": [np.ones(2)]}
    t3 = {"a": np.arange(4, dtype=np.float32) + 1, "b": [np.ones(2)]}
    assert hash_pytree(t1) == hash_pytree(t2)
    assert hash_pytree(t1) != hash_pytree(t3)
    # schema ignores values but not shapes
    assert schema_fingerprint(t1) == schema_fingerprint(t3)
    t4 = {"a": np.arange(5, dtype=np.float32), "b": [np.ones(2)]}
    assert schema_fingerprint(t1) != schema_fingerprint(t4)


def test_desync_recovery(tiny_cfg):
    """A worker 2+ epochs behind the swarm re-downloads state instead of
    training a stale epoch (hivemind_diloco.py:528-531 parity)."""
    from opendiloco_tpu.diloco.backend import PeerProgress

    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(7))
    world = LoopbackWorld(1)
    (backend,) = world.make_backends()
    cfg = DilocoConfig(local_steps=4, backend="loopback")
    opt = DiLoCoOptimizer(trainer, backend, cfg, state, batch_size=8)

    # fabricate an advanced peer: serves state at epoch 5 and gossips it
    advanced_master = [m + 1.0 for m in opt.master]
    world.state_provider = lambda: {
        "master": advanced_master,
        "epoch": 5,
        "outer_opt": opt.outer_opt.state_dict(),
    }
    world.progress["ghost"] = PeerProgress("ghost", epoch=5, samples=0,
                                           samples_per_second=1.0, timestamp=0)
    world.live.add("ghost")

    ids, labels = next(batches(0, tiny_cfg.vocab_size, 1))
    state, m = opt.step(state, trainer.shard_batch(ids, labels, accum=1))
    assert opt.epoch == 5  # adopted the swarm epoch
    for a, b in zip(opt.master, advanced_master):
        np.testing.assert_array_equal(a, b)
    # LR-schedule position teleported to the swarm's inner step (not warmup):
    # 5 epochs * 4 local steps, plus the one step just taken
    assert int(jax.device_get(state["step"])) == 5 * cfg.local_steps + 1
    # and the jit cache stayed warm through force_step_position
    ids, labels = next(batches(1, tiny_cfg.vocab_size, 1))
    state, _ = opt.step(state, trainer.shard_batch(ids, labels, accum=1))
    assert trainer._train_step._cache_size() == 1


def test_blocking_outer_step_drains_abandoned_round(tiny_cfg):
    """The blocking path writes slot-0 pseudo-grad buffers; an abandoned
    overlapped round (desync re-onboard -> drop_pending) may still be
    streaming from them, so outer_step must drain it first — and surrender
    the buffers if it is wedged — before putting bytes on the wire."""
    import concurrent.futures as cf
    from types import SimpleNamespace

    # unit: a finished abandoned round is cleared, buffers kept
    stub = SimpleNamespace(
        _abandoned=None,
        _pg_bufs=[["slot0"], ["slot1"]],
        cfg=SimpleNamespace(averaging_timeout=-59.8),  # drain deadline ~0.2s
    )
    fut: cf.Future = cf.Future()
    fut.set_result(([np.zeros(1)], 1))
    stub._abandoned = fut
    DiLoCoOptimizer._drain_abandoned(stub)
    assert stub._abandoned is None
    assert stub._pg_bufs == [["slot0"], ["slot1"]]

    # unit: a wedged round (never resolves) surrenders BOTH slots
    stub._abandoned = cf.Future()
    DiLoCoOptimizer._drain_abandoned(stub)
    assert stub._abandoned is None
    assert stub._pg_bufs == [None, None]

    # integration: the blocking outer path drains before writing slot 0
    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(7))
    world = LoopbackWorld(1)
    (backend,) = world.make_backends()
    cfg = DilocoConfig(local_steps=2, backend="loopback", overlap_comm="none")
    opt = DiLoCoOptimizer(trainer, backend, cfg, state, batch_size=8)
    done: cf.Future = cf.Future()
    done.set_result(([np.zeros(1)], 1))
    opt._abandoned = done
    for ids, labels in batches(0, tiny_cfg.vocab_size, 2):
        state, _ = opt.step(state, trainer.shard_batch(ids, labels, accum=1))
    assert opt.epoch == 1
    assert opt._abandoned is None


def test_onboarding_fetch_copies_outside_serve_lock(tiny_cfg):
    """ADVICE r3: _state_for_peers must not hold the serve lock during the
    model-sized copies — a peer's fetch would otherwise block the training
    thread's round-boundary publication for seconds at 1b scale."""
    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(7))
    world = LoopbackWorld(1)
    (backend,) = world.make_backends()
    cfg = DilocoConfig(local_steps=4, backend="loopback")
    opt = DiLoCoOptimizer(trainer, backend, cfg, state, batch_size=8)

    lock_at_refs = []
    lock_at_copy = []

    class SpyList(list):
        # _state_for_peers copies via `[m.copy() for m in master]`: record
        # whether the serve lock is held at the moment the copies iterate
        def __iter__(self):
            lock_at_copy.append(opt._serve_lock.locked())
            return super().__iter__()

    real_refs = DiLoCoOptimizer._state_refs_unlocked

    def spying_refs(self):
        master, epoch, opt_sd = real_refs(self)
        lock_at_refs.append(opt._serve_lock.locked())
        return SpyList(master), epoch, opt_sd

    opt._state_refs_unlocked = spying_refs.__get__(opt)
    got = opt._state_for_peers()
    # refs are captured under the lock; the copies run after it is released
    assert lock_at_refs == [True]
    assert lock_at_copy and not any(lock_at_copy)
    assert not opt._serve_lock.locked()
    assert got["epoch"] == 0
    assert len(got["master"]) == len(opt.master)
    # served arrays are copies, not aliases of the live master
    assert not any(
        g is m or np.shares_memory(g, m)
        for g, m in zip(got["master"], opt.master)
    )


def test_no_recompilation_across_outer_step(tiny_cfg):
    """SURVEY hard-part 3: the inner jit step must not recompile after the
    outer step rewrites params (same shapes/shardings/donation)."""
    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(7))
    world = LoopbackWorld(1)
    (backend,) = world.make_backends()
    opt = DiLoCoOptimizer(
        trainer, backend, DilocoConfig(local_steps=2, backend="loopback"), state, 8
    )
    data = list(batches(3, tiny_cfg.vocab_size, 5))
    for ids, labels in data[:2]:
        state, _ = opt.step(state, trainer.shard_batch(ids, labels, accum=1))
    assert opt.epoch == 1  # outer step happened
    n_compiles = trainer._train_step._cache_size()
    for ids, labels in data[2:]:
        state, _ = opt.step(state, trainer.shard_batch(ids, labels, accum=1))
    assert trainer._train_step._cache_size() == n_compiles == 1


# ---------------------------------------------------------------------------
# overlapped outer communication (arxiv 2502.12996)
# ---------------------------------------------------------------------------


def run_diloco_overlap(tiny_cfg, n_steps, mode, outer_lr=1.0, momentum=0.0,
                       backend=None, world=None):
    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(7))
    if backend is None:
        world = LoopbackWorld(1)
        (backend,) = world.make_backends()
    cfg = DilocoConfig(
        outer_lr=outer_lr,
        outer_momentum=momentum,
        outer_nesterov=False,
        local_steps=4,
        backend="loopback",
        overlap_comm=mode,
    )
    opt = DiLoCoOptimizer(trainer, backend, cfg, state, batch_size=8)
    losses = []
    for ids, labels in batches(0, tiny_cfg.vocab_size, n_steps):
        batch = trainer.shard_batch(ids, labels, accum=1)
        state, m = opt.step(state, batch)
        losses.append(float(m["loss"]))
    state = opt.flush(state)
    return np.array(losses), jax.device_get(state["params"]), opt


@pytest.mark.parametrize("mode", ["delayed", "eager"])
def test_overlap_identity_equals_plain_training(tiny_cfg, mode):
    """Single worker, outer_lr=1, momentum=0: the outer update is exactly
    the boundary rewrite theta_b -> theta_b, so both overlap modes must
    reproduce plain training bit-for-bit (the delta and the correction are
    both exactly zero)."""
    ref_losses, ref_params = run_plain(tiny_cfg, 8)
    got_losses, got_params, opt = run_diloco_overlap(tiny_cfg, 8, mode)
    assert opt.epoch == 2
    np.testing.assert_allclose(got_losses, ref_losses, rtol=1e-5, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6),
        got_params,
        ref_params,
    )


@pytest.mark.parametrize("mode", ["delayed", "eager"])
def test_overlap_two_workers_masters_converge(tiny_cfg, mode):
    """Two overlapped workers end (after flush) with identical masters."""
    world = LoopbackWorld(2)
    backends = world.make_backends()
    results = [None] * 2
    errors = []

    def worker(rank):
        try:
            trainer = make_trainer(tiny_cfg)
            state = trainer.init_state(jax.random.key(7))
            cfg = DilocoConfig(
                local_steps=4,
                outer_nesterov=True,
                backend="loopback",
                overlap_comm=mode,
                timeout_waiting_for_peers=30.0,
                averaging_timeout=60.0,
            )
            opt = DiLoCoOptimizer(trainer, backends[rank], cfg, state, batch_size=8)
            for ids, labels in batches(1000 + rank, tiny_cfg.vocab_size, 8):
                batch = trainer.shard_batch(ids, labels, accum=1)
                state, m = opt.step(state, batch)
            state = opt.flush(state)
            results[rank] = [m.copy() for m in opt.master]
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(r,)) for r in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert all(r is not None for r in results)
    for a, b in zip(results[0], results[1]):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
        assert np.all(np.isfinite(a))


def test_overlap_inner_steps_continue_during_comm(tiny_cfg):
    """With a slow all-reduce, the boundary step returns immediately and
    inner training continues while communication is in flight."""
    import time as _time

    world = LoopbackWorld(1)
    (backend,) = world.make_backends()
    orig = backend.all_reduce

    def slow_all_reduce(arrays, **kw):
        _time.sleep(1.0)
        return orig(arrays, **kw)

    backend.all_reduce = slow_all_reduce
    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(7))
    cfg = DilocoConfig(
        local_steps=2, backend="loopback", overlap_comm="delayed",
        outer_lr=0.7, outer_momentum=0.9,
    )
    opt = DiLoCoOptimizer(trainer, backend, cfg, state, batch_size=8)
    data = list(batches(2, tiny_cfg.vocab_size, 4))

    for ids, labels in data[:2]:
        state, m = opt.step(state, trainer.shard_batch(ids, labels, accum=1))
    assert m.get("outer_overlapped") == 1
    assert opt._pending is not None  # comm still in flight (1s sleep)
    t0 = _time.monotonic()
    state, _ = opt.step(state, trainer.shard_batch(*data[2], accum=1))
    assert _time.monotonic() - t0 < 0.9  # did not block on the slow comm
    state = opt.flush(state)
    assert opt._pending is None
    # the flushed master reflects the outer update (lr != 1 -> master moved)
    ref = jax.device_get(trainer.init_state(jax.random.key(7))["params"])
    moved = any(
        not np.allclose(a, b)
        for a, b in zip(opt.master, [np.asarray(x) for x in jax.tree.leaves(ref)])
    )
    assert moved


# ---------------------------------------------------------------------------
# gossip outer mode (NoLoCo-style, arxiv 2506.10911)
# ---------------------------------------------------------------------------


def run_gossip_workers(tiny_cfg, n_workers, n_steps, local_steps=4):
    world = LoopbackWorld(n_workers)
    backends = world.make_backends()
    results = [None] * n_workers
    errors = []

    def worker(rank):
        try:
            trainer = make_trainer(tiny_cfg)
            state = trainer.init_state(jax.random.key(7))
            cfg = DilocoConfig(
                local_steps=local_steps,
                outer_nesterov=True,
                backend="loopback",
                outer_mode="gossip",
                timeout_waiting_for_peers=30.0,
                averaging_timeout=60.0,
            )
            opt = DiLoCoOptimizer(trainer, backends[rank], cfg, state, batch_size=8)
            for ids, labels in batches(1000 + rank, tiny_cfg.vocab_size, n_steps):
                batch = trainer.shard_batch(ids, labels, accum=1)
                state, m = opt.step(state, batch)
                assert np.isfinite(float(m["loss"]))
            results[rank] = ([mm.copy() for mm in opt.master], opt)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(r,)) for r in range(n_workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert all(r is not None for r in results)
    return results


def test_gossip_two_workers_pair_is_full_sync(tiny_cfg):
    """With exactly two workers, each epoch's pair IS the whole swarm, so
    gossip keeps the masters identical across workers (state mixing)."""
    results = run_gossip_workers(tiny_cfg, 2, n_steps=8)
    (m0, opt0), (m1, opt1) = results
    assert opt0.epoch == opt1.epoch == 2
    for a, b in zip(m0, m1):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_gossip_four_workers_mix_and_learn(tiny_cfg):
    """Four workers, pairwise rounds only: everyone finishes, every round
    is a pair (never a global barrier), and state mixing keeps masters
    finite and in the same neighborhood."""
    results = run_gossip_workers(tiny_cfg, 4, n_steps=8)
    masters = [m for m, _ in results]
    for m, opt in results:
        assert opt.epoch == 2
        assert opt.last_outer_metrics["num_peers"] <= 2  # pair rounds only
        assert all(np.all(np.isfinite(x)) for x in m)
    # mixing bound: max pairwise master distance is small relative to scale
    flat = [np.concatenate([x.ravel() for x in m]) for m in masters]
    scale = max(np.abs(f).max() for f in flat)
    spread = max(
        np.abs(a - b).max() for i, a in enumerate(flat) for b in flat[i + 1:]
    )
    assert spread < 0.5 * scale


def test_optimizer_announces_progress_at_construction(tiny_cfg):
    """A worker must be visible to peers' WAIT_FOR_ALL polling from the
    moment its optimizer exists — NOT only after its first train_step
    returns. Before the join-time announce, a worker still inside its
    first (slow) XLA compile was invisible to a faster peer, which then
    read "no other peers known" and matchmade a solo outer group
    (observed live: two staggered 150m workers each all-reduced over 1
    peer). The reference's progress tracker reports from construction
    (hivemind_diloco.py:174-282)."""
    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(7))
    world = LoopbackWorld(2)
    backends = world.make_backends()
    DiLoCoOptimizer(
        trainer,
        backends[0],
        DilocoConfig(local_steps=4, backend="loopback"),
        state,
        batch_size=8,
    )
    # worker-1 has constructed no optimizer and taken no step: it must
    # already see worker-0 at epoch 0 through the progress gossip
    seen = {p.peer_id: p for p in backends[1].peer_progress()}
    assert backends[0].peer_id in seen
    assert seen[backends[0].peer_id].epoch == 0
    assert seen[backends[0].peer_id].samples == 0


def test_join_keepalive_reannounces_until_first_step(tiny_cfg, monkeypatch):
    """One announce at construction is not enough: the rendezvous TTL (60s)
    would reap a worker whose first XLA compile is silent for minutes. A
    background thread must keep re-announcing until the first step lands."""
    import opendiloco_tpu.diloco.optimizer as opt_mod

    monkeypatch.setattr(opt_mod, "_ANNOUNCE_INTERVAL_S", 0.05)
    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(7))
    world = LoopbackWorld(1)
    (backend,) = world.make_backends()
    reports = []
    orig = backend.report_progress
    backend.report_progress = lambda p: (reports.append(p), orig(p))
    opt = DiLoCoOptimizer(
        trainer,
        backend,
        DilocoConfig(local_steps=4, backend="loopback"),
        state,
        batch_size=8,
    )
    time.sleep(0.4)
    assert len(reports) >= 3, "keepalive must re-announce during the compile"
    # keepalive announces the JOIN epoch even after onboarding teleports
    # self.epoch (a compiling joiner must stay behind wait_for_peers'
    # >=2-epoch discount, not stall the swarm with an inf-ETA row at the
    # swarm's own epoch)
    opt.epoch = 50
    n_before = len(reports)
    time.sleep(0.3)
    assert len(reports) > n_before
    assert all(p.epoch == 0 for p in reports[n_before:]), (
        "keepalive must pin the join epoch, not track self.epoch"
    )
    opt.epoch = 0
    # the first step stops the keepalive
    ids, labels = next(batches(0, tiny_cfg.vocab_size, 1))
    state, _ = opt.step(state, trainer.shard_batch(ids, labels, accum=1))
    time.sleep(0.2)
    n = len(reports)
    time.sleep(0.3)
    assert len(reports) == n, "keepalive must stop after the first step"


def test_wait_for_peers_ignores_far_behind_joiners():
    """A fresh joiner announcing epoch 0 (sps 0 -> eta inf) must NOT stall
    an established swarm's boundary: peers >=2 epochs behind will desync-
    onboard anyway (optimizer._desynced), so waiting on them buys nothing."""
    from opendiloco_tpu.diloco.backend import PeerProgress, wait_for_peers

    class StubBackend:
        peer_id = "me"

        def peer_progress(self):
            return [
                PeerProgress("me", epoch=50, samples=64, samples_per_second=10.0, timestamp=time.time()),
                PeerProgress("joiner", epoch=0, samples=0, samples_per_second=0.0, timestamp=time.time()),
            ]

    t0 = time.monotonic()
    wait_for_peers(
        StubBackend(),
        target_samples=64,
        own_epoch=50,
        strategy="wait_for_all",
        timeout_waiting_for_peers=5.0,
        log=None,
    )
    assert time.monotonic() - t0 < 1.0, "must return without waiting on the epoch-0 joiner"

    # a peer ONE epoch behind (normal near boundaries) still holds the
    # round (slow enough that the ETA fast-path doesn't fire)
    class StubBehind(StubBackend):
        def peer_progress(self):
            return [
                PeerProgress("me", epoch=50, samples=64, samples_per_second=10.0, timestamp=time.time()),
                PeerProgress("lag", epoch=49, samples=32, samples_per_second=1.0, timestamp=time.time()),
            ]

    t0 = time.monotonic()
    wait_for_peers(
        StubBehind(),
        target_samples=64,
        own_epoch=50,
        strategy="wait_for_all",
        timeout_waiting_for_peers=0.5,
        log=None,
    )
    assert time.monotonic() - t0 >= 0.5, "one-epoch-behind peers must still be waited for"
