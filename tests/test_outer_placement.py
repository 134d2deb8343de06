"""Host/device outer-placement parity suite (diloco/outer_device.py).

The device-resident outer plane must be a pure placement change: for every
composition (blocking, delayed/eager overlap, fp16 wire, streaming
fragments, state averaging) the masters, momentum, epochs, and losses of an
``outer_placement=device`` run match the host-placement reference. Lossless
configs are held to rtol 1e-6 (the only divergence is XLA fusing the
Nesterov mul+add into an FMA, ~1 f32 ulp per round); the fp16 wire config
gets a wire-quantum tolerance because a 1-ulp upstream difference can flip
an f16 rounding and legitimately moves the result by one wire quantum
(2^-11 relative).

Runs on the CPU backend: placement resolution is forced with
``outer_placement="device"`` (auto picks host off-TPU, which the resolution
tests pin down).
"""

import threading
import time

import jax
import numpy as np
import pytest

from opendiloco_tpu.config import DilocoConfig
from opendiloco_tpu.diloco import DiLoCoOptimizer, LoopbackWorld
from opendiloco_tpu.diloco.compression import device_wire_dtype
from opendiloco_tpu.diloco.outer_device import DeviceOuterPlane
from opendiloco_tpu.diloco.outer_optimizer import OuterSGD
from opendiloco_tpu.parallel.mesh import build_mesh
from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

_next_dev = iter(range(10**9))


def make_trainer(tiny_cfg, devices=None, strategy="NO_SHARD"):
    tc = TrainerConfig(
        lr=1e-3, warmup_steps=2, total_steps=200, precision="fp32", remat=False
    )
    if devices is None:
        # one distinct single-device mesh per trainer (threaded workers on
        # the CPU client deadlock on concurrent multi-device executions)
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


def _wait_inflight(opt):
    """Pin the overlapped landing schedule. The spawned all-reduce thread
    races the next step's non-blocking poll, so WHICH step lands a round is
    timing-dependent (in both placements); parity needs the same landing
    schedule on both sides, so the harness drains the round before the
    next step."""
    p = opt._pending
    if p is not None and p.get("future") is not None:
        while not p["future"].done():
            time.sleep(0.001)


def run_single(
    tiny_cfg,
    placement,
    *,
    n_steps=9,
    local_steps=3,
    overlap="none",
    compression="none",
    frags=0,
    avg_every=0,
):
    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(7))
    world = LoopbackWorld(1, compression=compression)
    (backend,) = world.make_backends()
    cfg = DilocoConfig(
        local_steps=local_steps,
        backend="loopback",
        outer_placement=placement,
        overlap_comm=overlap,
        compression=compression,
        streaming_fragments=frags,
        average_state_every=avg_every,
    )
    opt = DiLoCoOptimizer(trainer, backend, cfg, state, batch_size=8)
    losses = []
    for ids, labels in batches(0, tiny_cfg.vocab_size, n_steps):
        b = trainer.shard_batch(ids, labels, accum=1)
        state, m = opt.step(state, b)
        losses.append(float(m["loss"]))
        _wait_inflight(opt)
    state = opt.flush(state)
    return losses, state, opt


# ---------------------------------------------------------------------------
# placement resolution
# ---------------------------------------------------------------------------


def test_auto_resolves_host_off_tpu(tiny_cfg):
    _, _, opt = _make_opt(tiny_cfg, outer_placement="auto")
    assert opt.placement == "host"
    assert opt._plane is None


def test_explicit_device_resolves_device_on_cpu(tiny_cfg):
    _, _, opt = _make_opt(tiny_cfg, outer_placement="device")
    assert opt.placement == "device"
    assert opt._plane is not None
    assert opt.master == []  # no host mirror in device mode


def test_gossip_honors_device_placement(tiny_cfg):
    # gossip composes with the device plane now: pair rounds fetch only
    # their fragment (host_frag) and land through gossip_land
    _, _, opt = _make_opt(
        tiny_cfg, outer_placement="device", outer_mode="gossip"
    )
    assert opt.placement == "device"
    assert opt._plane is not None
    assert opt._gossip is not None


def _make_opt(tiny_cfg, **cfg_kw):
    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(7))
    world = LoopbackWorld(1)
    (backend,) = world.make_backends()
    cfg = DilocoConfig(local_steps=3, backend="loopback", **cfg_kw)
    opt = DiLoCoOptimizer(trainer, backend, cfg, state, batch_size=8)
    return trainer, state, opt


# ---------------------------------------------------------------------------
# single-worker parity across every composition
# ---------------------------------------------------------------------------

_PARITY_CONFIGS = [
    pytest.param(dict(), id="blocking"),
    pytest.param(dict(overlap="delayed"), id="overlap-delayed"),
    pytest.param(dict(overlap="eager"), id="overlap-eager"),
    pytest.param(dict(compression="fp16"), id="fp16-wire"),
    pytest.param(dict(frags=3), id="streaming-fragments"),
    pytest.param(dict(avg_every=2), id="state-averaging"),
]


@pytest.mark.parametrize("kw", _PARITY_CONFIGS)
def test_placement_parity(tiny_cfg, kw):
    lossy = kw.get("compression") == "fp16"
    # lossless: XLA's FMA fusion of the Nesterov mul+add is the only
    # divergence, one f32 ulp of a MASTER a round. The masters here are O(1)
    # (norm weights start at 1.0), so that ulp is 1.2e-7 in absolute terms,
    # and the momentum -- a difference of masters, 1e-3 and smaller --
    # inherits it as an absolute error whatever its own size: rtol alone
    # cannot cover an element near zero. atol leaves four such ulps for the
    # three rounds run (1e-7 sat under a single one and failed every time).
    # fp16 wire: a 1-ulp upstream diff can flip an f16 rounding, so the
    # meaningful bound is the wire quantum.
    rt, at = (2e-3, 1e-5) if lossy else (1e-6, 5e-7)
    lh, _, oh = run_single(tiny_cfg, "host", **kw)
    ld, _, od = run_single(tiny_cfg, "device", **kw)
    assert oh.placement == "host" and od.placement == "device"
    np.testing.assert_allclose(lh, ld, rtol=1e-4 if lossy else 1e-5, atol=1e-6)
    sh, sd = oh.state_dict(), od.state_dict()
    assert sh["epoch"] == sd["epoch"]
    for a, b in zip(sh["master"], sd["master"]):
        np.testing.assert_allclose(a, b, rtol=rt, atol=at)
    bh, bd = sh["outer_opt"]["bufs"], sd["outer_opt"]["bufs"]
    assert (bh is None) == (bd is None)
    if bh is not None:
        for a, b in zip(bh, bd):
            np.testing.assert_allclose(a, b, rtol=rt, atol=at)


def test_multiworker_parity(tiny_cfg):
    """Two loopback workers, different data shards: the averaged outer
    trajectory must be placement-invariant."""

    def run_pair(placement):
        world = LoopbackWorld(2)
        backends = world.make_backends()
        results = [None, None]
        errors = []

        def worker(rank):
            try:
                trainer = make_trainer(tiny_cfg)
                state = trainer.init_state(jax.random.key(7))
                cfg = DilocoConfig(
                    local_steps=2,
                    backend="loopback",
                    outer_placement=placement,
                    timeout_waiting_for_peers=30.0,
                    averaging_timeout=60.0,
                )
                opt = DiLoCoOptimizer(trainer, backends[rank], cfg, state, 8)
                for ids, labels in batches(1000 + rank, tiny_cfg.vocab_size, 4):
                    state, _ = opt.step(
                        state, trainer.shard_batch(ids, labels, accum=1)
                    )
                results[rank] = opt.state_dict()
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [
            threading.Thread(target=worker, args=(r,)) for r in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errors, errors
        assert all(r is not None for r in results)
        return results

    host_sds = run_pair("host")
    dev_sds = run_pair("device")
    for sh, sd in zip(host_sds, dev_sds):
        assert sh["epoch"] == sd["epoch"]
        for a, b in zip(sh["master"], sd["master"]):
            # atol 1e-6: the inner AdamW's rsqrt amplifies the outer
            # apply's 1-ulp FMA difference a few ulps across rounds
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("frags", [0, 2], ids=["whole-model", "fragments"])
def test_a_galaxy_of_both_placements_meets_piece_for_piece(tiny_cfg, frags, monkeypatch):
    """One host worker and one device worker: every blocking round finds both
    in every piece's all-reduce, under the same tags in the same order, and
    each worker is left the bits of the same galaxy held to one piece a round
    (the wire either placement had before it was cut)."""
    from opendiloco_tpu.diloco import outer_device

    trainers = [make_trainer(tiny_cfg) for _ in range(2)]

    def galaxy():
        world = LoopbackWorld(2)
        backends = world.make_backends()
        results, tags, errors = [None, None], [[], []], []

        def worker(rank):
            try:
                trainer, backend = trainers[rank], backends[rank]
                all_reduce = backend.all_reduce

                def recorded(arrays, *, tag="grads", **kw):
                    tags[rank].append((tag, [a.shape for a in arrays]))
                    return all_reduce(arrays, tag=tag, **kw)

                backend.all_reduce = recorded
                state = trainer.init_state(jax.random.key(7))
                opt = DiLoCoOptimizer(
                    trainer, backend,
                    DilocoConfig(local_steps=1, backend="loopback",
                                 outer_placement=("host", "device")[rank],
                                 streaming_fragments=frags,
                                 timeout_waiting_for_peers=30.0, averaging_timeout=20.0),
                    state, 8,
                )
                rows = []
                for ids, labels in batches(1000 + rank, tiny_cfg.vocab_size, 3):
                    state, m = opt.step(state, trainer.shard_batch(ids, labels, accum=1))
                    rows.append(m)
                sd = opt.state_dict()
                results[rank] = (
                    list(sd["master"]) + list(sd["outer_opt"]["bufs"])
                    + jax.device_get(jax.tree.leaves(state["params"])),
                    rows,
                )
            except Exception as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(r,)) for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not errors, errors
        assert all(r is not None for r in results)
        return results, tags

    cut, tags = galaxy()
    assert tags[0] == tags[1] and len({t for t, _ in tags[0]}) > 2
    for _, rows in cut:
        assert [r["num_peers"] for r in rows] == [2, 2, 2]
        assert not any(r.get("elastic") for r in rows)
    monkeypatch.setattr(
        outer_device, "cut_pieces", lambda nbytes: [list(range(len(nbytes)))]
    )
    whole, tags_1 = galaxy()
    assert {t for t, _ in tags_1[0] + tags_1[1]} == {"grads"}
    for (got, _), (want, _) in zip(cut, whole):
        assert len(got) == len(want)
        for a, b in zip(got, want):
            a, b = np.asarray(a), np.asarray(b)
            assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


# ---------------------------------------------------------------------------
# state_dict / serve / checkpoint interop across placements
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "src,dst", [("device", "host"), ("host", "device"), ("device", "device")]
)
def test_state_dict_roundtrip_across_placements(tiny_cfg, src, dst):
    """A checkpoint written under either placement restores under either:
    the serialized format is the host-view schema for both."""
    _, _, opt = run_single(tiny_cfg, src, n_steps=6, local_steps=3)
    sd = opt.state_dict()
    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(9))
    world = LoopbackWorld(1)
    (backend,) = world.make_backends()
    opt2 = DiLoCoOptimizer(
        trainer,
        backend,
        DilocoConfig(
            local_steps=3, backend="loopback", outer_placement=dst
        ),
        state,
        8,
    )
    opt2.load_state_dict(sd)
    assert opt2.epoch == opt.epoch
    sd2 = opt2.state_dict()
    for a, b in zip(sd["master"], sd2["master"]):
        np.testing.assert_array_equal(a, b)
    bufs, bufs2 = sd["outer_opt"]["bufs"], sd2["outer_opt"]["bufs"]
    assert (bufs is None) == (bufs2 is None)
    if bufs is not None:
        for a, b in zip(bufs, bufs2):
            np.testing.assert_array_equal(a, b)
    # the restored optimizer keeps training without recompiling anything
    for ids, labels in batches(5, tiny_cfg.vocab_size, 3):
        state, m = opt2.step(state, trainer.shard_batch(ids, labels, accum=1))
        assert np.isfinite(m["loss"])
    assert opt2.epoch == opt.epoch + 1


def test_serve_state_matches_state_dict_in_device_mode(tiny_cfg):
    """The onboarding serve path (lazy host snapshot of the device plane)
    must publish the same host-schema state the checkpoint writes."""
    _, _, opt = run_single(tiny_cfg, "device", n_steps=6, local_steps=3)
    served = opt._state_for_peers()
    sd = opt.state_dict()
    assert served["epoch"] == sd["epoch"]
    for a, b in zip(served["master"], sd["master"]):
        assert isinstance(a, np.ndarray) and a.dtype == np.float32
        np.testing.assert_array_equal(a, b)
    sb, db = served["outer_opt"]["bufs"], sd["outer_opt"]["bufs"]
    assert (sb is None) == (db is None)
    if sb is not None:
        for a, b in zip(sb, db):
            np.testing.assert_array_equal(a, b)


def test_ckpt_pack_coerces_device_arrays(tiny_cfg):
    """ckpt._pack_tree serializes a tree holding live device arrays (the
    placement-portable guard): restore equals the host view bit-for-bit."""
    from opendiloco_tpu import ckpt

    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(3))
    leaves = jax.tree.leaves(state["params"])
    tree = {
        "master": [x.astype(jax.numpy.float32) for x in leaves[:2]],
        "epoch": 4,
        "outer_opt": {"lr": 0.7, "momentum": 0.9, "nesterov": True, "bufs": None},
    }
    meta, blob = ckpt._pack_tree(tree)
    restored = ckpt._unpack_tree(meta, blob)
    assert restored["epoch"] == 4
    for a, b in zip(tree["master"], restored["master"]):
        np.testing.assert_array_equal(np.asarray(a), b)


# ---------------------------------------------------------------------------
# device-plane unit behavior
# ---------------------------------------------------------------------------


def _make_plane(tiny_cfg, momentum=0.9, compression="none"):
    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(11))
    leaves = jax.tree.leaves(state["params"])
    plane = DeviceOuterPlane(
        trainer,
        leaves,
        lr=0.7,
        momentum=momentum,
        nesterov=True,
        compression=compression,
    )
    return plane, leaves


def test_plane_blocking_round_matches_outer_sgd(tiny_cfg):
    plane, leaves = _make_plane(tiny_cfg)
    host_master = [np.array(x, np.float32) for x in jax.device_get(leaves)]
    opt = OuterSGD(0.7, 0.9, nesterov=True)
    rng = np.random.default_rng(0)
    for _ in range(3):
        fake = [
            rng.normal(scale=1e-3, size=m.shape).astype(np.float32)
            for m in host_master
        ]
        opt.step(host_master, [f.copy() for f in fake])
        plane.apply_average([f.copy() for f in fake])
    got, bufs = plane.host_state()
    for a, b in zip(host_master, got):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    assert bufs is not None and len(bufs) == len(host_master)
    for a, b in zip(opt.bufs, bufs):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)


def test_plane_pseudo_grad_and_norm(tiny_cfg):
    plane, leaves = _make_plane(tiny_cfg)
    # perturb the params so the pseudo-gradient is non-zero
    moved = [x - 1e-3 for x in leaves]
    pg, norm, _ = plane.pseudo_grad(moved)
    ref = [
        np.asarray(m, np.float32) - np.asarray(p, np.float32)
        for m, p in zip(jax.device_get(plane.masters), jax.device_get(moved))
    ]
    for a, b in zip(pg, ref):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    ref_norm = float(
        np.sqrt(sum(float(np.dot(r.ravel(), r.ravel())) for r in ref))
    )
    assert norm == pytest.approx(ref_norm, rel=1e-5)


def test_plane_fp16_wire_precast(tiny_cfg):
    """With the plain fp16 codec the D2H rides the wire dtype: the host
    pseudo-gradient is exactly f16-representable (the cast happened inside
    jit), so the host encode is a no-op re-encode of the same bytes."""
    assert device_wire_dtype("fp16") == "float16"
    assert device_wire_dtype("none") is None
    assert device_wire_dtype("scaled-fp16") is None  # pre-scales on host
    assert device_wire_dtype("blockwise8bit") is None
    plane, leaves = _make_plane(tiny_cfg, compression="fp16")
    moved = [x - 1e-3 for x in leaves]
    pg, _, _ = plane.pseudo_grad(moved)
    for g in pg:
        assert g.dtype == np.float32  # widened for the backend
        np.testing.assert_array_equal(
            g, g.astype(np.float16).astype(np.float32)
        )


def test_plane_sync_params_returns_fresh_buffers(tiny_cfg):
    """sync_params output must not alias the masters: the caller binds the
    result as train-state leaves the next train_step donates."""
    plane, leaves = _make_plane(tiny_cfg)
    fresh = plane.sync_params(leaves)
    for f, m in zip(fresh, plane.masters):
        np.testing.assert_array_equal(np.asarray(f), np.asarray(m))
        assert f is not m
    # masters survive a donation of the synced leaves
    del fresh
    got, _ = plane.host_state()
    assert all(np.isfinite(x).all() for x in got)


def test_device_rounds_do_not_recompile(tiny_cfg):
    """The fragment partition is fixed at construction: after the first
    round of each shape family, later rounds hit the jit cache."""
    from opendiloco_tpu.diloco import outer_device as od

    trainer = make_trainer(tiny_cfg)
    state = trainer.init_state(jax.random.key(7))
    world = LoopbackWorld(1)
    (backend,) = world.make_backends()
    cfg = DilocoConfig(
        local_steps=2, backend="loopback", outer_placement="device"
    )
    opt = DiLoCoOptimizer(trainer, backend, cfg, state, batch_size=8)
    data = list(batches(0, tiny_cfg.vocab_size, 8))
    for ids, labels in data[:4]:  # two full rounds compile everything
        state, _ = opt.step(state, trainer.shard_batch(ids, labels, accum=1))
    sizes = {
        name: getattr(od, name)._cache_size()
        for name in ("_pg_f32", "_apply_fused", "_overwrite_fused")
    }
    for ids, labels in data[4:]:
        state, _ = opt.step(state, trainer.shard_batch(ids, labels, accum=1))
    for name, before in sizes.items():
        assert getattr(od, name)._cache_size() == before, name
