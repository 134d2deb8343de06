"""The device outer plane's sharded fetch, bit for bit and buffer by buffer.

``outer_device._fetch_sharded`` stands where ``jax.device_get`` stood in
``DeviceOuterPlane.pseudo_grad``: the same bits, assembled into host arrays
the plane keeps from round to round. Held here: the bits against
``device_get`` for every sharding a FULL_SHARD plan makes, the old path for
leaves with nothing to assemble, the pool's contract (an array somebody
holds is never written again; dropped arrays come back), and whole
``DiLoCoOptimizer`` rounds on a sharded CPU mesh against the same rounds
fetched the parent's way.
"""

import threading

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from opendiloco_tpu.config import DilocoConfig
from opendiloco_tpu.diloco import DiLoCoOptimizer, LoopbackWorld
from opendiloco_tpu.diloco import outer_device as od
from opendiloco_tpu.diloco.hostpool import OutputPool
from opendiloco_tpu.parallel.mesh import build_mesh
from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig


def _mesh(*sizes_names):
    sizes = [s for s, _ in sizes_names]
    devices = np.array(jax.devices()[: int(np.prod(sizes))]).reshape(sizes)
    return Mesh(devices, tuple(n for _, n in sizes_names))


# name -> (global shape, mesh, spec, distinct shards)
SHARDED = {
    "axis0": ((16, 6), lambda: _mesh((4, "x")), P("x", None), 4),
    "axis1": ((6, 16), lambda: _mesh((4, "x")), P(None, "x"), 4),
    "stacked_last": ((3, 5, 8), lambda: _mesh((4, "x")), P(None, None, "x"), 4),
    "stacked_middle": ((3, 8, 5), lambda: _mesh((4, "x")), P(None, "x", None), 4),
    # the axis "r" replicates: every shard lives on two devices
    "repeated": ((8, 6), lambda: _mesh((2, "r"), (4, "x")), P("x", None), 4),
    "two_axes": ((4, 6), lambda: _mesh((2, "a"), (2, "b")), P("a", "b"), 4),
}


def _values(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal(shape).astype(dtype)
    flat = a.reshape(-1)
    flat[:4] = [np.nan, np.inf, -0.0, np.finfo(dtype).tiny]  # bits, not values
    return a


def _put(name, dtype, seed=0):
    shape, mesh, spec, _ = SHARDED[name]
    return jax.device_put(_values(shape, dtype, seed), NamedSharding(mesh(), spec))


def _same_bits(a, b):
    return a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == b.tobytes()


@pytest.mark.parametrize("dtype", [np.float32, np.float16], ids=["f32", "f16"])
@pytest.mark.parametrize("name", list(SHARDED))
def test_bits_are_device_gets(name, dtype):
    x = _put(name, dtype)
    assert od._is_assembled(x)
    pool = OutputPool(keep=2)
    (got,), stats = od._fetch_sharded([x], [0], pool, threading.Lock())
    assert _same_bits(got, jax.device_get(x))
    assert stats == {"bytes": x.nbytes, "shards": SHARDED[name][3],
                     "new_bytes": x.nbytes}
    # the pool's own array: it owns its memory and can be written
    assert got.base is None and got.flags.owndata and got.flags.writeable
    assert any(got is a for kept in pool._arrays.values() for a in kept)


def _one_device(shape=(8, 6)):
    return jax.device_put(_values(shape, np.float32), jax.devices()[0])


def _replicated(shape=(8, 6)):
    return jax.device_put(_values(shape, np.float32, 1),
                          NamedSharding(_mesh((4, "x")), P()))


def _sharded_on_one_device_mesh(shape=(8, 6)):
    return jax.device_put(_values(shape, np.float32, 2),
                          NamedSharding(_mesh((1, "x")), P("x", None)))


@pytest.mark.parametrize(
    "make", [_one_device, _replicated, _sharded_on_one_device_mesh],
    ids=["one_device", "replicated", "one_device_mesh"],
)
def test_nothing_to_assemble_is_left_to_device_get(make):
    x = make()
    assert not od._is_assembled(x)
    pool = OutputPool(keep=2)
    (got,), stats = od._fetch_sharded([x], [0], pool, threading.Lock())
    assert _same_bits(got, jax.device_get(x))
    assert stats == {"bytes": 0, "shards": 0, "new_bytes": 0}
    assert not pool._arrays


def test_a_mixed_list_keeps_its_order():
    leaves = [_one_device(), _put("axis1", np.float32), _replicated(),
              _put("stacked_last", np.float16), _put("repeated", np.float32)]
    pool = OutputPool(keep=2)
    got, stats = od._fetch_sharded(leaves, range(len(leaves)), pool, threading.Lock())
    for a, b in zip(got, jax.device_get(leaves)):
        assert _same_bits(a, b)
    assembled = [x for x in leaves if od._is_assembled(x)]
    assert stats["bytes"] == stats["new_bytes"] == sum(x.nbytes for x in assembled)
    assert stats["shards"] == 12


def _round(pool, seed, names=("axis1", "stacked_last"), keys=None):
    leaves = [_put(n, np.float32, seed) for n in names]
    got, stats = od._fetch_sharded(
        leaves, keys or range(len(leaves)), pool, threading.Lock())
    for a, b in zip(got, jax.device_get(leaves)):
        assert _same_bits(a, b)
    return got, stats


def test_a_second_round_writes_into_the_first_rounds_arrays():
    pool = OutputPool(keep=2)
    got, stats = _round(pool, 0)
    ids = [id(a) for a in got]
    assert stats["new_bytes"] == stats["bytes"] > 0
    del got
    got, stats = _round(pool, 1)
    assert stats["new_bytes"] == 0 and [id(a) for a in got] == ids


@pytest.mark.parametrize("holder", ["array", "view", "list"])
def test_an_array_somebody_holds_is_never_written_again(holder):
    """An unresolved all-reduce future, the eager path's ``pg_host``: whoever
    keeps a fetched array, or a view of it, keeps its bits."""
    pool = OutputPool(keep=2)
    got, _ = _round(pool, 0)
    before = [a.copy() for a in got]
    held = {"array": got[0], "view": got[0][:1], "list": got}[holder]
    first = got[0]
    del got
    for seed in (1, 2, 3):
        again, _ = _round(pool, seed)
        assert again[0] is not first
        del again
    kept = held[0] if holder == "list" else held
    assert _same_bits(np.asarray(kept), before[0][: kept.shape[0]])
    # let go, and the array is handed out again: nothing new is allocated
    del held, kept
    taken = pool.new_bytes
    again, stats = _round(pool, 4)
    assert stats["new_bytes"] == 0 and pool.new_bytes == taken


def test_held_rounds_cost_one_more_array_and_no_more():
    """Delayed and eager rounds hold a fetch across the next boundary: the
    pool grows to two arrays a leaf and stays there."""
    pool = OutputPool(keep=2)
    held, _ = _round(pool, 0)
    one = pool.new_bytes
    for seed in range(1, 6):
        nxt, _ = _round(pool, seed)
        held = nxt  # the older one is let go as the newer is taken
        del nxt
    assert pool.new_bytes == 2 * one


def test_fragments_do_not_share_arrays():
    """Two fragments of equal shapes, fetched in turn under their leaves'
    own positions: each comes back to its own arrays."""
    pool = OutputPool(keep=2)
    names = ("axis1", "axis1")
    a, stats = _round(pool, 0, names, keys=[0, 2])
    ids_a = [id(x) for x in a]
    del a
    b, stats = _round(pool, 1, names, keys=[1, 3])
    assert stats["new_bytes"] == stats["bytes"]
    assert not set(ids_a) & {id(x) for x in b}
    del b
    a, stats = _round(pool, 2, names, keys=[0, 2])
    assert stats["new_bytes"] == 0 and [id(x) for x in a] == ids_a


def test_concurrent_fetches_never_share_an_array():
    """Streaming fragments fetch on their comm threads while a boundary
    fetches on its own: more fetchers than cores over one pool and one lock,
    all asking for the same positions. An array handed to two of them at
    once would show one the other's bits."""
    import sys
    import time

    pool, lock = OutputPool(keep=2), threading.Lock()
    n_threads, rounds = 24, 15
    leaves = [[_put("axis1", np.float32, 100 + t), _put("stacked_last", np.float32, 200 + t)]
              for t in range(n_threads)]
    want = [[np.asarray(x).copy() for x in mine] for mine in leaves]
    errors, done = [], []

    def fetcher(t):
        try:
            for _ in range(rounds):
                got, _ = od._fetch_sharded(leaves[t], [0, 1], pool, lock)
                time.sleep(0)  # let the others write
                for a, b in zip(got, want[t]):
                    assert _same_bits(a, b)
                del got
            done.append(t)
        except BaseException as e:  # reported below, in the test's thread
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=fetcher, args=(t,)) for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(120.0)
    finally:
        sys.setswitchinterval(old)
    assert not errors, errors[0]
    assert sorted(done) == list(range(n_threads))
    assert all(len(kept) <= pool.keep for kept in pool._arrays.values())


# -- whole rounds on a sharded mesh -------------------------------------------


def _worker(tiny_cfg, *, strategy="FULL_SHARD", n_devices=4, overlap="none",
            compression="none", frags=0, local_steps=2):
    tc = TrainerConfig(lr=1e-3, warmup_steps=2, total_steps=200, precision="fp32",
                       remat=False)
    plan = build_mesh(strategy, devices=jax.devices()[:n_devices])
    trainer = InnerTrainer(tiny_cfg, tc, plan)
    state = trainer.init_state(jax.random.key(3))
    (backend,) = LoopbackWorld(1, compression=compression).make_backends()
    opt = DiLoCoOptimizer(
        trainer, backend,
        DilocoConfig(local_steps=local_steps, backend="loopback",
                     outer_placement="device", overlap_comm=overlap,
                     compression=compression, streaming_fragments=frags,
                     skip_load_from_peers=True),
        state, 8,
    )
    return trainer, state, opt


def _run_rounds(tiny_cfg, rounds=3, **kw):
    """-> (masters after each round's landing, rows, the plane)"""
    import time

    trainer, state, opt = _worker(tiny_cfg, **kw)
    rng = np.random.default_rng(0)
    rows = []
    for _ in range(rounds * opt.cfg.local_steps):
        ids = ((rng.integers(0, tiny_cfg.vocab_size, (8, 1)) + np.arange(16))
               % tiny_cfg.vocab_size).astype(np.int32)
        state, m = opt.step(state, trainer.shard_batch(ids, ids.copy(), accum=1))
        if "outer_step_s" in m:
            rows.append(m)
        # pin the landing schedule (test_outer_placement): which step lands
        # a round in flight is a race, in the parent as well
        p = opt._pending
        if p is not None and p.get("future") is not None:
            while not p["future"].done():
                time.sleep(0.001)
        if opt._stream is not None:
            opt._stream.wait_inflight()
    state = opt.flush(state)
    masters, bufs = opt._plane.host_state()
    return masters, bufs, jax.device_get(jax.tree.leaves(state["params"])), rows, opt._plane


MODES = {
    "blocking": {},
    "delayed": {"overlap": "delayed"},
    "eager": {"overlap": "eager"},
    "blocking-fp16": {"compression": "fp16"},
    "eager-fp16": {"overlap": "eager", "compression": "fp16"},
    "fragments": {"frags": 2},
    # the streaming launch fetches on its comm thread, outside any row
    "stream-delayed": {"frags": 2, "overlap": "delayed"},
    "stream-eager-fp16": {"frags": 2, "overlap": "eager", "compression": "fp16"},
}


@pytest.mark.parametrize("mode", list(MODES))
def test_rounds_on_a_sharded_mesh_match_the_parents_fetch(tiny_cfg, mode, monkeypatch):
    masters, bufs, params, rows, plane = _run_rounds(tiny_cfg, **MODES[mode])
    model_bytes = sum(m.nbytes for m in masters)
    wire_bytes = model_bytes // 2 if "fp16" in mode else model_bytes
    sharded = [x for x in plane.masters if od._is_assembled(x)]
    assert sharded and len(sharded) < len(plane.masters)  # the norms replicate
    # the fetch engaged, and stopped allocating
    if not mode.startswith("stream"):
        assert rows[0]["outer_d2h_new_bytes"] > 0
        assert rows[-1]["outer_d2h_new_bytes"] == 0
    limit = 1 if mode.startswith("blocking") or mode == "fragments" else 2
    assert 0 < plane._fetched.new_bytes <= limit * wire_bytes
    # the parent's way: every leaf through jax.device_get
    monkeypatch.setattr(od, "_is_assembled", lambda x: False)
    masters_p, bufs_p, params_p, rows_p, plane_p = _run_rounds(tiny_cfg, **MODES[mode])
    assert plane_p._fetched.new_bytes == 0
    assert not any("outer_d2h_new_bytes" in r for r in rows_p)
    for a, b in zip(masters + bufs + params, masters_p + bufs_p + params_p):
        assert _same_bits(a, b)


def test_one_device_plane_never_enters_the_helper(tiny_cfg, monkeypatch):
    """The one-chip cell and every one-device test: today's code to the letter."""
    def refuse(*a, **k):
        raise AssertionError("the sharded fetch was entered")

    monkeypatch.setattr(od, "_fetch_sharded", refuse)
    trainer, state, opt = _worker(tiny_cfg, strategy="NO_SHARD", n_devices=1,
                                  local_steps=1)
    ids = (np.arange(8 * 16).reshape(8, 16) % tiny_cfg.vocab_size).astype(np.int32)
    state, row = opt.step(state, trainer.shard_batch(ids, ids.copy(), accum=1))
    assert row["outer_d2h_s"] > 0 and "outer_d2h_new_bytes" not in row
    assert opt._plane.last_fetch == {} and not opt._plane._fetched._arrays
