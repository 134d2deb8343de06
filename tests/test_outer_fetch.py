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


def _run_rounds(tiny_cfg, rounds=3, landing="early", **kw):
    """-> (masters after each round's landing, rows, the plane)

    ``landing`` (streaming only): a fragment whose slot is the phase's last
    step is launched by that step's tick and lands in the boundary of the
    same ``opt.step`` if its comm thread (fetch and all-reduce) is done by
    then, else one inner step later -- the one landing the waits below
    cannot reach, and the two differ in bits (ROADMAP A6 (d)). "early": it
    is always done by then; "late": never (its all-reduce is held until the
    step has returned); None: as the threads fall."""
    import time

    trainer, state, opt = _worker(tiny_cfg, **kw)
    released = threading.Event()
    released.set()
    if opt._stream is not None and landing == "early":
        boundary = opt._stream.boundary

        def pinned_boundary(state):
            opt._stream.wait_inflight()
            return boundary(state)

        opt._stream.boundary = pinned_boundary
    if opt._stream is not None and landing == "late":
        all_reduce = opt.backend.all_reduce

        def held(arrays, **kw):
            assert released.wait(60.0)
            return all_reduce(arrays, **kw)

        opt.backend.all_reduce = held
    rng = np.random.default_rng(0)
    rows = []
    for _ in range(rounds * opt.cfg.local_steps):
        ids = ((rng.integers(0, tiny_cfg.vocab_size, (8, 1)) + np.arange(16))
               % tiny_cfg.vocab_size).astype(np.int32)
        if landing == "late":
            released.clear()
        state, m = opt.step(state, trainer.shard_batch(ids, ids.copy(), accum=1))
        released.set()
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


def test_a_streamed_round_lands_early_or_late_and_nowhere_else(tiny_cfg):
    """No pin at the boundary: the last slot's fragment lands where its comm
    thread lets it, and the bits are one landing's or the other's."""
    def bits(landing):
        masters, bufs, params, _, _ = _run_rounds(
            tiny_cfg, rounds=2, landing=landing, **MODES["stream-delayed"])
        return [a.tobytes() for a in masters + bufs + params]

    early, late = bits("early"), bits("late")
    assert early != late  # else the pin above pins nothing
    assert bits(None) in (early, late)


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


# -- the boundary's pipeline: fetch -> all-reduce -> put, a piece at a time ---


@pytest.mark.parametrize(
    "nbytes, pieces",
    [
        # the 1.7B cell's leaves: three stacks, five of a quarter their size,
        # three norms that ride with the last piece
        ([403, 0.008, 1611, 1611, 0.2, 403, 403, 0.2, 403, 1611, 403],
         [[2], [3], [9], [0], [5], [6], [8], [10, 4, 7, 1]]),
        ([5, 5, 5], [[0], [1], [2]]),  # equal leaves keep their order
        ([1, 1000], [[1, 0]]),  # large first, the small one rides
        ([0.1, 0.1, 100, 0.1], [[2, 0, 1, 3]]),
        ([7], [[0]]),
        ([], []),
    ],
)
def test_pieces_are_whole_leaves_large_first(nbytes, pieces):
    assert od.cut_pieces(nbytes) == pieces
    # every leaf in exactly one piece
    assert sorted(j for p in pieces for j in p) == list(range(len(nbytes)))


def test_a_fetch_hands_its_pieces_on_in_order_and_lets_go_of_them():
    """Stage 1 alone: the pieces arrive in their order whatever order the
    shards land in, with device_get's bits, and a piece's device arrays are
    the fetch's no longer once it has been handed on."""
    names = ["axis1", "stacked_last", "axis0", "repeated"]
    leaves = [_put(n, np.float32, i) for i, n in enumerate(names)]
    leaves += [_replicated(), _one_device()]
    want = jax.device_get(leaves)
    pieces = [[2], [5, 0], [3, 1, 4]]
    got = []

    def deliver(k, arrays):
        # the piece's own device arrays are gone from the caller's list
        assert all(leaves[j] is None for j in pieces[k])
        assert all(leaves[j] is not None for p in pieces[k + 1:] for j in p)
        got.append((k, arrays))

    out, stats = od._fetch_sharded(
        leaves, range(6), OutputPool(keep=2), threading.Lock(), pieces, deliver)
    assert [k for k, _ in got] == [0, 1, 2]
    for (k, arrays), piece in zip(got, pieces):
        for a, j in zip(arrays, piece):
            assert _same_bits(a, want[j]) and a is out[j]
    assert stats["shards"] == 16 and leaves == [None] * 6


def test_the_last_pieces_span_carries_what_the_whole_fetch_assembled():
    from opendiloco_tpu.diloco.optimizer import _BoundaryFetch

    spans = []

    class Tracer:
        def add_span(self, name, t0, t1, **attrs):
            spans.append((name, attrs))

    def fetch(deliver):
        deliver(0, [np.zeros(4, np.float32)])
        deliver(1, [np.zeros(2, np.float32)])

    stats = {"bytes": 24, "shards": 5, "new_bytes": 8}
    f = _BoundaryFetch(Tracer(), 3, fetch, stats=lambda: stats, piecewise=True)
    f.start()
    assert [k for k, _ in f.arrivals()] == [0, 1]
    f.join()
    assert spans == [
        ("outer/d2h", {"epoch": 3, "piece": 0, "bytes": 16}),
        ("outer/d2h", {"epoch": 3, "piece": 1, "bytes": 8, "shards": 5, "new_bytes": 8}),
    ]
    assert f.row() == {
        "outer_d2h_s": f.seconds, "outer_d2h_new_bytes": 8, "outer_pieces": 2,
    }


class _Recording:
    """A loopback backend that keeps the calls it saw, and can be told to
    fail one or to misreport one's group."""

    def __init__(self, backend):
        self._b = backend
        self.calls = []  # (tag, shapes)
        self.fail_call = self.lie_call = None

    def __getattr__(self, name):
        return getattr(self._b, name)

    def all_reduce(self, arrays, **kw):
        n = len(self.calls)
        self.calls.append((kw.get("tag", "grads"), [a.shape for a in arrays]))
        if n == self.fail_call:
            from opendiloco_tpu.diloco.backend import AllReduceError

            raise AllReduceError("the test's failure")
        out, group = self._b.all_reduce(arrays, **kw)
        return out, group + (n == self.lie_call)


def _pipeline_worker(trainer, *, frags=0, **cfg):
    state = trainer.init_state(jax.random.key(3))
    world = LoopbackWorld(1, compression=cfg.get("compression", "none"))
    (backend,) = world.make_backends()
    opt = DiLoCoOptimizer(
        trainer, _Recording(backend),
        DilocoConfig(local_steps=1, backend="loopback", outer_placement="device",
                     streaming_fragments=frags, skip_load_from_peers=True, **cfg),
        state, 8,
    )
    return state, opt, world


def _batches(tiny_cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        ids = ((rng.integers(0, tiny_cfg.vocab_size, (8, 1)) + np.arange(16))
               % tiny_cfg.vocab_size).astype(np.int32)
        yield ids, ids.copy()


def _plane_bits(opt, state):
    masters, bufs = opt._plane.host_state()
    return masters + (bufs or []) + jax.device_get(jax.tree.leaves(state["params"]))


_TRAINERS = {}


def _trainer(tiny_cfg, mesh):
    """One trainer a mesh for the whole file's pipeline tests: its compiled
    step is what these tests would otherwise spend their seconds on."""
    if mesh not in _TRAINERS:
        tc = TrainerConfig(lr=1e-3, warmup_steps=2, total_steps=200,
                           precision="fp32", remat=False)
        plan = (build_mesh("FULL_SHARD", devices=jax.devices()[:4]) if mesh == "sharded"
                else build_mesh("NO_SHARD", devices=jax.devices()[:1]))
        _TRAINERS[mesh] = InnerTrainer(tiny_cfg, tc, plan)
    return _TRAINERS[mesh]


def _one_piece(nbytes):
    return [list(range(len(nbytes)))]


_ROUNDS = {}


def _three_rounds(tiny_cfg, mesh, frags, forced, monkeypatch):
    """Three rounds with momentum -> (bits after each round, rows, opt, world);
    ``forced``: the same rounds held to one piece."""
    key = (mesh, frags, forced)
    if key not in _ROUNDS:
        if forced:
            monkeypatch.setattr(od, "cut_pieces", _one_piece)
        trainer = _trainer(tiny_cfg, mesh)
        state, opt, world = _pipeline_worker(trainer, frags=frags)
        bits, rows, grown = [], [], []
        for ids, labels in _batches(tiny_cfg, 3):
            state, row = opt.step(state, trainer.shard_batch(ids, labels, accum=1))
            bits.append(_plane_bits(opt, state))
            rows.append(row)
            grown.append(world._outputs.new_bytes)
        _ROUNDS[key] = bits, rows, grown, opt
    return _ROUNDS[key]


PIPELINE_CASES = [("one-device", 0), ("sharded", 0), ("one-device", 2), ("sharded", 2)]
_case_ids = [f"{m}{'-fragments' if f else ''}" for m, f in PIPELINE_CASES]


@pytest.mark.parametrize("mesh, frags", PIPELINE_CASES, ids=_case_ids)
def test_pipelined_rounds_leave_the_one_piece_rounds_bits(tiny_cfg, mesh, frags, monkeypatch):
    """Parameters, masters and momentum after every one of three rounds."""
    bits, rows, _, opt = _three_rounds(tiny_cfg, mesh, frags, False, monkeypatch)
    bits_1, rows_1, _, opt_1 = _three_rounds(tiny_cfg, mesh, frags, True, monkeypatch)
    assert all(r["outer_pieces"] == 1 for r in rows_1)
    assert all(r["outer_pieces"] > 2 for r in rows)
    assert opt._plane.bufs is not None  # momentum armed
    # a tag a piece on the wire; the one-piece round is the round it always was
    assert {t for t, _ in opt_1.backend.calls} == {"grads"}
    n = rows[0]["outer_pieces"]
    assert [t for t, _ in opt.backend.calls[:n]] == [f"grads-p{k}" for k in range(n)]
    for got, want in zip(bits, bits_1):
        assert len(got) == len(want)
        for a, b in zip(got, want):
            assert _same_bits(a, b)


@pytest.mark.parametrize("mesh, frags", PIPELINE_CASES, ids=_case_ids)
def test_no_round_after_the_first_writes_a_new_page(tiny_cfg, mesh, frags, monkeypatch):
    """More pieces a round than either pool keeps a position: the plane's
    arrays by leaf, the world's by tag and index, and both stop growing. A
    fragment schedule's first round is each fragment's first."""
    _, rows, grown, opt = _three_rounds(tiny_cfg, mesh, frags, False, monkeypatch)
    world = opt.backend.world
    assert rows[0]["outer_pieces"] > world._outputs.keep == opt._plane._fetched.keep
    first = 2 if frags else 1
    assert grown[0] > 0 and grown[first - 1] == grown[-1]
    assert all(len(kept) == 1 for kept in world._outputs._arrays.values())
    if mesh == "sharded":
        assert rows[0]["outer_d2h_new_bytes"] > 0
        assert all(r["outer_d2h_new_bytes"] == 0 for r in rows[first:])
    else:
        assert not any("outer_d2h_new_bytes" in r for r in rows)


def _three_pieces(nbytes):
    order = sorted(range(len(nbytes)), key=lambda j: -nbytes[j])
    return [order[:2], order[2:5], order[5:]]


def _stage_threads():
    return [t.name for t in threading.enumerate() if t.name in ("outer-d2h", "outer-h2d")]


@pytest.mark.parametrize("mesh", ["one-device", "sharded"])
def test_a_failed_piece_leaves_the_pre_round_state(tiny_cfg, mesh, monkeypatch):
    """The second of three pieces raises: the round raises, no stage thread
    stays alive, masters, momentum, parameters and epoch are the pre-round
    ones, and the next round runs as if nothing had happened: bit for bit the
    round of a twin that never failed."""
    from opendiloco_tpu.diloco.backend import AllReduceError

    monkeypatch.setattr(od, "cut_pieces", _three_pieces)
    trainer = _trainer(tiny_cfg, mesh)
    b0, b1 = (trainer.shard_batch(i, l, accum=1) for i, l in _batches(tiny_cfg, 2))

    def drive(faulty):
        state, opt, _ = _pipeline_worker(trainer)
        state, _ = opt.step(state, b0)  # round 0 (calls 0-2) arms the momentum
        state, _ = trainer.train_step(state, b1)  # the parameters leave the masters
        if faulty:
            before, epoch = _plane_bits(opt, state), opt.epoch
            opt.backend.fail_call = 4  # round 1 is calls 3, 4, 5
            with pytest.raises(AllReduceError):
                opt.outer_step(state)
            assert not _stage_threads()
            opt.backend.fail_call = None
            assert opt.epoch == epoch
            for a, b in zip(_plane_bits(opt, state), before):
                assert _same_bits(a, b)
        state, row = opt.outer_step(state)
        assert row["outer_pieces"] == 3 and not _stage_threads()
        return _plane_bits(opt, state), opt.epoch

    (got, epoch), (want, epoch_twin) = drive(True), drive(False)
    assert epoch == epoch_twin == 2
    for a, b in zip(got, want):
        assert _same_bits(a, b)


@pytest.mark.parametrize("mesh", ["one-device", "sharded"])
def test_pieces_of_different_group_sizes_make_an_elastic_round(tiny_cfg, mesh, monkeypatch):
    """A peer that drops between two pieces leaves the later ones a smaller
    group: the round completes with every leaf's own mean, reports the
    smallest group and says that it was elastic; one check of the group's
    size a round."""
    monkeypatch.setattr(od, "cut_pieces", _three_pieces)
    trainer = _trainer(tiny_cfg, mesh)
    (b0,) = (trainer.shard_batch(i, l, accum=1) for i, l in _batches(tiny_cfg, 1))

    def drive(drop):
        state, opt, _ = _pipeline_worker(trainer)
        checked = []
        check = opt._check_group_size
        opt._check_group_size = lambda n: (checked.append(n), check(n))
        if drop:
            opt.backend.lie_call = 0  # the first piece saw one peer more
        state, row = opt.step(state, b0)
        assert row["outer_pieces"] == 3 and not _stage_threads()
        assert row["num_peers"] == 1 and checked == [1]
        assert row.get("elastic", False) is drop
        if drop:
            assert row["expected_peers"] == 2
        return _plane_bits(opt, state)

    for a, b in zip(drive(True), drive(False)):
        assert _same_bits(a, b)


def _serial_boundary(opt, state):
    """The boundary one stage after another over the whole list, from the
    plane's own operations in the order the optimizer ran them before it
    had pieces: what a round in one piece has to leave, bit for bit."""
    plane = opt._plane
    leaves = jax.tree.leaves(state["params"])
    pg, _, _ = plane.pseudo_grad(leaves)
    if opt._ef is not None:
        opt._ef.prepare("main", range(len(pg)), pg)
    avg, _ = opt.backend.all_reduce(pg, epoch=opt.epoch, timeout=30.0)
    if opt._ef is not None:
        opt._ef.commit("main")
    if opt._is_state_avg_epoch():
        plane.apply_average(avg)
        masters, _ = plane.host_state()
        mean, _ = opt.backend.all_reduce(masters, tag="state", timeout=30.0)
        plane.load_masters(mean)
        new = plane.sync_params(leaves)
    else:
        new = plane.apply_average(avg, sync=leaves)
    opt.epoch += 1
    return dict(state, params=jax.tree.unflatten(opt.treedef, new))


WHOLE_LIST_ROUNDS = {
    "error-feedback": dict(compression="blockwise4bit", error_feedback=True),
    "state-averaging": dict(average_state_every=2),
    "gossip": dict(outer_mode="gossip"),
}


@pytest.mark.parametrize("mode", list(WHOLE_LIST_ROUNDS))
def test_rounds_that_need_the_whole_list_run_in_one_piece(tiny_cfg, mode):
    """Error feedback stages its residual over the whole pseudo-gradient, a
    state-averaging epoch has a second leg, gossip mixes the whole list with
    its partner: the same code with one piece, one all-reduce under the tag
    the round always had, and the serial boundary's bits."""
    trainer = _trainer(tiny_cfg, "sharded")
    batches = [trainer.shard_batch(i, l, accum=1) for i, l in _batches(tiny_cfg, 3)]
    state, opt, _ = _pipeline_worker(trainer, **WHOLE_LIST_ROUNDS[mode])
    rows = []
    for b in batches:
        state, _ = trainer.train_step(state, b)
        state, row = opt.outer_step(state)
        rows.append(row)
    n_leaves = len(opt._plane.masters)
    tags = [(t, len(shapes)) for t, shapes in opt.backend.calls]
    if mode == "state-averaging":
        # the epoch with the second leg alone; its neighbours run in pieces
        n = rows[0]["outer_pieces"]
        assert n > 2 and [r["outer_pieces"] for r in rows] == [n, 1, n]
        assert tags[n:-n] == [("grads", n_leaves), ("state", n_leaves)]
    else:
        assert [r["outer_pieces"] for r in rows] == [1, 1, 1]
    if mode == "gossip":
        assert tags == []  # a pair round is no all-reduce
        return
    if mode == "error-feedback":
        assert tags == [("grads", n_leaves)] * 3
    twin_state, twin, _ = _pipeline_worker(trainer, **WHOLE_LIST_ROUNDS[mode])
    for b in batches:
        twin_state, _ = trainer.train_step(twin_state, b)
        twin_state = _serial_boundary(twin, twin_state)
    for a, b in zip(_plane_bits(opt, state), _plane_bits(twin, twin_state)):
        assert _same_bits(a, b)
    if opt._ef is not None:
        for a, b in zip(opt._plane.ef_host_state(), twin._plane.ef_host_state()):
            assert _same_bits(a, b)
