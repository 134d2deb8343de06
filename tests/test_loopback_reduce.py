"""The loopback backend's round, byte for byte and buffer by buffer.

``LoopbackBackend.all_reduce`` computes the mean of n contributions in n
reads and one write and hands results over instead of copying them where it
can. These tests hold it to the expression it replaced,
``np.sum([c[i] for c in contribs], axis=0) / n`` over the codec's round
trip of every contribution, for every codec and a spread of n, and to its
ownership contract: inputs are only read, a result is its caller's alone.
"""

import itertools
import os
import random
import sys
import threading
import time

import numpy as np
import pytest

from opendiloco_tpu import obs
from opendiloco_tpu.diloco import LoopbackWorld
from opendiloco_tpu.diloco.compression import _CODECS

NS = (1, 2, 3, 5, 8, 9)
# one element (numpy reduces it pairwise from 8 rows on), a scalar, a column,
# a matrix, a long vector; _inputs adds a float64
# array, two in another memory order than C, and a strided view
SHAPES = ((1000,), (33, 7), (1,), (), (5, 1), (70_000,))


@pytest.fixture(autouse=True)
def _clean_obs(monkeypatch):
    monkeypatch.delenv("ODTP_OBS", raising=False)
    obs.reset()
    yield
    obs.reset()


def _inputs(n: int, seed: int = 0) -> list[list[np.ndarray]]:
    rng = np.random.default_rng(seed)
    peers = []
    for _ in range(n):
        arrays = [
            np.asarray(
                rng.standard_normal(s) * 10 ** rng.uniform(-3, 3), np.float32
            )
            for s in SHAPES
        ]
        arrays.append(rng.standard_normal((17,)))  # float64: cast on the way in
        # what device_get hands back on the TPU: the device's layout, not C order
        arrays.append(np.asfortranarray(rng.standard_normal((12, 9)).astype(np.float32)))
        arrays.append(
            rng.standard_normal((3, 8, 5)).astype(np.float32).transpose(0, 2, 1)
        )
        arrays.append(rng.standard_normal((40,)).astype(np.float32)[::2])
        for a in arrays:
            a.flags.writeable = False  # a write into an input raises
        peers.append(arrays)
    return peers


def _wire(codec, a: np.ndarray) -> np.ndarray:
    """One contribution as the parent's round saw it: the codec's round trip."""
    payload, meta = codec.encode(a)
    return codec.decode(payload, a.shape, meta)


def _parent_mean(codec, contributions: list[list[np.ndarray]]) -> list[np.ndarray]:
    """The expression ``all_reduce`` held before: a stack, a sum, a divide."""
    wired = [[_wire(codec, a) for a in arrays] for arrays in contributions]
    n = len(wired)
    return [
        np.asarray(np.sum([c[i] for c in wired], axis=0) / n)
        for i in range(len(wired[0]))
    ]


def _run_round(backends, inputs, *, ordered: bool, **kw):
    """Every backend contributes ``inputs[i]`` from a thread of its own.
    ``ordered`` starts peer i+1 only once peer i's contribution is in the
    world, so the order of arrival (the order of the sum) is the list's."""
    world = backends[0].world
    out = [None] * len(backends)
    errors = []

    def worker(i):
        try:
            out[i] = backends[i].all_reduce(inputs[i], timeout=30.0, **kw)
        except BaseException as e:  # surfaced below
            errors.append(e)

    threads = []
    for i, b in enumerate(backends):
        t = threading.Thread(target=worker, args=(i,), daemon=True)
        t.start()
        threads.append(t)
        if ordered and i + 1 < len(backends):
            _wait_for_contribution(world, b.peer_id)
    for t in threads:
        t.join(timeout=60.0)
    assert not errors, errors
    assert all(r is not None for r in out)
    return out


def _same_bytes(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        g = np.asarray(g)
        assert g.dtype == np.float32 and g.shape == w.shape
        assert g.tobytes() == w.tobytes()


@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("codec", sorted(_CODECS))
def test_all_reduce_bits_match_the_parent_expression(codec, n):
    world = LoopbackWorld(n, compression=codec)
    backends = world.make_backends()
    inputs = _inputs(n, seed=n)
    want = _parent_mean(world.codec, inputs)
    for result, group in _run_round(backends, inputs, ordered=True, epoch=3):
        assert group == n
        _same_bytes(result, want)


@pytest.mark.parametrize("n", NS)
@pytest.mark.parametrize("codec", sorted(_CODECS))
def test_group_round_bits_match_the_parent_expression(codec, n):
    cap = max(2, (n + 1) // 2)
    world = LoopbackWorld(n, compression=codec)
    backends = world.make_backends()
    inputs = _inputs(n, seed=100 + n)
    tag, epoch = "grads", 5
    # the partition the first arriver freezes, and the sorted order in which
    # a group's contributions are summed
    members = sorted(b.peer_id for b in backends)
    random.Random(f"{tag}-epoch-{epoch}").shuffle(members)
    groups = [
        tuple(sorted(members[i : i + cap])) for i in range(0, len(members), cap)
    ]
    by_id = {b.peer_id: inputs[i] for i, b in enumerate(backends)}
    out = _run_round(
        backends, inputs, ordered=False, tag=tag, epoch=epoch, group_cap=cap
    )
    for b, (result, size) in zip(backends, out):
        (group,) = [g for g in groups if b.peer_id in g]
        assert size == len(group)
        _same_bytes(result, _parent_mean(world.codec, [by_id[m] for m in group]))


@pytest.mark.parametrize("group_cap", (0, 2), ids=("world", "groups"))
@pytest.mark.parametrize("n", (1, 2, 4))
@pytest.mark.parametrize("codec", ("none", "fp16", "topk"))
def test_results_are_owned_and_inputs_only_read(codec, n, group_cap):
    world = LoopbackWorld(n, compression=codec)
    backends = world.make_backends()
    inputs = _inputs(n, seed=7)
    before = [[a.tobytes() for a in arrays] for arrays in inputs]
    out = _run_round(backends, inputs, ordered=False, group_cap=group_cap)
    results = [r for r, _ in out]
    flat_inputs = [a for arrays in inputs for a in arrays]
    for i, result in enumerate(results):
        for r in result:
            r = np.asarray(r)
            assert r.flags.writeable
            assert not any(np.shares_memory(r, a) for a in flat_inputs)
            for j, other in enumerate(results):
                if j != i:
                    assert not any(np.shares_memory(r, np.asarray(o)) for o in other)
    # writing into one peer's result changes neither an input nor a result
    kept = [[np.asarray(r).tobytes() for r in result] for result in results[1:]]
    for r in results[0]:
        np.asarray(r)[...] = 12345.0
    assert [[a.tobytes() for a in arrays] for arrays in inputs] == before
    assert [[np.asarray(r).tobytes() for r in res] for res in results[1:]] == kept


def test_a_result_outlives_later_rounds():
    (backend,) = LoopbackWorld(1).make_backends()
    x = np.arange(1000, dtype=np.float32)
    first, _ = backend.all_reduce([x], epoch=0)
    snapshot = first[0].tobytes()
    for epoch in range(1, 6):
        backend.all_reduce([x * epoch], epoch=epoch)
    assert first[0].tobytes() == snapshot == x.tobytes()


def _kept(world):
    return [a for kept in world._outputs._arrays.values() for a in kept]


@pytest.mark.parametrize("n", (1, 3))
def test_a_dropped_result_is_written_again_and_a_held_one_never(n):
    """Output arrays are kept across rounds (new pages are what a pass
    costs on the TPU host) and handed out again only when nobody holds
    them."""
    world = LoopbackWorld(n)
    backends = world.make_backends()
    inputs = [[np.full((4096,), float(i + 1), np.float32)] for i in range(n)]
    mean = np.float32(sum(range(1, n + 1))) / np.float32(n)
    for _ in range(6):  # results dropped at once: the same n arrays serve
        out = _run_round(backends, inputs, ordered=False)
        assert all(r[0][0] == mean for r, _ in out)
        del out
    assert len(_kept(world)) == n
    held = []
    for k in range(6):  # results held: every round gets arrays of its own
        scaled = [[a * (k + 1) for a in arrays] for arrays in inputs]
        held.append((k, _run_round(backends, scaled, ordered=False)))
    flat = [r[0] for _, out in held for r, _ in out]
    assert len({a.ctypes.data for a in flat}) == len(flat) == 6 * n
    for k, out in held:
        for r, _ in out:
            assert (r[0] == mean * (k + 1)).all()
    # and the world remembers a bounded number of them
    assert len(_kept(world)) == world._outputs.keep == 2 * n


def test_a_result_keeps_the_contributions_memory_order():
    """The copy is a flat one: the output is laid out as the input is (on
    the TPU, as the device laid it out), not re-ordered into C order."""
    (backend,) = LoopbackWorld(1).make_backends()
    x = np.asfortranarray(np.arange(12, dtype=np.float32).reshape(3, 4))
    (r,), _ = backend.all_reduce([x])
    assert r.strides == x.strides and (r == x).all()
    assert not np.shares_memory(r, x)


def _spans(cap, name):
    return [s for s in cap.spans if s["name"] == name]


@pytest.mark.parametrize(
    "codec,n,path",
    [
        ("none", 1, "copy"),
        ("fp16", 1, "handover"),
        ("blockwise4bit", 1, "handover"),
        ("none", 3, "accumulate"),
        ("uniform8bit", 3, "accumulate"),
    ],
)
def test_reduce_span_says_which_path_and_how_many_bytes(codec, n, path):
    world = LoopbackWorld(n, compression=codec)
    backends = world.make_backends()
    inputs = _inputs(n, seed=3)
    obs.capture.start()
    try:
        _run_round(backends, inputs, ordered=False)
    finally:
        cap = obs.capture.stop()
    (reduce_,) = _spans(cap, "outer/reduce")  # one peer publishes the mean
    args = reduce_["args"]
    assert args["path"] == path and args["group"] == n
    written = sum(int(np.prod(a.shape)) * 4 for a in inputs[0])
    assert args["bytes"] == (0 if path == "handover" else written)
    # every collector but the generation's last copies the published mean
    adopts = _spans(cap, "outer/adopt")
    assert sorted(s["args"]["copied"] for s in adopts) == [False] + [True] * (n - 1)
    assert len(_spans(cap, "outer/encode")) == n


@pytest.mark.parametrize("contributed", (False, True), ids=("before", "after"))
@pytest.mark.parametrize("codec", ("none", "scaled-fp16"))
def test_a_peer_that_closes_mid_round_leaves_the_survivors_mean(codec, contributed):
    world = LoopbackWorld(3, compression=codec)
    b0, b1, b2 = world.make_backends()
    inputs = _inputs(3, seed=11)
    out = {}

    def worker(i, b):
        out[i] = b.all_reduce(inputs[i], timeout=30.0, epoch=1)

    threads = [threading.Thread(target=worker, args=(0, b0), daemon=True)]
    if contributed:
        # the third peer's contribution arrives, its collector never does:
        # put it in as the backend would, then drop the peer
        from opendiloco_tpu.diloco.loopback import _contribution

        threads[0].start()
        _wait_for_contribution(world, b0.peer_id)
        with world.cond:
            slot = next(iter(world._rounds.values()))
            slot["contrib"][b2.peer_id] = _contribution(world.codec, inputs[2])
        b2.close()
        want = _parent_mean(world.codec, [inputs[0], inputs[2], inputs[1]])
        size = 3
    else:
        threads[0].start()
        _wait_for_contribution(world, b0.peer_id)
        b2.close()
        want = _parent_mean(world.codec, inputs[:2])
        size = 2
    threads.append(threading.Thread(target=worker, args=(1, b1), daemon=True))
    threads[1].start()
    for t in threads:
        t.join(timeout=30.0)
    assert sorted(out) == [0, 1]
    for result, group in out.values():
        assert group == size
        _same_bytes(result, want)
    assert not np.shares_memory(out[0][0][0], out[1][0][0])
    # every survivor collected: the key's slot is gone
    assert not world._rounds


def _wait_for_contribution(world, peer_id):
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        with world.lock:
            if any(peer_id in s["contrib"] for s in world._rounds.values()):
                return
        time.sleep(0.001)
    raise AssertionError(f"{peer_id} never contributed")


def test_concurrent_tags_share_the_world_without_mixing():
    n, tags = 3, ("frag0", "frag1", "frag2", "frag3")
    world = LoopbackWorld(n)
    backends = world.make_backends()
    per_tag = {t: _inputs(n, seed=50 + k) for k, t in enumerate(tags)}
    out = {}

    def worker(i, tag):
        out[(i, tag)] = backends[i].all_reduce(
            per_tag[tag][i], timeout=30.0, tag=tag, epoch=2
        )

    threads = [
        threading.Thread(target=worker, args=(i, t), daemon=True)
        for t in tags for i in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    assert len(out) == n * len(tags)
    for tag in tags:
        results = [out[(i, tag)] for i in range(n)]
        assert all(g == n for _, g in results)
        # arrival order is the threads' business: all peers agree, and the
        # mean is the parent's expression over one of the orders
        for r, _ in results[1:]:
            _same_bytes(r, [np.asarray(a) for a in results[0][0]])
        got = [np.asarray(a).tobytes() for a in results[0][0]]
        orders = [
            [a.tobytes() for a in _parent_mean(world.codec, [per_tag[tag][i] for i in order])]
            for order in itertools.permutations(range(n))
        ]
        assert got in orders
    assert not world._rounds


def test_stress_many_peers_rounds_and_held_results():
    """More peers than cores, a short switch interval, results held across
    rounds and checked again later: a result written twice while somebody
    holds it, or a copy read while its source is written, breaks the sums."""
    n, rounds, hold = 2 * (os.cpu_count() or 4) + 1, 40, 3
    world = LoopbackWorld(n)
    backends = world.make_backends()
    size = 2048
    errors = []

    def expect(r):  # peers contribute i + r: the mean is r + (n - 1) / 2
        return np.float32(sum(range(r, r + n))) / np.float32(n)

    def worker(i):
        try:
            held = []
            for r in range(rounds):
                x = np.full((size,), float(i + r), np.float32)
                tag = "frag%d" % (r % 2)
                (got,), group = backends[i].all_reduce(
                    [x], timeout=60.0, tag=tag, epoch=r
                )
                assert group == n
                assert (x == i + r).all()
                held.append((r, got))
                for rr, a in held:
                    assert (a == expect(rr)).all(), (i, r, rr)
                if i % 2:
                    got += 1.0  # a result is its holder's to write into
                    held[-1] = (r, got)
                    held[-1][1][...] = expect(r)
                del held[:-hold]
        except BaseException as e:
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [
            threading.Thread(target=worker, args=(i,), daemon=True) for i in range(n)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120.0)
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors[:3]
    assert not any(t.is_alive() for t in threads)
    assert not world._rounds


@pytest.mark.parametrize("n", (1, 3))
def test_a_rounds_pieces_keep_arrays_of_their_own(n):
    """The blocking boundary calls once a piece, a tag a piece, every call's
    arrays starting at index 0 and several of one shape: more calls a round
    than the pool keeps a position. While the way back (or the caller) holds
    a piece's result no later piece's call and no later round writes it, and
    from the second round on nothing new is allocated."""
    world = LoopbackWorld(n)
    backends = world.make_backends()
    pieces = world._outputs.keep + 3

    def one_round(r, held):
        results = []
        for k in range(pieces):
            inputs = [[np.full((4096,), 100.0 * r + 10 * k + i, np.float32)]
                      for i in range(n)]
            results.append(_run_round(backends, inputs, ordered=False,
                                      tag=f"grads-p{k}", epoch=r))
        # every piece's result still holds its own mean at the round's end
        for k, out in enumerate(results):
            mean = np.float32(100.0 * r + 10 * k + (n - 1) / 2)
            for arrays, group in out:
                assert group == n and (arrays[0] == mean).all()
        for arrays, snapshot in held:
            assert arrays[0].tobytes() == snapshot
        return results

    first = one_round(0, [])
    after_first = world._outputs.new_bytes
    assert after_first == pieces * n * 4096 * 4
    held = [(arrays, arrays[0].tobytes()) for arrays, _ in first[1]]  # piece 1's
    del first
    for r in (1, 2, 3):
        one_round(r, held)
    # the held piece cost its tag one more array a collector, once
    assert world._outputs.new_bytes == after_first + n * 4096 * 4
    del held
    grown = world._outputs.new_bytes
    one_round(4, [])
    assert world._outputs.new_bytes == grown
