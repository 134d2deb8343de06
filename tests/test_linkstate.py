"""Adaptive outer transport (diloco/linkstate.py + ODTP_LINK_ADAPT).

Three layers under test:

- the pure pieces: EWMA estimator semantics, publish hysteresis, the
  capacity model and proportional planner (min-share floor, determinism,
  mixed-swarm veto), BDP-derived transport parameters;
- bit-parity: a 4-worker galaxy with adaptive (non-uniform) partitioning
  produces EXACTLY the bytes of the uniform butterfly on codec "none" —
  re-partitioning is a transport decision, not a numerics change (the
  group-order accumulation in tcp.py is what makes this hold);
- the closed loop: a chaos-straggled worker (subprocess, because the chaos
  plane is per-process) loses part share within two rounds of measurement.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from opendiloco_tpu.diloco import linkstate
from opendiloco_tpu.diloco.backend import PeerProgress
from opendiloco_tpu.diloco.rendezvous import RendezvousServer
from opendiloco_tpu.diloco.tcp import TcpBackend

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SLOW, FAST = 25e6, 100e6


# -- estimator ---------------------------------------------------------------


def test_ewma_first_sample_then_convergence():
    est = linkstate.LinkEstimator("me", alpha=0.5)
    est.observe_send("p", 1 << 20, 1.0)
    assert est.bps_to("p") == pytest.approx(float(1 << 20))
    for _ in range(20):
        est.observe_send("p", 3 << 20, 1.0)
    assert est.bps_to("p") == pytest.approx(float(3 << 20), rel=0.01)
    est.observe_rtt("p", 0.004)
    assert est.rtt_to("p") == pytest.approx(0.004)


def test_rate_regression_removes_fixed_overhead():
    """Mixed transfer sizes toward one peer (the adaptive regime) must
    recover the true link rate even when every transfer pays a large
    fixed cost (RTT, scheduler stall): elapsed = overhead + bytes/rate.
    The naive bytes/elapsed figure would call a 1 MB transfer on this
    link ~9 MB/s and an 8 MB one ~36 MB/s — the spiral that starves
    whichever worker the planner shrinks first."""
    est = linkstate.LinkEstimator("me", alpha=0.3)
    rate, overhead = 50e6, 0.1
    for _ in range(10):
        for nb in (1 << 20, 4 << 20, 8 << 20):
            est.observe_send("p", nb, overhead + nb / rate)
    assert est.bps_to("p") == pytest.approx(rate, rel=0.05)


def test_tiny_samples_rejected():
    # a 2 KB control frame measures the syscall, not the link
    est = linkstate.LinkEstimator("me")
    est.observe_send("p", 2048, 0.001)
    est.observe_send("p", 1 << 20, 0.0)
    assert est.bps_to("p") is None


def test_seed_never_overrides_real_samples():
    est = linkstate.LinkEstimator("me")
    est.observe_send("p", 1 << 20, 1.0)
    est.seed("p", 999e6, 0.5)
    assert est.bps_to("p") == pytest.approx(float(1 << 20))
    # rtt had no real sample, so the probe's figure is accepted
    assert est.rtt_to("p") == pytest.approx(0.5)
    assert not est.needs_probe("p")
    est2 = linkstate.LinkEstimator("me")
    assert est2.needs_probe("p")
    est2.seed("p", 50e6, 0.002)
    assert not est2.needs_probe("p")
    assert est2.bps_to("p") == pytest.approx(50e6)


def test_publish_hysteresis(monkeypatch):
    monkeypatch.delenv("ODTP_LINK_HYST", raising=False)  # default 0.25
    est = linkstate.LinkEstimator("me", alpha=1.0)
    est.observe_send("p", 100_000_000, 1.0)
    assert est.publish()["peers"]["p"]["bps"] == pytest.approx(1e8)
    # 10% drift: published value must NOT move (plans stay stable)
    est.observe_send("p", 110_000_000, 1.0)
    assert est.publish()["peers"]["p"]["bps"] == pytest.approx(1e8)
    # 100% drift: published value follows the EWMA
    est.observe_send("p", 200_000_000, 1.0)
    assert est.publish()["peers"]["p"]["bps"] == pytest.approx(2e8)


def test_merge_remote_version_gate():
    est = linkstate.LinkEstimator("w0")
    est.merge_remote("w1", {"v": linkstate.LINK_VEC_VERSION, "peers": {}})
    est.merge_remote("w2", {"v": 99, "peers": {}})
    est.merge_remote("w3", "not-a-dict")
    est.merge_remote("w0", {"v": linkstate.LINK_VEC_VERSION, "peers": {}})
    mat = est.matrix()
    assert "w1" in mat and "w2" not in mat and "w3" not in mat
    assert "w0" in mat  # own vector always present


# -- capacity model + planner ------------------------------------------------


def _vec(peers):
    return {"v": linkstate.LINK_VEC_VERSION, "peers": peers}


def _member(pid, peers):
    return {"peer_id": pid, "progress": {"links": _vec(peers)}}


def _skewed_group(n=4, slow=SLOW, fast=FAST):
    """worker-0's links (both directions) run at ``slow``; all others at
    ``fast`` — the canonical 4:1 WAN-straggler galaxy."""
    ids = [f"worker-{i}" for i in range(n)]
    group = []
    for i, pid in enumerate(ids):
        peers = {}
        for j, qid in enumerate(ids):
            if i == j:
                continue
            peers[qid] = {"bps": slow if (i == 0 or j == 0) else fast,
                          "rtt_ms": 2.0}
        group.append(_member(pid, peers))
    return group


def test_group_capacities_min_of_egress_and_ingress():
    caps = linkstate.group_capacities(_skewed_group())
    assert caps == pytest.approx([SLOW, FAST, FAST, FAST])


def test_group_capacities_mixed_swarm_vetoes():
    group = _skewed_group()
    # a member not speaking the link protocol forces uniform for everyone
    assert linkstate.group_capacities(
        group[:3] + [{"peer_id": "worker-3", "progress": {}}]
    ) is None
    bad_version = dict(group[3])
    bad_version["progress"] = {"links": {"v": 99, "peers": {}}}
    assert linkstate.group_capacities(group[:3] + [bad_version]) is None


def test_group_capacities_unknowns_fill_with_median():
    # only worker-1 has measured anything (50 MB/s toward worker-0):
    # worker-2 is unknown and must get the neutral median, not zero
    group = [
        _member("worker-0", {}),
        _member("worker-1", {"worker-0": {"bps": 50e6, "rtt_ms": 1.0}}),
        _member("worker-2", {}),
    ]
    caps = linkstate.group_capacities(group)
    assert caps == pytest.approx([50e6, 50e6, 50e6])
    # nobody has measured anything: uniform (None), not divide-by-zero
    assert linkstate.group_capacities(
        [_member(f"worker-{i}", {}) for i in range(3)]
    ) is None


def test_plan_shares_proportional_and_floored(monkeypatch):
    monkeypatch.delenv("ODTP_LINK_MIN_SHARE", raising=False)  # default 0.25
    assert linkstate.plan_shares([1.0, 1.0, 1.0, 1.0]) == pytest.approx(
        [0.25] * 4
    )
    shares = linkstate.plan_shares([SLOW, FAST, FAST, FAST])
    assert shares == pytest.approx([25 / 325, 100 / 325, 100 / 325, 100 / 325])
    # extreme skew: the floor (0.25 of the uniform 1/4) pins the slow peer
    shares = linkstate.plan_shares([1e3, FAST, FAST, FAST])
    assert shares[0] == pytest.approx(0.0625)
    assert sum(shares) == pytest.approx(1.0)
    assert shares[1:] == pytest.approx([(1.0 - 0.0625) / 3] * 3)
    # degenerate inputs fall back to uniform
    assert linkstate.plan_shares([0.0, 0.0]) == pytest.approx([0.5, 0.5])
    assert linkstate.plan_shares([7.0]) == [1.0]


def test_plan_bounds_deterministic_from_fixed_matrix():
    group = _skewed_group()
    total = 524288  # the chaos test's 2^21-element array / uniform part
    b1 = linkstate.plan_bounds(total, group)
    b2 = linkstate.plan_bounds(total, group)
    assert b1 is not None
    np.testing.assert_array_equal(b1, b2)
    assert b1[0] == 0 and b1[-1] == total
    assert np.all(np.diff(b1) >= 0)
    # interior bounds land on the 1024-element quantum grid
    assert all(int(b) % 1024 == 0 for b in b1[:-1])
    shares = linkstate.shares_of(b1, total)
    assert shares[0] < 0.25 - 0.05  # bytes moved off the slow link
    assert max(shares) > 0.25
    assert sum(shares) == pytest.approx(1.0, abs=0.01)
    assert len(linkstate.plan_hash(b1)) == 12
    assert linkstate.plan_hash(b1) == linkstate.plan_hash(b1.copy())
    uniform = np.linspace(0, total, 5).astype(np.int64)
    assert linkstate.plan_hash(b1) != linkstate.plan_hash(uniform)


def test_plan_bounds_uniform_fallbacks():
    group = _skewed_group()
    # tiny buffers (barrier probes) must stay bit-stable: uniform
    assert linkstate.plan_bounds(1000, group) is None
    assert linkstate.plan_bounds(524288, group[:1]) is None
    # mixed swarm: veto propagates up
    assert linkstate.plan_bounds(
        524288, group[:3] + [{"peer_id": "worker-3", "progress": {}}]
    ) is None


# -- BDP-derived transport parameters ----------------------------------------


def test_stripes_for_bdp():
    # 1 GB/s x 50 ms = 50 MB BDP -> 12 x 4 MiB windows, clamped to max_streams
    assert linkstate.stripes_for(64 << 20, 1e9, 0.05, max_streams=8) == 8
    assert linkstate.stripes_for(64 << 20, 1e9, 0.05, max_streams=32) == 12
    # never more stripes than MBs of payload
    assert linkstate.stripes_for(1 << 20, 1e9, 0.05, max_streams=8) == 1
    # LAN: BDP under one window -> a single stream suffices
    assert linkstate.stripes_for(64 << 20, 100e6, 0.001, max_streams=8) == 1
    assert linkstate.stripes_for(64 << 20, 0.0, 0.05) == 1


def test_chunk_elems_for_clamps():
    assert linkstate.chunk_elems_for(0.0, 0.01, 1234) == 1234
    # a thin link never shrinks chunks below the static default (smaller
    # chunks only multiply per-chunk overhead)
    assert linkstate.chunk_elems_for(1e6, 0.001, 2 << 20) == 2 << 20
    # a fat link grows chunks toward one BDP: 1 GB/s x 20 ms = 20 MB
    assert linkstate.chunk_elems_for(1e9, 0.02, 2 << 20) == int(2e7) // 4
    # ... capped at 32 MiB of payload
    assert linkstate.chunk_elems_for(1e12, 1.0, 2 << 20) == (32 << 20) // 4


def test_chunk_elems_for_codec_align():
    """Chunk sizes snap DOWN to the codec's chunk_align so pipeline chunk
    boundaries stay on block grids (blockwise4bit packs nibbles per 4096
    block; a misaligned boundary would change the block grid and break
    chunked/whole bit-parity)."""
    # 1 GB/s x 20 ms = 20 MB -> 5e6 elems; 5e6 % 4096 != 0 -> rounds down
    ce = linkstate.chunk_elems_for(1e9, 0.02, 2 << 20, align=4096)
    assert ce == (int(2e7) // 4) - (int(2e7) // 4) % 4096
    assert ce % 4096 == 0 and ce > 0
    # align=1 (default) leaves historic values untouched
    assert linkstate.chunk_elems_for(1e9, 0.02, 2 << 20) == int(2e7) // 4
    # never rounds below align itself, even when the fallback is tiny
    assert linkstate.chunk_elems_for(0.0, 0.01, 100, align=4096) == 4096
    # already-aligned results pass through unchanged
    assert linkstate.chunk_elems_for(0.0, 0.01, 8192, align=4096) == 8192


def test_hedge_deadline(monkeypatch):
    monkeypatch.delenv("ODTP_LINK_HEDGE_FACTOR", raising=False)  # default 3
    d = linkstate.hedge_deadline_s(8 << 20, 100e6, 0.002, 4)
    expected = 3.0 * (8 << 20) * 4 / 100e6 + 2 * 0.002 + 0.25
    assert d == pytest.approx(expected)
    assert linkstate.hedge_deadline_s(8 << 20, 0.0, 0.002, 4) == 0.0
    monkeypatch.setenv("ODTP_LINK_HEDGE_FACTOR", "0")
    assert linkstate.hedge_deadline_s(8 << 20, 100e6, 0.002, 4) == 0.0


# -- 4-worker galaxy: adaptive vs uniform bit-parity -------------------------


@pytest.fixture
def rendezvous():
    server = RendezvousServer(host="127.0.0.1", port=0).start_in_thread()
    yield server
    server.stop()


def _make_backends(rendezvous, n, **kwargs):
    return [
        TcpBackend(
            [rendezvous.address],
            peer_id=f"worker-{i}",
            matchmaking_time=kwargs.pop("matchmaking_time", 2.0),
            **kwargs,
        )
        for i in range(n)
    ]


def _concurrent_allreduce(backends, arrays_per_peer, timeout=60.0):
    results = [None] * len(backends)
    errors = []

    def run(i):
        try:
            results[i] = backends[i].all_reduce(
                arrays_per_peer[i], timeout=timeout
            )
        except Exception as e:  # pragma: no cover - surfaced via assert
            errors.append((i, e))

    threads = [
        threading.Thread(target=run, args=(i,)) for i in range(len(backends))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout + 30)
    assert not errors, errors
    return results


def _peer_arrays(n_peers, seed=31):
    # 123k elements: big enough that plan_bounds doesn't take the tiny-buffer
    # uniform exit (>= n * quantum * 4); odd total so parts have ragged tails
    out = []
    for rank in range(n_peers):
        rng = np.random.default_rng(seed + rank)
        out.append([
            rng.standard_normal(120_001).astype(np.float32),
            rng.standard_normal((3, 1024)).astype(np.float32),
        ])
    return out


def test_adaptive_bit_identical_to_uniform(rendezvous, monkeypatch):
    """The acceptance gate: with codec "none" and a fixed seed, the adaptive
    (non-uniform, worker-0-slow) partition reduces to EXACTLY the bytes of
    the uniform butterfly, while the health ledger shows the skewed plan."""
    monkeypatch.delenv("ODTP_LINK_ADAPT", raising=False)
    n = 4
    ids = [f"worker-{i}" for i in range(n)]
    arrays = _peer_arrays(n)
    results, shares = {}, None
    for mode in ("uniform", "adaptive"):
        backends = _make_backends(
            rendezvous, n, compression="none",
            link_adapt=(mode == "adaptive"),
        )
        try:
            if mode == "adaptive":
                # seed the worker-0-slow matrix, then push each worker's
                # link vector to the daemon so the join_group snapshot --
                # the planner's only input -- carries it
                for i, b in enumerate(backends):
                    for j, pid in enumerate(ids):
                        if j == i:
                            continue
                        b.links.seed(
                            pid, SLOW if (i == 0 or j == 0) else FAST, 0.002
                        )
                    b.report_progress(
                        PeerProgress(ids[i], 0, 0, 0.0, time.time())
                    )
            results[mode] = _concurrent_allreduce(backends, arrays)
            if mode == "adaptive":
                shares = backends[0].last_round_health.get("link_shares")
                plans = {
                    b.last_round_health.get("link_plan") for b in backends
                }
                assert len(plans) == 1, plans  # one galaxy, one plan
        finally:
            for b in backends:
                b.close()

    # the plan really was non-uniform (otherwise parity is vacuous)
    assert shares is not None and len(shares) == n
    assert shares[0] < 0.25 - 0.05, shares
    assert max(shares) > 0.25, shares
    assert sum(shares) == pytest.approx(1.0, abs=0.01)

    # ... and bit-parity holds anyway, for every peer and every array
    for (u_out, u_n), (a_out, a_n) in zip(
        results["uniform"], results["adaptive"]
    ):
        assert u_n == a_n == n
        for ua, aa in zip(u_out, a_out):
            np.testing.assert_array_equal(ua, aa)


# -- closed loop: chaos straggler loses part share ---------------------------

_WORKER_SRC = """
import json, sys, time
import numpy as np
from opendiloco_tpu.diloco.backend import PeerProgress
from opendiloco_tpu.diloco.tcp import TcpBackend

addr, rank, n, min_rounds, max_rounds = sys.argv[1], *map(int, sys.argv[2:6])
b = TcpBackend(
    [addr], peer_id="worker-%d" % rank, compression="none",
    expect_peers=n, matchmaking_time=5.0,
)
b.report_progress(PeerProgress("worker-%d" % rank, 0, 0, 0.0, time.time()))
rng = np.random.default_rng(100 + rank)
arr = rng.standard_normal(1 << 21).astype(np.float32)  # 8 MB, 2 MB parts
history = []
for r in range(max_rounds):
    out, cnt = b.all_reduce([arr], timeout=90.0, epoch=r)
    assert cnt == n, (r, cnt)
    shares = b.last_round_health.get("link_shares")
    history.append(shares)
    # what a trainer does after a round, and synchronous: this round's link
    # estimates are at the daemon before this worker joins the next group
    # (the fire-and-forget announce raced a fixed sleep here)
    b.report_progress(
        PeerProgress("worker-%d" % rank, r + 1, 0, 0.0, time.time())
    )
    # every member holds the same plan, so all of them stop after the same
    # round: once the straggler's share has settled, not after a fixed count
    if r + 1 >= min_rounds and shares and shares[0] <= 0.15:
        break
print("SHARES " + json.dumps(history), flush=True)
b.close()
"""


def test_chaos_straggler_loses_share(rendezvous, tmp_path):
    """ODTP_CHAOS straggle on worker 0 only (its own process): the shared
    plan shifts bytes off the slow link as rounds are measured, and every
    member computes the identical plan each round. How many rounds the
    estimates need depends on what else the machine is running, so the
    workers go on until the share has settled (at most ``max_rounds``)."""
    n, min_rounds, max_rounds = 4, 4, 12
    procs, logs = [], []
    for rank in range(n):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["ODTP_LINK_ADAPT"] = "1"
        # RTT-only probes: a bandwidth probe would seed worker-0 "fast"
        # (probe frames dodge the chaos straggle) and slow convergence
        env["ODTP_LINK_PROBE_BYTES"] = "0"
        env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
        if rank == 0:
            env["ODTP_CHAOS"] = "seed=5;straggle_ms=60..60"
        out_f = open(tmp_path / f"w{rank}.out", "w+")
        err_f = open(tmp_path / f"w{rank}.err", "w+")
        logs.append((out_f, err_f))
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER_SRC,
             rendezvous.address, str(rank), str(n), str(min_rounds),
             str(max_rounds)],
            env=env, stdout=out_f, stderr=err_f, text=True,
        ))
    deadline = time.monotonic() + 180
    try:
        for p in procs:
            p.wait(timeout=max(1.0, deadline - time.monotonic()))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    histories = []
    for rank, (p, (out_f, err_f)) in enumerate(zip(procs, logs)):
        out_f.seek(0), err_f.seek(0)
        out, err = out_f.read(), err_f.read()
        out_f.close(), err_f.close()
        assert p.returncode == 0, f"worker {rank}:\n{err[-4000:]}"
        lines = [l for l in out.splitlines() if l.startswith("SHARES ")]
        assert lines, f"worker {rank} printed no SHARES line:\n{out[-2000:]}"
        histories.append(json.loads(lines[-1][len("SHARES "):]))

    # determinism: every member planned identical shares every round
    for h in histories[1:]:
        assert h == histories[0], histories
    hist = histories[0]
    assert all(s is not None and len(s) == n for s in hist), hist
    # round 1 has no measurements yet: the uniform fallback plan
    assert hist[0] == pytest.approx([0.25] * n)
    # measured rounds shifted bytes off worker 0 (group is sorted by
    # peer_id, so index 0 IS the straggler) and the workers stopped on it
    assert min_rounds <= len(hist) <= max_rounds, hist
    assert hist[-1][0] <= 0.15, hist
    assert sum(hist[-1]) == pytest.approx(1.0, abs=0.01)
