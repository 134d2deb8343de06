#!/usr/bin/env python
"""Outer-step benchmark: DCN butterfly all-reduce of model-sized
pseudo-gradients between N worker processes, per compression codec.

The reference logs outer all-reduce wall-clock but publishes no number
(BASELINE.md); this gives ours a measurable line:

    python scripts/bench_outer.py [--peers 2] [--model 150m] [--rounds 3]

Each peer is its own process (the real deployment shape -- one worker per
TPU-VM host); the rendezvous runs in the parent.

Because the bench box is shared and often single-core, raw ms/round is
noise across runs. Every codec row therefore also records the *loopback
TCP ceiling* measured immediately before it (same box, same moment) and a
normalized efficiency = effective GB/s / ceiling GB/s, which survives box
throttling. Results append incrementally to OUTER_BENCH.json at the repo
root so a killed run keeps whatever finished.
"""
import argparse
import json
import os
import socket
import statistics
import subprocess
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ALL_CODECS = [
    "none", "fp16", "scaled-fp16", "uniform8bit", "quantile8bit",
    "blockwise8bit", "blockwise4bit", "topk",
]
# the wire-plane benches (sweep, hetero, hier, compress, gossip, async) do
# no device work at all and their artifacts say so (HOST_ONLY); the
# in-process benches (boundary, stream) stamp the devices JAX gave them
from opendiloco_tpu.utils.device import HOST_ONLY  # noqa: E402
# tests point this somewhere disposable; default is the banked artifact
_OUT = os.environ.get("ODTP_OUTER_BENCH_OUT") or os.path.join(
    REPO, "OUTER_BENCH.json"
)
# --boundary mode banks here: outer-boundary (d2h/apply/h2d) wall-clock per
# outer_placement, the artifact the device-resident plane is judged against
_BOUNDARY_OUT = os.environ.get("ODTP_BOUNDARY_BENCH_OUT") or os.path.join(
    REPO, "BOUNDARY_BENCH.json"
)
# --hetero mode banks here: uniform-vs-adaptive medians on a bandwidth-skewed
# galaxy, the artifact the adaptive link layer (ODTP_LINK_ADAPT) is judged
# against
_HETERO_OUT = os.environ.get("ODTP_HETERO_BENCH_OUT") or os.path.join(
    REPO, "HETERO_BENCH.json"
)
# --stream mode banks here: blocking vs delayed-overlap vs streaming-eager
# outer-overhead-% of the inner phase, the artifact the staggered fragment
# scheduler (streaming_fragments x overlap_comm) is judged against
_STREAM_OUT = os.environ.get("ODTP_STREAM_BENCH_OUT") or os.path.join(
    REPO, "STREAM_BENCH.json"
)
# --compress mode banks here: sub-8-bit codec A/B on the 4:1-skewed galaxy
# (wire bytes + round time vs the uniform8bit baseline, error feedback on
# for the lossy sub-8-bit arms), the artifact the blockwise4bit/topk codecs
# are judged against
_COMPRESS_OUT = os.environ.get("ODTP_COMPRESS_BENCH_OUT") or os.path.join(
    REPO, "COMPRESS_BENCH.json"
)
# --hier mode banks here: flat butterfly vs two-level hierarchical reduce on
# an emulated 2-site galaxy (chaos wan_bps/wan_peers uplink shaping), the
# artifact the topology planner (ODTP_HIER) is judged against
_HIER_OUT = os.environ.get("ODTP_HIER_BENCH_OUT") or os.path.join(
    REPO, "HIER_BENCH.json"
)
# --gossip mode banks here: NoLoCo pairwise outer rounds vs the global
# butterfly all-reduce across growing single-host loopback galaxies, the
# artifact the barrier-free gossip plane (outer_mode="gossip") is judged
# against: per-round cost stays ~flat in galaxy size and wire bytes per
# worker per round are independent of N
_GOSSIP_OUT = os.environ.get("ODTP_GOSSIP_BENCH_OUT") or os.path.join(
    REPO, "GOSSIP_BENCH.json"
)
# --async mode banks here: lockstep vs bounded-staleness async gossip
# rounds on a heterogeneous (2x/4x inner-step skewed) loopback galaxy, the
# artifact the free-running round clock (ODTP_ASYNC_STALENESS) is judged
# against: lockstep aggregate tokens/s degrades toward the slowest worker,
# async holds near the sum of per-worker standalone rates
_ASYNC_OUT = os.environ.get("ODTP_ASYNC_BENCH_OUT") or os.path.join(
    REPO, "ASYNC_BENCH.json"
)


def expected_group(peers: int, group_cap: int) -> int:
    """Matchmade group size a healthy bench round must reach. The parent
    rejects peers % group_cap != 0, so capped groups are exactly the cap
    (a designed-but-solo remainder group would bench nothing)."""
    return group_cap or peers


def make_leaves(model: str, rank: int):
    """Model-shaped fp32 leaves, generated directly in fp32 (a float64
    intermediate at 1b scale costs 8 GB and minutes on one core).

    ``tiny:N`` is a synthetic model: one flat N-megabyte fp32 leaf, no jax
    or model-config import — the hetero/CI benches measure the wire plane,
    not leaf assembly, and worker startup should stay milliseconds."""
    if model.startswith("tiny:"):
        mb = float(model.split(":", 1)[1])
        rng = np.random.default_rng(rank)
        a = rng.standard_normal(max(1, int(mb * 1e6) // 4), dtype=np.float32)
        a *= 1e-3
        return [a]
    from opendiloco_tpu.models.hf_io import load_config
    from opendiloco_tpu.models.llama import shapes
    import jax

    cfg = load_config(model)
    rng = np.random.default_rng(rank)
    out = []
    for s in jax.tree.leaves(shapes(cfg)):
        a = rng.standard_normal(s.shape, dtype=np.float32)
        a *= 1e-3
        out.append(a)
    return out


def loopback_ceiling_gbps(nbytes: int = 1 << 30, chunk: int = 4 << 20) -> float:
    """Raw loopback TCP throughput right now, sender/receiver in two threads
    (sendall/recv_into release the GIL, so one process is enough and the
    timesharing penalty matches the 2-worker bench shape on a 1-core box)."""
    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    addr = srv.getsockname()

    def recv_all():
        conn, _ = srv.accept()
        with conn:
            buf = bytearray(chunk)
            got = 0
            while got < nbytes:
                n = conn.recv_into(buf, min(chunk, nbytes - got))
                if n == 0:
                    break
                got += n

    t = threading.Thread(target=recv_all)
    t.start()
    payload = b"\x5a" * chunk
    cli = socket.create_connection(addr)
    cli.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    sent = 0
    t0 = time.perf_counter()
    with cli:
        while sent < nbytes:
            cli.sendall(payload[: min(chunk, nbytes - sent)])
            sent += len(payload[: min(chunk, nbytes - sent)])
    t.join()
    dt = time.perf_counter() - t0
    srv.close()
    return nbytes / dt / 1e9


def worker_main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--rendezvous", required=True)
    ap.add_argument("--rank", type=int, required=True)
    ap.add_argument("--model", required=True)
    ap.add_argument("--compression", required=True)
    ap.add_argument("--rounds", type=int, required=True)
    ap.add_argument("--peers", type=int, required=True)
    ap.add_argument("--timeout", type=float, default=600.0)
    ap.add_argument("--sweep-start", type=float, default=0.0)
    ap.add_argument("--group-cap", type=int, default=0)
    ap.add_argument("--pipeline", default="1")
    ap.add_argument("--ef", action="store_true")
    args = ap.parse_args()

    # the pipelined/serial choice must agree across the whole group (the
    # two paths key their mailbox frames differently); the parent passes it
    # explicitly per sweep
    os.environ["ODTP_PIPELINE"] = args.pipeline
    # the bench sources its HEALTH accounting from the obs plane instead of
    # hand-rolled accumulators: arm it unconditionally (events stay
    # in-process unless ODTP_OBS_DIR is also set)
    os.environ.setdefault("ODTP_OBS", "bench")

    from opendiloco_tpu import obs
    from opendiloco_tpu.diloco.backend import PeerProgress
    from opendiloco_tpu.diloco.tcp import TcpBackend

    tr = obs.tracer()
    tr.set_identity(worker=args.rank, role="bench")

    data = make_leaves(args.model, args.rank)
    ef = None
    if args.ef:
        # production EF protocol around every wire launch: residual folded
        # into the round's pseudo-gradient at prepare, roundtrip error
        # adopted at commit (the residual-norm gauge lands in HEALTH)
        from opendiloco_tpu.diloco.compression import get_codec
        from opendiloco_tpu.diloco.error_feedback import ErrorFeedback

        ef = ErrorFeedback(get_codec(args.compression), len(data))
    # the window must cover the slowest peer's join on a box where all
    # peers contend for one core; 1 s split 8-peer runs into partial
    # groups. Under an egress cap the join frames also queue behind the
    # previous round's residual throttled bytes (8 peers at 100 Mbps
    # matchmade 6/8 with the uncapped window), so widen by the time a
    # part-sized residual takes to drain at the cap. Generosity is free:
    # the rendezvous closes the window EARLY once every live peer joined.
    window = max(2.0, 0.75 * args.peers)
    cap_bps = float(os.environ.get("ODTP_BULK_BANDWIDTH_BPS", 0) or 0)
    if cap_bps > 0:
        nbytes = sum(a.nbytes for a in data)
        window += min(60.0, 4.0 * nbytes / max(args.peers, 1) / cap_bps)
    backend = TcpBackend(
        [args.rendezvous],
        peer_id=f"bench-{args.rank}",
        compression=args.compression,
        matchmaking_time=window,
        # the bench KNOWS the swarm size: the rendezvous closes each
        # matchmaking window the instant all peers have joined, never
        # early on a stale registry view — this is what turned the old
        # "matchmade group N < peers" error rows into clean rounds.
        # (expect counts JOINERS, so it holds under --group-cap too: the
        # partition into capped groups happens at close.)
        expect_peers=args.peers,
    )
    # a worker that starts its round before the others register gets a SOLO
    # matchmaking group (n=1, no wire traffic -- a meaningless number); the
    # production loop gates rounds on peer progress, so the bench must too
    backend.report_progress(
        PeerProgress(f"bench-{args.rank}", 0, 0, 0.0, time.time())
    )
    # setup (jax import + model-sized leaf generation) serializes on a
    # 1-core box, so assembly time scales with the peer count; falling
    # through to a solo/partial round would bench nothing, so fail loudly
    # instead (the parent records a diagnosable worker-failure row).
    # Only progress reported AFTER this sweep started counts: a previous
    # killed sweep's workers never unregistered, and their stale entries
    # (same bench-N ids, up to PEER_TTL old) would otherwise satisfy the
    # count while the real peers are still importing jax
    def fresh_peers():
        return sum(
            1
            for pr in backend.peer_progress()
            if pr.timestamp >= args.sweep_start
        )

    deadline = time.time() + 60 + 60 * args.peers
    while fresh_peers() < args.peers and time.time() < deadline:
        time.sleep(0.3)
    assembled = fresh_peers()
    if assembled < args.peers:
        print(
            f"FATAL: only {assembled}/{args.peers} peers assembled before "
            "the deadline",
            flush=True,
        )
        backend.close()
        sys.exit(3)
    # one untimed warmup round: first-touch page-in of the model-sized
    # buffers (4.4 GB at 1b) plus codec scratch allocation dominate the
    # first round (measured 179 s vs 11 s steady-state at 1b); keep it out
    # of the timings entirely
    try:
        backend.barrier(timeout=args.timeout)
        backend.all_reduce(data, timeout=args.timeout, group_cap=args.group_cap)
    except Exception as e:
        print(f"FATAL: warmup round failed: {e}", flush=True)
        backend.close()
        sys.exit(3)

    times = []
    n = 0
    want = expected_group(args.peers, args.group_cap)

    def ctr(name: str) -> int:
        return int(tr.counters().get((name, ()), 0))

    # on a loaded 1-core box the peers drift apart across rounds (codec CPU
    # is serialized), so a matchmaking window that fit round 1 splits round
    # 3. Two mitigations, both deterministic across workers: an untimed
    # barrier before every timed round re-aligns the swarm, and a partial
    # group is first retried with a doubled window (every member of every
    # partial group sees n < want, so all retry in lockstep; skipped under
    # --group-cap where a capped group can't tell a split from a healthy
    # partition). A partial group that SURVIVES the retries is an ELASTIC
    # round: its average is correctly rescaled by the actual contributor
    # count, so it is recorded as data (group size + elastic flag), never
    # as an error row.
    while len(times) < args.rounds:
        try:
            backend.barrier(timeout=args.timeout)
        except Exception as e:
            print(f"FATAL: inter-round barrier failed: {e}", flush=True)
            backend.close()
            sys.exit(3)
        t0 = time.perf_counter()
        if ef is not None:
            # the copy + prepare are part of the arm's honest round cost:
            # production pays the residual add and the encode roundtrip on
            # the boundary path too
            pgs = [a.copy() for a in data]
            ef.prepare("bench", range(len(pgs)), pgs)
        else:
            pgs = data
        out, n = backend.all_reduce(
            pgs, timeout=args.timeout, group_cap=args.group_cap
        )
        if ef is not None:
            ef.commit("bench")
        t1 = time.perf_counter()
        dt = t1 - t0
        if n < want and not args.group_cap and ctr("bench_retries") < 3:
            tr.count("bench_retries")
            backend.matchmaking_time = min(backend.matchmaking_time * 2, 120.0)
            print(
                f"RETRY {ctr('bench_retries')}: group {n} < {want}, "
                f"window -> {backend.matchmaking_time:.1f}s",
                flush=True,
            )
            continue  # timing discarded; re-run this round
        if n < want:
            tr.count("bench_elastic_rounds")
        # accepted-round ledger lives in the trace: one span per timed
        # round, group size in the args (the HEALTH line reads these back)
        tr.add_span("bench/round", t0, t1, group=n)
        times.append(dt)
    timings = {
        k: (round(v, 3) if isinstance(v, float) else v)
        for k, v in getattr(backend, "last_round_timings", {}).items()
    }
    lrh = dict(getattr(backend, "last_round_health", {}) or {})
    backend.close()
    retries = ctr("bench_retries")
    if args.rank == 0:
        print(
            "RESULT " + " ".join(f"{t:.4f}" for t in times)
            + f" retries={retries} n={n}",
            flush=True,
        )
        print("TIMINGS " + json.dumps(timings), flush=True)
    # EVERY worker reports its round health (with group_cap only rank 0's
    # group would otherwise be visible); the parent aggregates these into
    # the row instead of classifying partial groups as errors. The values
    # come straight from the obs plane: per-round spans carry the group
    # sizes, counters carry retries/elastic, and snapshot() folds the
    # chaos plane's fault counters in first-class. Keys are unchanged, so
    # the parent parser and the banked OUTER_BENCH.json schema are too.
    snap = tr.snapshot()
    health = {
        "rank": args.rank,
        "group_sizes": [
            ev["args"]["group"] for ev in tr.events
            if ev["name"] == "bench/round"
        ],
        "elastic_rounds": ctr("bench_elastic_rounds"),
        "retries": retries,
    }
    # adaptive-transport fields, when the last round planned adaptively:
    # the hetero bench asserts on these (bytes shifted off the slow link)
    for k in ("link_plan", "link_shares"):
        if lrh.get(k) is not None:
            health[k] = lrh[k]
    # cumulative wire byte counters, WAN split included: the hier bench
    # sums these across workers and gates on the flat/hier WAN ratio (both
    # arms run the same round structure, so the ratio needs no per-round
    # normalization)
    for name in (
        "wire_tx_bytes", "wire_rx_bytes",
        "wire_tx_bytes_wan", "wire_rx_bytes_wan",
    ):
        health[name] = ctr(name)
    if lrh.get("hier") is not None:
        health["hier"] = lrh["hier"]
    faults = {
        dict(labels).get("kind", "?"): int(v)
        for (name, labels), v in snap["counters"].items()
        if name == "chaos_faults"
    }
    if faults:
        health["faults"] = faults
    # per-codec wire accounting (transport-side record_wire counters) and
    # the EF residual-norm gauge: the compress bench's acceptance reads
    # these back instead of re-deriving byte counts from codec math
    wire: dict = {}
    for (name, labels), v in snap["counters"].items():
        if name in ("outer_raw_bytes", "outer_wire_bytes"):
            codec = dict(labels).get("codec", "?")
            wire.setdefault(codec, {})[name.replace("outer_", "")] = int(v)
    for (name, labels), v in snap["gauges"].items():
        if name == "outer_compression_ratio":
            codec = dict(labels).get("codec", "?")
            wire.setdefault(codec, {})["ratio"] = round(float(v), 3)
    if wire:
        health["wire"] = wire
    efn = snap["gauges"].get(("ef_residual_norm", ()))
    if efn is not None:
        health["ef_residual_norm"] = round(float(efn), 6)
    print("HEALTH " + json.dumps(health), flush=True)


def _append_row(
    row: dict,
    out: str = "",
    ident_keys: tuple = ("model", "peers", "codec", "pipelined"),
) -> None:
    out = out or _OUT
    doc = {"rows": []}
    if os.path.exists(out):
        try:
            with open(out) as f:
                doc = json.load(f)
        except ValueError:
            pass
    # latest run wins: a re-run of one sweep replaces its old row instead
    # of stacking duplicates
    ident = lambda r: tuple(r.get(k) for k in ident_keys)
    doc["rows"] = [
        r for r in doc.setdefault("rows", []) if ident(r) != ident(row)
    ] + [row]
    doc["updated"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    doc.setdefault("host", {}).update(
        cores=os.cpu_count(), loadavg=round(os.getloadavg()[0], 2)
    )
    if out == _OUT:  # the wire sweep; boundary rows stamp themselves
        doc.update(HOST_ONLY)
    with open(out, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")


def _boundary_round_host(master, outer, params_dev, shardings, pg_bufs):
    """One host-placement outer boundary, staged exactly like the
    production path (diloco/optimizer.py blocking round): full-width f32
    D2H fetch, pseudo-gradient into persistent slot buffers, clone-then-
    rebind OuterSGD step, full f32 master H2D back into the params. The
    all-reduce itself is the wire plane's cost (OUTER_BENCH rows); here
    the averaged pseudo-gradient is taken as given (loopback identity).
    Returns (d2h_s, apply_s, h2d_s, master, outer, params_dev)."""
    import jax
    from opendiloco_tpu import native

    t0 = time.perf_counter()
    flat = [
        np.asarray(x, dtype=np.float32)
        for x in jax.device_get(list(params_dev))
    ]
    t1 = time.perf_counter()
    pg = [
        native.sub(m, d, out=b) for m, d, b in zip(master, flat, pg_bufs)
    ]
    # clone-then-rebind, as the live path must (serve-thread fetches hold
    # references to the published arrays) -- this double copy is exactly
    # what the device plane's donation deletes
    new_master = [m.copy() for m in master]
    new_outer = outer.clone()
    new_outer.step(new_master, pg)
    t2 = time.perf_counter()
    params_dev = [
        jax.device_put(m, s) for m, s in zip(new_master, shardings)
    ]
    jax.block_until_ready(params_dev)
    t3 = time.perf_counter()
    return t1 - t0, t2 - t1, t3 - t2, new_master, new_outer, params_dev


def _boundary_round_device(plane, params_dev):
    """One device-placement outer boundary: wire-width D2H of the fused
    pseudo-gradient, averaged-pg H2D, then ONE donated jit for the fused
    Nesterov apply + params <- master overwrite (no master ever crosses
    back to host). The stage split reaches one level into the plane so
    the H2D and the fused apply time separately --
    ``apply_average(avg, sync=params)`` is exactly these calls under the
    lock. Returns (d2h_s, apply_s, h2d_s, params_dev)."""
    import jax
    from opendiloco_tpu.diloco import outer_device as od

    t0 = time.perf_counter()
    host_pg, _, _ = plane.pseudo_grad(params_dev)
    d2h_s = time.perf_counter() - t0
    # untimed: materialize the "averaged" pseudo-gradient in host-owned
    # memory, as the backend's pooled reduce buffers would be -- feeding
    # the fetched views straight back would let device_put recognize
    # device-backed memory and skip the H2D copy production always pays
    host_pg = [np.array(a, np.float32) for a in host_pg]
    t1 = time.perf_counter()
    with plane.lock:
        plane._ensure_bufs()
        lr, mom = plane._scalars()
        avg_dev = plane._h2d(host_pg, None)
        jax.block_until_ready(avg_dev)
        t2 = time.perf_counter()
        new_m, new_b, new_p = od._apply_sync_fused(
            plane.masters, plane._sel(plane.bufs, None), avg_dev,
            list(params_dev), lr, mom,
            nesterov=plane.nesterov, has_mom=plane._has_mom,
        )
        jax.block_until_ready(new_p)
        plane.masters = list(new_m)
        if plane._has_mom:
            plane.bufs = list(new_b)
        params_dev = list(new_p)
    t3 = time.perf_counter()
    return d2h_s, t3 - t2, t2 - t1, params_dev


def boundary_main(args) -> None:
    """Host-vs-device outer-boundary sweep, in-process (the boundary has
    no wire component, so no peers/sockets): times d2h / apply / h2d per
    placement and codec and banks BOUNDARY_BENCH.json."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from opendiloco_tpu.diloco.outer_device import DeviceOuterPlane
    from opendiloco_tpu.diloco.outer_optimizer import OuterSGD
    from opendiloco_tpu.utils.device import device_stamp

    leaves = make_leaves(args.model, 0)
    nbytes = sum(a.nbytes for a in leaves)
    # a shared box's CPU-steal spikes can poison single rounds by 4x, so
    # the headline number is a MEDIAN over enough rounds to outvote them
    rounds = max(args.rounds, 9)
    print(
        f"boundary bench: model {args.model} ({nbytes / 1e6:.0f} MB fp32), "
        f"{rounds} rounds/config, backend={jax.default_backend()}"
    )
    sh = SingleDeviceSharding(jax.devices()[0])
    shardings = [sh] * len(leaves)

    class _Shim:  # DeviceOuterPlane only reads state_shardings["params"]
        state_shardings = {"params": shardings}

    host_total = 0.0
    # the host boundary has no device pre-cast (its codec work happens in
    # the wire plane, not at the boundary), so it is measured ONCE; every
    # device codec row records its speedup against that one baseline
    for placement, codec in [("host", "none")] + [
        ("device", c) for c in args.codecs.split(",")
    ]:
        params_dev = [jax.device_put(a, sh) for a in leaves]
        stages: list[tuple] = []
        if placement == "host":
            master = [a.copy() for a in leaves]
            outer = OuterSGD(0.7, 0.9, nesterov=True)
            pg_bufs = [np.empty(m.shape, np.float32) for m in master]
            for r in range(rounds + 1):  # round 0 is untimed warmup
                d2h, ap, h2d, master, outer, params_dev = (
                    _boundary_round_host(
                        master, outer, params_dev, shardings, pg_bufs
                    )
                )
                if r:
                    stages.append((d2h, ap, h2d))
        else:
            plane = DeviceOuterPlane(
                _Shim(), params_dev, lr=0.7, momentum=0.9,
                nesterov=True, compression=codec,
            )
            for r in range(rounds + 1):
                d2h, ap, h2d, params_dev = _boundary_round_device(
                    plane, params_dev
                )
                if r:
                    stages.append((d2h, ap, h2d))
        totals = sorted(sum(s) for s in stages)
        # MEDIAN, not a trimmed mean: a shared box's CPU-steal spikes
        # (measured 4x on single rounds) survive trimming but not the
        # median; the mean is still recorded for reference
        total = statistics.median(totals)
        med = lambda i: statistics.median(s[i] for s in stages)
        row = {
            "model": args.model, "mb_fp32": round(nbytes / 1e6),
            "placement": placement, "codec": codec, "rounds": rounds,
            "d2h_ms": round(med(0) * 1e3, 1),
            "apply_ms": round(med(1) * 1e3, 1),
            "h2d_ms": round(med(2) * 1e3, 1),
            "total_ms": round(total * 1e3, 1),
            "mean_total_ms": round(statistics.fmean(totals) * 1e3, 1),
            "best_total_ms": round(totals[0] * 1e3, 1),
            "rounds_ms": [round(sum(s) * 1e3, 1) for s in stages],
            **device_stamp(),
        }
        note = ""
        if placement == "host":
            host_total = total
        elif host_total:
            row["speedup_vs_host"] = round(host_total / total, 3)
            note = f"  {row['speedup_vs_host']:4.2f}x vs host"
        _append_row(
            row, out=_BOUNDARY_OUT,
            ident_keys=("model", "placement", "codec"),
        )
        print(
            f"{placement:>7}[{codec}]: d2h {row['d2h_ms']:7.1f}  "
            f"apply {row['apply_ms']:7.1f}  h2d {row['h2d_ms']:7.1f}  "
            f"total {row['total_ms']:7.1f} ms{note}"
        )


def _parse_bandwidth(spec: str) -> float:
    """'1gbps' / '100mbps' / '12500000' (bytes/s) -> bytes/s; 0 = unlimited."""
    s = spec.strip().lower()
    if s.endswith("gbps"):
        return float(s[:-4]) * 1e9 / 8
    if s.endswith("mbps"):
        return float(s[:-4]) * 1e6 / 8
    return float(s or 0)


def _hetero_sweep(
    args, server, cap_bps: float, skew: float, adapt: bool, warm: int,
    rounds: int, base_env: dict, compression: str = "none", ef: bool = False,
) -> tuple:
    """One uniform-or-adaptive pass over the skewed galaxy. Every worker's
    egress is token-bucketed at ``cap_bps``; worker 0 is additionally capped
    at ``cap_bps / skew`` through the chaos plane (the LOWER cap binds), so
    the galaxy has one 4:1-slow link without any kernel-level shaping.
    Returns (per-round seconds AFTER the ``warm`` learning rounds,
    rank-0 HEALTH dict)."""
    nbytes = sum(a.nbytes for a in make_leaves(args.model, 0))
    round_timeout = max(60.0, 20.0 * nbytes * 2 / (cap_bps / skew))
    procs = []
    for i in range(args.peers):
        env = dict(base_env)
        env["ODTP_BULK_BANDWIDTH_BPS"] = str(int(cap_bps))
        env["ODTP_LINK_ADAPT"] = "1" if adapt else "0"
        if i == 0:
            env["ODTP_CHAOS"] = f"egress_bps={int(cap_bps / skew)}"
        procs.append(subprocess.Popen(
            [
                sys.executable, os.path.abspath(__file__), "--worker",
                "--rendezvous", server.address, "--rank", str(i),
                "--model", args.model, "--compression", compression,
                "--rounds", str(warm + rounds),
                "--peers", str(args.peers),
                "--timeout", str(round_timeout),
                "--sweep-start", str(time.time()),
                "--group-cap", "0", "--pipeline", "1",
            ] + (["--ef"] if ef else []),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        ))
    proc_timeout = (warm + rounds + 2) * round_timeout + 120.0
    try:
        outs = [p.communicate(timeout=proc_timeout)[0] for p in procs]
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        for p in procs:
            try:
                p.communicate(timeout=10)
            except Exception:
                pass
        raise SystemExit(f"hetero sweep (adapt={adapt}) timed out")
    if any(p.returncode for p in procs):
        detail = [" | ".join(o.splitlines()[-3:])[-400:] for o in outs]
        raise SystemExit(
            f"hetero sweep (adapt={adapt}) worker failure: {detail}"
        )
    line = next(
        l for o in outs for l in o.splitlines() if l.startswith("RESULT")
    )
    times = [float(x) for x in line.split()[1:] if "=" not in x]
    health = next(
        (
            json.loads(l.split(None, 1)[1])
            for o in outs for l in o.splitlines()
            if l.startswith("HEALTH ") and '"rank": 0' in l
        ),
        {},
    )
    return times[warm:], health


def hetero_main(args) -> None:
    """Bandwidth-skewed galaxy A/B: the same chaos-emulated 4:1-slow link,
    uniform butterfly vs adaptive (ODTP_LINK_ADAPT) partitioning. Banks
    HETERO_BENCH.json with both medians and the speedup; exits nonzero if
    the full run regresses below the 1.2x acceptance line.

    The arithmetic the adaptive plan exploits: a slow worker's push-phase
    egress (everyone else's parts) is irreducible, but its fan-back egress
    is proportional to its OWN part — shrinking that part moves the
    fan-back bytes onto fast links, cutting the slow worker's per-round
    egress from 2*(1-1/n) to (1-s0) + (n-1)*s0 of the payload.
    """
    from opendiloco_tpu.diloco.rendezvous import RendezvousServer

    skew = 4.0
    if args.selftest:
        args.peers, args.model, rounds, warm = 4, "tiny:8", 2, 1
        cap_bps = 64e6
        out_path = os.environ.get("ODTP_HETERO_BENCH_OUT") or os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "HETERO_BENCH.selftest.json"
        )
    else:
        args.peers, args.model = 8, "tiny:32"
        rounds, warm = max(args.rounds, 5), 2
        # low enough that the emulated link time dominates the 1-core
        # box's scheduler noise (at 128 MB/s the CPU-starvation wait is
        # additive and similar for every worker, compressing the 4:1
        # bandwidth ratio out of the per-transfer measurements)
        cap_bps = 64e6
        out_path = _HETERO_OUT
    nbytes = sum(a.nbytes for a in make_leaves(args.model, 0))
    print(
        f"hetero bench: {args.peers} peers, {nbytes / 1e6:.0f} MB fp32, "
        f"egress {cap_bps * 8 / 1e6:.0f} Mbps/worker, worker 0 at "
        f"1/{skew:.0f} of that, {rounds} measured rounds "
        f"(+{warm} learning)"
    )
    base_env = dict(os.environ)
    base_env["PYTHONPATH"] = REPO + os.pathsep + base_env.get("PYTHONPATH", "")

    results = {}
    server = RendezvousServer(host="127.0.0.1", port=0).start_in_thread()
    try:
        for adapt in (False, True):
            mode = "adaptive" if adapt else "uniform"
            times, health = _hetero_sweep(
                args, server, cap_bps, skew, adapt, warm, rounds, base_env
            )
            results[mode] = {
                "rounds_s": [round(t, 3) for t in times],
                "median_s": round(statistics.median(times), 3),
                "best_s": round(min(times), 3),
                **(
                    {"link_shares": health["link_shares"]}
                    if "link_shares" in health else {}
                ),
            }
            print(
                f"{mode:>9}: median {results[mode]['median_s'] * 1e3:7.0f} "
                f"ms/round  rounds {results[mode]['rounds_s']}"
            )
    finally:
        server.stop()

    speedup = round(
        results["uniform"]["median_s"] / results["adaptive"]["median_s"], 3
    )
    doc = {
        "peers": args.peers,
        "model": args.model,
        "mb_fp32": round(nbytes / 1e6),
        "bandwidth_mbps": round(cap_bps * 8 / 1e6),
        "skew": skew,
        "selftest": bool(args.selftest),
        "uniform": results["uniform"],
        "adaptive": results["adaptive"],
        "speedup": speedup,
        "updated": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "host": {
            "cores": os.cpu_count(), "loadavg": round(os.getloadavg()[0], 2)
        },
        **HOST_ONLY,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"speedup {speedup:.2f}x (banked {out_path})")
    shares = results["adaptive"].get("link_shares")
    if shares and shares[0] >= 1.0 / args.peers:
        raise SystemExit(
            f"adaptive sweep never shifted bytes off worker 0: {shares}"
        )
    if not args.selftest and speedup < 1.2:
        raise SystemExit(
            f"hetero speedup {speedup:.2f}x below the 1.2x acceptance line"
        )


def _hier_galaxy(peers: int) -> tuple[list[list[int]], list[int], str, str]:
    """The emulated 2-site galaxy layout for ``peers`` workers: ranks split
    into two equal sites, rank 0 of each half is the preferred aggregator.
    Returns (sites, aggregator ranks, ODTP_SITES spec, ODTP_HIER_AGG spec)
    over the bench's ``bench-N`` peer ids."""
    half = peers // 2
    sites = [list(range(half)), list(range(half, peers))]
    agg_ranks = [s[0] for s in sites]
    site_spec = ";".join(
        "|".join(f"bench-{r}" for r in s) for s in sites
    )
    agg_spec = "|".join(f"bench-{r}" for r in agg_ranks)
    return sites, agg_ranks, site_spec, agg_spec


def _hier_sweep(
    args, server, hier: bool, nic_bps: float, agg_wan_bps: float,
    member_wan_bps: float, warm: int, rounds: int, base_env: dict,
) -> tuple[list, list]:
    """One flat-or-hierarchical pass over the emulated 2-site galaxy.

    Every worker's NIC is token-bucketed at ``nic_bps``; frames to the
    OTHER site additionally drain a per-worker WAN bucket (chaos
    wan_bps/wan_peers) — fat for the two aggregator ranks, thin for the
    rest, the clusters-of-clusters shape where only the site uplink hosts
    have real WAN bandwidth. Both arms run with ODTP_SITES set so the
    flat arm's WAN byte accounting is topology-aware too; only ODTP_HIER
    differs. Returns (per-round seconds after ``warm`` learning rounds,
    ALL workers' HEALTH dicts — WAN bytes must sum over every worker)."""
    sites, agg_ranks, site_spec, agg_spec = _hier_galaxy(args.peers)
    nbytes = sum(a.nbytes for a in make_leaves(args.model, 0))
    round_timeout = max(60.0, 20.0 * nbytes * 2 / member_wan_bps)
    procs = []
    for i in range(args.peers):
        env = dict(base_env)
        env["ODTP_BULK_BANDWIDTH_BPS"] = str(int(nic_bps))
        env["ODTP_LINK_ADAPT"] = "0"
        env["ODTP_HIER"] = "1" if hier else "0"
        env["ODTP_SITES"] = site_spec
        env["ODTP_HIER_AGG"] = agg_spec
        other = next(s for s in sites if i not in s)
        wan_bps = agg_wan_bps if i in agg_ranks else member_wan_bps
        env["ODTP_CHAOS"] = (
            f"wan_bps={int(wan_bps)};wan_peers="
            + "|".join(f"bench-{r}" for r in other)
        )
        procs.append(subprocess.Popen(
            [
                sys.executable, os.path.abspath(__file__), "--worker",
                "--rendezvous", server.address, "--rank", str(i),
                "--model", args.model, "--compression", "none",
                "--rounds", str(warm + rounds),
                "--peers", str(args.peers),
                "--timeout", str(round_timeout),
                "--sweep-start", str(time.time()),
                "--group-cap", "0", "--pipeline", "1",
            ],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env,
        ))
    proc_timeout = (warm + rounds + 2) * round_timeout + 120.0
    try:
        outs = [p.communicate(timeout=proc_timeout)[0] for p in procs]
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        for p in procs:
            try:
                p.communicate(timeout=10)
            except Exception:
                pass
        raise SystemExit(f"hier sweep (hier={hier}) timed out")
    if any(p.returncode for p in procs):
        detail = [" | ".join(o.splitlines()[-3:])[-400:] for o in outs]
        raise SystemExit(f"hier sweep (hier={hier}) worker failure: {detail}")
    line = next(
        l for o in outs for l in o.splitlines() if l.startswith("RESULT")
    )
    times = [float(x) for x in line.split()[1:] if "=" not in x]
    healths = [
        json.loads(l.split(None, 1)[1])
        for o in outs for l in o.splitlines()
        if l.startswith("HEALTH ")
    ]
    return times[warm:], healths


def hier_main(args) -> None:
    """Hierarchical galaxy A/B: the same emulated 2-site topology (fat
    intra-site links, thin per-worker WAN uplinks, fat uplinks only on the
    two aggregator hosts), flat butterfly vs the planner's two-level round
    (ODTP_HIER). Banks HIER_BENCH.json with both arms' medians, the summed
    WAN egress, and the reduction ratio; the full run exits nonzero below
    the 3x WAN-reduction acceptance line or if the round time regressed.

    The arithmetic the two-level round exploits: flat, every worker ships
    its slices for all cross-site owners plus its fan-back part over the
    WAN (group total ~= the full payload per site per DIRECTION twice);
    hierarchical, only the two aggregators touch the WAN, exchanging one
    site-summed butterfly = ~2/S of the payload each way at S sites — a
    ~peers/2-per-site galaxy cuts WAN bytes ~(peers/sites)x (4x at 2x4),
    and routing them over the fat aggregator uplinks wins the round time
    too."""
    from opendiloco_tpu.diloco.rendezvous import RendezvousServer

    if args.selftest:
        args.peers, args.model, rounds, warm = 4, "tiny:8", 2, 1
        nic_bps, agg_wan, member_wan = 64e6, 16e6, 4e6
        out_path = os.environ.get("ODTP_HIER_BENCH_OUT") or os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "HIER_BENCH.selftest.json"
        )
        # a 2x2 galaxy's theoretical WAN cut is only 2x (n/sites); gate
        # leniently — the selftest checks the machinery, not the headline
        wan_floor = 1.5
    else:
        args.peers, args.model = 8, "tiny:32"
        rounds, warm = max(args.rounds, 3), 1
        nic_bps, agg_wan, member_wan = 64e6, 8e6, 2e6
        out_path = _HIER_OUT
        wan_floor = 3.0
    sites, agg_ranks, site_spec, _ = _hier_galaxy(args.peers)
    nbytes = sum(a.nbytes for a in make_leaves(args.model, 0))
    # warmup + learning + measured: every worker runs this many all-reduce
    # rounds, so cumulative WAN counters normalize to per-round by it
    total_rounds = 1 + warm + rounds
    print(
        f"hier bench: {args.peers} peers in 2 sites {sites}, "
        f"{nbytes / 1e6:.0f} MB fp32, NIC {nic_bps * 8 / 1e6:.0f} Mbps, WAN "
        f"{agg_wan * 8 / 1e6:.0f} Mbps (aggregators bench-"
        f"{'/'.join(str(r) for r in agg_ranks)}) / "
        f"{member_wan * 8 / 1e6:.0f} Mbps (members), {rounds} measured "
        f"rounds (+{warm} learning)"
    )
    base_env = dict(os.environ)
    base_env["PYTHONPATH"] = REPO + os.pathsep + base_env.get("PYTHONPATH", "")

    results = {}
    server = RendezvousServer(host="127.0.0.1", port=0).start_in_thread()
    try:
        for hier in (False, True):
            mode = "hier" if hier else "flat"
            times, healths = _hier_sweep(
                args, server, hier, nic_bps, agg_wan, member_wan, warm,
                rounds, base_env,
            )
            wan_tx = sum(h.get("wire_tx_bytes_wan", 0) for h in healths)
            tx = sum(h.get("wire_tx_bytes", 0) for h in healths)
            results[mode] = {
                "rounds_s": [round(t, 3) for t in times],
                "median_s": round(statistics.median(times), 3),
                "best_s": round(min(times), 3),
                "wan_tx_bytes": wan_tx,
                "tx_bytes": tx,
                "wan_bytes_per_round": round(wan_tx / total_rounds),
            }
            hp = next((h["hier"] for h in healths if "hier" in h), None)
            if hp:
                results[mode]["plan"] = hp
            print(
                f"{mode:>5}: median {results[mode]['median_s'] * 1e3:7.0f} "
                f"ms/round  WAN {wan_tx / total_rounds / 1e6:7.1f} MB/round "
                f"({wan_tx / max(tx, 1) * 100:.0f}% of egress)"
            )
    finally:
        server.stop()

    wan_reduction = round(
        results["flat"]["wan_tx_bytes"]
        / max(results["hier"]["wan_tx_bytes"], 1),
        3,
    )
    speedup = round(
        results["flat"]["median_s"] / results["hier"]["median_s"], 3
    )
    doc = {
        "bench": "hier",
        "peers": args.peers,
        "sites": 2,
        "model": args.model,
        "mb_fp32": round(nbytes / 1e6),
        "nic_mbps": round(nic_bps * 8 / 1e6),
        "wan_mbps_aggregator": round(agg_wan * 8 / 1e6),
        "wan_mbps_member": round(member_wan * 8 / 1e6),
        "selftest": bool(args.selftest),
        "flat": results["flat"],
        "hier": results["hier"],
        "wan_reduction": wan_reduction,
        "speedup": speedup,
        "updated": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "host": {
            "cores": os.cpu_count(), "loadavg": round(os.getloadavg()[0], 2)
        },
        **HOST_ONLY,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(
        f"WAN reduction {wan_reduction:.2f}x, round-time speedup "
        f"{speedup:.2f}x (banked {out_path})"
    )
    if wan_reduction < wan_floor:
        raise SystemExit(
            f"hier WAN reduction {wan_reduction:.2f}x below the "
            f"{wan_floor}x line"
        )
    if not args.selftest and speedup <= 1.0:
        raise SystemExit(
            f"hier round time regressed: speedup {speedup:.2f}x <= 1.0x"
        )


def compress_main(args) -> None:
    """Sub-8-bit codec A/B on the bandwidth-skewed galaxy: uniform8bit (the
    8-bit baseline) vs blockwise4bit and topk, error feedback ON for the
    sub-8-bit arms (the production pairing — config.py rejects them without
    it in training, and the bench should price the residual add + roundtrip
    encode too). Same 4:1-slow-link topology as --hetero, adaptive
    partitioning off so the wire bytes are the only variable — but at a
    WAN-class 64 Mbps/worker cap (worker 0 at 16 Mbps) instead of --hetero's
    512: sub-8-bit is the slow-internet-link tier (arxiv 2407.07852), and at
    datacenter bandwidth the codec compute, not the wire, is the round's
    critical path. Banks COMPRESS_BENCH.json; the full run exits nonzero
    unless every sub-8-bit arm cuts wire bytes ~2x+ vs uniform8bit (topk
    >= 2.0x; blockwise4bit >= 1.95x — its ceiling vs the ~1 B/elem 8-bit
    baseline is just UNDER 2x, 0.5 B/elem plus per-4096-block fp16 scales
    = 1.998x) AND wins on round time."""
    from opendiloco_tpu.diloco.rendezvous import RendezvousServer

    skew = 4.0
    if args.selftest:
        args.peers, args.model, rounds, warm = 4, "tiny:8", 2, 1
        cap_bps = 64e6
        out_path = os.environ.get("ODTP_COMPRESS_BENCH_OUT") or os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "COMPRESS_BENCH.selftest.json"
        )
    else:
        args.peers, args.model = 8, "tiny:32"
        rounds, warm = max(args.rounds, 5), 2
        cap_bps = 8e6  # 64 Mbps/worker, worker 0 at 16 -- the WAN regime
        out_path = _COMPRESS_OUT
    nbytes = sum(a.nbytes for a in make_leaves(args.model, 0))
    print(
        f"compress bench: {args.peers} peers, {nbytes / 1e6:.0f} MB fp32, "
        f"egress {cap_bps * 8 / 1e6:.0f} Mbps/worker, worker 0 at "
        f"1/{skew:.0f} of that, {rounds} measured rounds (+{warm} learning)"
    )
    base_env = dict(os.environ)
    base_env["PYTHONPATH"] = REPO + os.pathsep + base_env.get("PYTHONPATH", "")

    arms = [("uniform8bit", False), ("blockwise4bit", True), ("topk", True)]
    results: dict[str, dict] = {}
    server = RendezvousServer(host="127.0.0.1", port=0).start_in_thread()
    try:
        for codec, ef in arms:
            times, health = _hetero_sweep(
                args, server, cap_bps, skew, False, warm, rounds, base_env,
                compression=codec, ef=ef,
            )
            wire = (health.get("wire") or {}).get(codec, {})
            row = {
                "error_feedback": ef,
                "rounds_s": [round(t, 3) for t in times],
                "median_s": round(statistics.median(times), 3),
                "best_s": round(min(times), 3),
                "wire_bytes": wire.get("wire_bytes"),
                "raw_bytes": wire.get("raw_bytes"),
                "compression_ratio": wire.get("ratio"),
            }
            if "ef_residual_norm" in health:
                row["ef_residual_norm"] = health["ef_residual_norm"]
            results[codec] = row
            print(
                f"{codec:>14}{'[ef]' if ef else '    '}: median "
                f"{row['median_s'] * 1e3:7.0f} ms/round  wire "
                f"{(row['wire_bytes'] or 0) / 1e6:7.1f} MB  ratio "
                f"{row['compression_ratio'] or 0:5.2f}x"
            )
    finally:
        server.stop()

    base = results["uniform8bit"]
    wire_reduction = {}
    speedup = {}
    for codec, _ in arms[1:]:
        r = results[codec]
        if base["wire_bytes"] and r["wire_bytes"]:
            wire_reduction[codec] = round(
                base["wire_bytes"] / r["wire_bytes"], 3
            )
        speedup[codec] = round(base["median_s"] / r["median_s"], 3)
    doc = {
        "bench": "compress",
        "peers": args.peers,
        "model": args.model,
        "mb_fp32": round(nbytes / 1e6),
        "bandwidth_mbps": round(cap_bps * 8 / 1e6),
        "skew": skew,
        "selftest": bool(args.selftest),
        "topk_density": float(
            os.environ.get("ODTP_TOPK_DENSITY", 0.03125) or 0.03125
        ),
        "arms": results,
        "wire_reduction_vs_uniform8bit": wire_reduction,
        "speedup_vs_uniform8bit": speedup,
        "updated": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "host": {
            "cores": os.cpu_count(), "loadavg": round(os.getloadavg()[0], 2)
        },
        **HOST_ONLY,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(
        "wire reduction vs uniform8bit: "
        + ", ".join(f"{k} {v:.2f}x" for k, v in wire_reduction.items())
        + "; round-time speedup: "
        + ", ".join(f"{k} {v:.2f}x" for k, v in speedup.items())
        + f" (banked {out_path})"
    )
    if not args.selftest:
        # blockwise4bit's reduction vs the ~1 B/elem 8-bit baseline tops out
        # just under 2x (0.5 B/elem + per-4096-block fp16 scales = 1.998x),
        # so its line sits at 1.95; topk has no such ceiling
        for codec, floor in (("blockwise4bit", 1.95), ("topk", 2.0)):
            if wire_reduction.get(codec, 0.0) < floor:
                raise SystemExit(
                    f"{codec} wire reduction "
                    f"{wire_reduction.get(codec)}x below the {floor}x line"
                )
        for codec, _ in arms[1:]:
            if speedup.get(codec, 0.0) <= 1.0:
                raise SystemExit(
                    f"{codec} round time did not beat uniform8bit "
                    f"({speedup.get(codec)}x)"
                )


def _stream_batches(seed: int, vocab: int, n: int, bs: int, seq: int):
    """Learnable deterministic stream (same generator as the convergence
    oracle): each row is a consecutive-token ramp from a random start."""
    rng = np.random.default_rng(seed)
    for _ in range(n):
        starts = rng.integers(0, vocab, (bs, 1))
        ids = ((starts + np.arange(seq)) % vocab).astype(np.int32)
        yield ids, ids.copy()


def _stream_arm(
    label: str, cfg_model, workers: int, warm: int, epochs: int,
    local_steps: int, bs: int, seq: int, dcfg_kwargs: dict,
) -> tuple[list, list]:
    """One arm of the streaming A/B/C: ``workers`` loopback threads in one
    shared world, each on its OWN single-device mesh (concurrent
    multi-device XLA executions deadlock on the CPU client — the
    per-worker-mesh idiom of tests/test_diloco.py). Every worker times
    every ``opt.step`` to loss-sync; the warm epochs are dropped (inner +
    outer jit compiles land there). Returns (per-worker step seconds for
    the measured epochs, per-worker final master leaves)."""
    import threading as th

    import jax

    from opendiloco_tpu.config import DilocoConfig
    from opendiloco_tpu.diloco import DiLoCoOptimizer, LoopbackWorld
    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

    n_steps = (warm + epochs) * local_steps
    world = LoopbackWorld(workers)
    backends = world.make_backends()
    times: list[list[float]] = [[] for _ in range(workers)]
    masters: list = [None] * workers
    errors: list[str] = []
    start = th.Barrier(workers)

    def worker(rank: int) -> None:
        try:
            tc = TrainerConfig(
                lr=1e-3, warmup_steps=2, total_steps=n_steps,
                precision="fp32", remat=False,
            )
            dev = jax.devices()[rank % len(jax.devices())]
            trainer = InnerTrainer(
                cfg_model, tc, build_mesh("NO_SHARD", devices=[dev])
            )
            state = trainer.init_state(jax.random.key(7))
            opt = DiLoCoOptimizer(
                trainer,
                backends[rank],
                DilocoConfig(
                    local_steps=local_steps,
                    outer_nesterov=True,
                    backend="loopback",
                    timeout_waiting_for_peers=300.0,
                    averaging_timeout=600.0,
                    **dcfg_kwargs,
                ),
                state,
                batch_size=bs,
            )
            data = [
                trainer.shard_batch(ids, labels, accum=1)
                for ids, labels in _stream_batches(
                    1000 + rank, cfg_model.vocab_size, n_steps, bs, seq
                )
            ]
            start.wait()
            for batch in data:
                t0 = time.perf_counter()
                state, m = opt.step(state, batch)
                float(m["loss"])  # sync: the step (and any blocking
                # boundary work inside it) has fully executed
                times[rank].append(time.perf_counter() - t0)
            state = opt.flush(state)  # untimed: land whatever still flies
            masters[rank] = [np.asarray(x) for x in opt.master]
        except Exception as e:  # pragma: no cover - surfaced to the parent
            errors.append(f"{label} worker {rank}: {e!r}")
            try:
                start.abort()
            except Exception:
                pass

    threads = [th.Thread(target=worker, args=(r,)) for r in range(workers)]
    t0 = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise SystemExit("stream bench arm failed: " + "; ".join(errors))
    print(f"  [{label}: {workers} workers x {n_steps} steps, "
          f"{time.time() - t0:.1f}s wall]")
    return [ts[warm * local_steps:] for ts in times], masters


def stream_main(args) -> None:
    """Streaming eager outer sync A/B/C: blocking vs delayed-overlap vs
    staggered streaming-eager fragment sync on the SAME in-process
    loopback galaxy, same data/init, same chaos-emulated WAN latency on
    every all-reduce contribution. The headline per arm is the OUTER
    OVERHEAD as a % of the inner phase: measured-epoch wall clock against
    an inner-only ideal priced from the blocking arm's median undisturbed
    (non-boundary) step. Blocking pays the emulated round-trip on the
    training thread at every boundary; the overlapped arms pay it on comm
    threads, where it should vanish under inner compute. Banks
    STREAM_BENCH.json; the full run exits nonzero if streaming-eager
    overhead breaches the 5% acceptance line."""
    if args.selftest:
        workers, warm, epochs, local_steps = 2, 1, 2, 4
        fragments, delay_ms, bs = 2, 50, 4
        out_path = os.environ.get("ODTP_STREAM_BENCH_OUT") or os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "STREAM_BENCH.selftest.json"
        )
    else:
        # H=32 keeps the inner phase long enough that the per-fragment
        # launch/land host math AND the comm threads' copy/sum CPU (which
        # a 1-core box charges against inner steps even when the wire
        # wait itself is hidden) price under the 5% line — the same ratio
        # production has, where inner steps are seconds, not milliseconds
        workers, warm, epochs, local_steps = 8, 2, 3, 32
        fragments, delay_ms, bs = 4, 300, 8
        out_path = _STREAM_OUT
    seq, stagger = 64, 1.0
    # per-worker single-device meshes need >= ``workers`` host devices;
    # the flag only takes effect before the first backend init, so set it
    # before anything imports jax
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={workers}"
        ).strip()
    import jax

    # a choice, not a fallback: the arms compare outer-overhead shares on
    # N virtual CPU devices, one per worker thread; the artifact says so
    jax.config.update("jax_platforms", "cpu")
    from opendiloco_tpu.models.hf_io import get_model
    from opendiloco_tpu.utils.device import device_stamp

    cfg_model, _ = get_model("2m")
    # WAN round-trip stand-in: the chaos plane sleeps every all-reduce
    # contribution for delay_ms before it joins its round (pinned value +
    # seed => identical schedule across arms). Loopback's in-memory sum is
    # otherwise free, which would make every arm trivially "overlapped".
    os.environ["ODTP_CHAOS"] = f"seed=7;delay_ms={delay_ms}"
    print(
        f"stream bench: {workers} workers, model 2m, H={local_steps}, "
        f"{epochs} measured epochs (+{warm} warm), emulated round-trip "
        f"{delay_ms} ms, streaming N={fragments} stagger={stagger}"
    )

    arms = [
        ("blocking", {}),
        ("delayed", {"overlap_comm": "delayed"}),
        (
            "streaming_eager",
            {
                "streaming_fragments": fragments,
                "overlap_comm": "eager",
                "stream_stagger": stagger,
            },
        ),
    ]
    H = local_steps
    results: dict[str, dict] = {}
    baseline_inner = 0.0
    for label, kwargs in arms:
        measured, masters = _stream_arm(
            label, cfg_model, workers, warm, epochs, H, bs, seq, kwargs
        )
        # every arm all-reduces the same values on every peer, so the
        # masters must agree across workers — guards the bench against
        # silently timing a broken sync path
        for a, b in zip(masters[0], masters[-1]):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        inner = [
            t for ts in measured for i, t in enumerate(ts) if i % H != H - 1
        ]
        bound = [
            t for ts in measured for i, t in enumerate(ts) if i % H == H - 1
        ]
        if label == "blocking":
            # the shared inner-only price: blocking's non-boundary steps
            # carry NO outer work at all (no ticks, no launches), so their
            # median is the purest contended-inner-step cost available
            baseline_inner = statistics.median(inner)
        per_worker_pct = []
        for ts in measured:
            ideal = len(ts) * baseline_inner
            per_worker_pct.append(round(100.0 * (sum(ts) - ideal) / ideal, 2))
        results[label] = {
            "outer_overhead_pct": round(statistics.median(per_worker_pct), 2),
            "per_worker_overhead_pct": per_worker_pct,
            "median_epoch_s": round(
                statistics.median(
                    sum(ts[e * H:(e + 1) * H])
                    for ts in measured for e in range(epochs)
                ),
                4,
            ),
            "median_inner_step_s": round(statistics.median(inner), 4),
            "median_boundary_step_s": round(statistics.median(bound), 4),
            "epochs_s_w0": [
                round(sum(measured[0][e * H:(e + 1) * H]), 4)
                for e in range(epochs)
            ],
        }
        r = results[label]
        print(
            f"{label:>16}: overhead {r['outer_overhead_pct']:6.2f}% of inner"
            f"  (epoch {r['median_epoch_s'] * 1e3:7.0f} ms, inner step "
            f"{r['median_inner_step_s'] * 1e3:6.0f} ms, boundary step "
            f"{r['median_boundary_step_s'] * 1e3:6.0f} ms)"
        )
    os.environ.pop("ODTP_CHAOS", None)

    doc = {
        "bench": "stream",
        "model": "2m",
        "workers": workers,
        "local_steps": H,
        "epochs_measured": epochs,
        "epochs_warm": warm,
        "fragments": fragments,
        "stream_stagger": stagger,
        "emulated_rtt_ms": delay_ms,
        "selftest": bool(args.selftest),
        "baseline_inner_step_s": round(baseline_inner, 4),
        "arms": results,
        "updated": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "host": {
            "cores": os.cpu_count(), "loadavg": round(os.getloadavg()[0], 2)
        },
        **device_stamp(),
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    stream_pct = results["streaming_eager"]["outer_overhead_pct"]
    print(
        f"streaming-eager outer overhead {stream_pct:.2f}% of inner phase "
        f"(blocking {results['blocking']['outer_overhead_pct']:.2f}%, "
        f"banked {out_path})"
    )
    if not args.selftest and stream_pct >= 5.0:
        raise SystemExit(
            f"streaming-eager overhead {stream_pct:.2f}% breaches the 5% "
            "acceptance line"
        )


def _gossip_galaxy(
    n_workers: int, rounds: int, model: str, compression: str, mode: str
) -> tuple[list[list[float]], list[list[float]], list[int], list[int]]:
    """One galaxy of ``n_workers`` loopback threads running ``rounds``
    outer rounds in ``mode`` ("gossip" pair exchange vs "allreduce"
    global butterfly stand-in). Returns per-worker wall seconds, per-
    worker CPU (thread_time) seconds, wire bytes, and dropped counts.

    Wall time on an oversubscribed single host mostly measures the
    timesharing of N threads; per-round THREAD CPU is the scalable
    signal — it excludes waiting, so it prices exactly the work one
    worker must do per round (encode/decode/mix for gossip; codec
    roundtrip plus a 1/N share of the O(N x model) published sum for the
    all-reduce)."""
    from opendiloco_tpu.diloco.gossip import GossipPlane
    from opendiloco_tpu.diloco.loopback import LoopbackWorld

    world = LoopbackWorld(n_workers, compression=compression)
    backends = world.make_backends()
    wall: list[list[float]] = [[] for _ in range(n_workers)]
    cpu: list[list[float]] = [[] for _ in range(n_workers)]
    wire = [0] * n_workers
    drops = [0] * n_workers
    errors: list[str] = []
    start = threading.Barrier(n_workers)

    def worker(rank: int) -> None:
        try:
            masters = make_leaves(model, rank)
            bufs = make_leaves(model, 100 + rank)
            pgs = make_leaves(model, 200 + rank)
            idxs = list(range(len(masters)))
            plane = (
                GossipPlane(
                    backends[rank], len(masters),
                    compression=compression, error_feedback=True,
                )
                if mode == "gossip" else None
            )
            start.wait()
            for r in range(rounds):
                t0 = time.perf_counter()
                c0 = time.thread_time()
                if plane is None:
                    backends[rank].all_reduce(
                        pgs, timeout=600.0, tag="bench", epoch=r
                    )
                else:
                    res = plane.exchange(
                        epoch=r, frag_id=0, idxs=idxs, masters=masters,
                        bufs=bufs, pgs=pgs, timeout=600.0,
                    )
                    if res is None:
                        drops[rank] += 1
                    else:
                        wire[rank] += backends[rank].last_round_health.get(
                            "wire_bytes", 0
                        )
                cpu[rank].append(time.thread_time() - c0)
                wall[rank].append(time.perf_counter() - t0)
        except Exception as e:  # pragma: no cover - surfaced to the parent
            errors.append(f"{mode} worker {rank}: {e!r}")
            try:
                start.abort()
            except Exception:
                pass

    threads = [
        threading.Thread(target=worker, args=(r,)) for r in range(n_workers)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise SystemExit("gossip bench galaxy failed: " + "; ".join(errors))
    return wall, cpu, wire, drops


def gossip_main(args) -> None:
    """Barrier-free gossip outer rounds vs the global collective, swept
    over galaxy size on one host: N loopback worker threads per galaxy,
    each round either ONE NoLoCo pair exchange (masters+momentum on the
    fp16 state codec, pseudo-grads on blockwise4bit with per-partner
    error feedback) or one global all-reduce of the same pseudo-grads
    through the same world. Headlines: per-worker per-round CPU stays
    ~flat for gossip while the collective grows with N, and gossip wire
    bytes per worker per round are independent of N. Banks
    GOSSIP_BENCH.json."""
    if args.selftest:
        sizes, rounds, model = (4, 6), 3, "tiny:1"
        out_path = os.environ.get("ODTP_GOSSIP_BENCH_OUT") or os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "GOSSIP_BENCH.selftest.json"
        )
    else:
        sizes, rounds, model = (8, 16, 32), 10, "tiny:4"
        out_path = _GOSSIP_OUT
    compression = "blockwise4bit"
    print(
        f"gossip bench: galaxies {sizes}, {rounds} rounds, model {model}, "
        f"grad codec {compression} (+fp16 state sections on the pair wire)"
    )
    rows = []
    for n in sizes:
        for mode in ("gossip", "allreduce"):
            t0 = time.time()
            wall, cpu, wire, drops = _gossip_galaxy(
                n, rounds, model, compression, mode
            )
            flat_wall = [t for ts in wall for t in ts]
            flat_cpu = [t for ts in cpu for t in ts]
            paired = rounds * n - sum(drops) - (rounds * (n % 2))
            row = {
                "mode": mode,
                "peers": n,
                "rounds": rounds,
                "median_round_s": round(statistics.median(flat_wall), 4),
                "p90_round_s": round(
                    sorted(flat_wall)[int(0.9 * (len(flat_wall) - 1))], 4
                ),
                "median_round_cpu_s": round(statistics.median(flat_cpu), 4),
                "dropped_rounds": sum(drops),
            }
            if mode == "gossip":
                # self-rounds (odd N) ship zero bytes by design; average
                # over the rounds that actually hit the wire
                row["wire_mb_per_worker_round"] = round(
                    sum(wire) / max(paired, 1) / 1e6, 3
                )
            rows.append(row)
            print(
                f"  n={n:3d} {mode:>9}: round {row['median_round_s'] * 1e3:7.1f} ms wall, "
                f"{row['median_round_cpu_s'] * 1e3:7.1f} ms cpu"
                + (
                    f", {row.get('wire_mb_per_worker_round', 0):.3f} MB/worker/round"
                    if mode == "gossip" else ""
                )
                + f"  [{time.time() - t0:.1f}s]"
            )
    doc = {
        "bench": "gossip",
        "model": model,
        "galaxies": list(sizes),
        "rounds": rounds,
        "grad_codec": compression,
        "selftest": bool(args.selftest),
        "rows": rows,
        "updated": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "host": {
            "cores": os.cpu_count(), "loadavg": round(os.getloadavg()[0], 2)
        },
        **HOST_ONLY,
    }
    g = {r["peers"]: r for r in rows if r["mode"] == "gossip"}
    a = {r["peers"]: r for r in rows if r["mode"] == "allreduce"}
    lo, hi = min(sizes), max(sizes)
    doc["gossip_cpu_growth"] = round(
        g[hi]["median_round_cpu_s"] / max(g[lo]["median_round_cpu_s"], 1e-9), 3
    )
    doc["allreduce_cpu_growth"] = round(
        a[hi]["median_round_cpu_s"] / max(a[lo]["median_round_cpu_s"], 1e-9), 3
    )
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(
        f"per-round cpu growth x{doc['gossip_cpu_growth']:.2f} (gossip) vs "
        f"x{doc['allreduce_cpu_growth']:.2f} (all-reduce) from n={lo} to "
        f"n={hi}; banked {out_path}"
    )
    if sum(r["dropped_rounds"] for r in rows):
        raise SystemExit("gossip bench dropped rounds on a healthy galaxy")
    wires = {
        r["wire_mb_per_worker_round"] for r in rows if r["mode"] == "gossip"
    }
    if len(wires) > 1 and (max(wires) - min(wires)) / max(wires) > 0.01:
        raise SystemExit(
            f"gossip wire bytes vary with galaxy size: {sorted(wires)}"
        )
    if not args.selftest and doc["gossip_cpu_growth"] > 2.0:
        raise SystemExit(
            f"gossip per-round cpu grew x{doc['gossip_cpu_growth']:.2f} from "
            f"n={lo} to n={hi} — not flat"
        )


def _async_galaxy(
    n: int, epochs: list[int], local_steps: int, base_dt: float,
    tok_per_step: int, model: str, gossip: bool,
) -> list[dict]:
    """One leg over the inner-step-skewed loopback galaxy: each of ``n``
    worker threads runs its epoch budget, every inner step priced at
    ``base_dt * straggle_inner_x(rank)`` (the chaos plane's skew table —
    a pure lookup, so concurrent threads share one plane safely), with an
    outer gossip exchange at every epoch boundary when ``gossip`` is on
    (lockstep or async per the ambient ODTP_ASYNC_* env; off = the
    standalone inner-only baseline). Returns per-worker rows; a worker
    exception becomes an ``error`` row — the acceptance gate requires
    zero of them."""
    from opendiloco_tpu.diloco import chaos
    from opendiloco_tpu.diloco.gossip import GossipPlane
    from opendiloco_tpu.diloco.loopback import LoopbackWorld

    compression = "blockwise4bit"
    world = LoopbackWorld(n, compression=compression)
    backends = world.make_backends()
    rows: list = [None] * n
    start = threading.Barrier(n)

    def worker(rank: int) -> None:
        try:
            cp = chaos.plane()
            x = cp.straggle_inner_x(rank=rank) if cp is not None else 1.0
            masters = make_leaves(model, rank)
            bufs = make_leaves(model, 100 + rank)
            pgs = make_leaves(model, 200 + rank)
            idxs = list(range(len(masters)))
            plane = (
                GossipPlane(
                    backends[rank], len(masters),
                    compression=compression, error_feedback=True,
                )
                if gossip else None
            )
            start.wait()
            paired = selfed = dropped = 0
            lags: list[int] = []
            t0 = time.perf_counter()
            for e in range(epochs[rank]):
                for _ in range(local_steps):
                    time.sleep(base_dt * x)
                if plane is None:
                    continue
                res = plane.exchange(
                    epoch=e, frag_id=0, idxs=idxs, masters=masters,
                    bufs=bufs, pgs=pgs, timeout=120.0,
                )
                if res is None:
                    dropped += 1
                elif res[4] == 2:
                    paired += 1
                    lags.append(
                        backends[rank].last_round_health.get("pair_lag", 0)
                    )
                else:
                    selfed += 1
            wall = time.perf_counter() - t0
            tokens = epochs[rank] * local_steps * tok_per_step
            rows[rank] = {
                "rank": rank,
                "skew_x": x,
                "epochs": epochs[rank],
                "wall_s": round(wall, 3),
                "tokens_per_s": round(tokens / wall, 1),
                "paired_rounds": paired,
                "self_rounds": selfed,
                "dropped_rounds": dropped,
                "mean_pair_lag": (
                    round(statistics.fmean(lags), 2) if lags else None
                ),
            }
        except Exception as e:  # pragma: no cover - becomes an error row
            rows[rank] = {"rank": rank, "error": repr(e)}
            try:
                start.abort()
            except Exception:
                pass

    threads = [threading.Thread(target=worker, args=(r,)) for r in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # close only after every thread exited: a worker that finishes its
    # budget first must stay LIVE, or the stragglers' in-flight lockstep
    # pairs resolve as partner-left drops (and one of them eats the full
    # pair timeout waiting on a deposit that never comes)
    for b in backends:
        b.close()
    return rows


def async_main(args) -> None:
    """Async outer rounds vs epoch lockstep on a heterogeneous galaxy: 8
    loopback worker threads with 2x/4x inner-step-speed skew injected
    through the chaos plane (straggle_inner_x). Three legs over the SAME
    skew table: standalone (inner-only per-worker ceilings), lockstep
    gossip (PR-15 epoch-aligned pair keys — every pair waits for its
    slower member), and async gossip (ODTP_ASYNC_STALENESS free-running
    clocks — misses self-round after patience). Banks ASYNC_BENCH.json;
    the full run exits nonzero unless the async aggregate holds >= 0.8x
    the standalone sum while lockstep is bounded by the slowest worker,
    or if any leg produced an error row."""
    from opendiloco_tpu.diloco import chaos

    window, decay = 2, 0.5
    if args.selftest:
        n, local_steps, base_dt, model = 4, 4, 0.01, "tiny:0.1"
        skew_spec = "straggle_inner_x=w2:2.0,w3:4.0"
        skews = [1.0, 1.0, 2.0, 4.0]
        epochs_1x, lock_epochs, patience = 8, 3, 0.05
        out_path = os.environ.get("ODTP_ASYNC_BENCH_OUT") or os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "ASYNC_BENCH.selftest.json"
        )
    else:
        n, local_steps, base_dt, model = 8, 8, 0.02, "tiny:0.25"
        # the ISSUE's heterogeneous galaxy: 4 full-speed workers, two at
        # half speed, two at quarter speed (per-rank table form — the
        # workers are threads of one process, so rank must be explicit)
        skew_spec = "straggle_inner_x=w4:2.0,w5:2.0,w6:4.0,w7:4.0"
        skews = [1.0, 1.0, 1.0, 1.0, 2.0, 2.0, 4.0, 4.0]
        epochs_1x, lock_epochs, patience = 16, 6, 0.1
        out_path = _ASYNC_OUT
    tok_per_step = 1024  # nominal; only ratios between legs matter
    # equal WALL budgets per worker: epoch counts inverse to the skew, so
    # every worker is active (and matchable) for the whole leg
    async_epochs = [max(2, round(epochs_1x / x)) for x in skews]
    print(
        f"async bench: {n} workers, skew {skews}, {local_steps} inner "
        f"steps/epoch at {base_dt * 1e3:.0f} ms base, window {window}, "
        f"patience {patience}s"
    )

    saved = {
        k: os.environ.get(k)
        for k in (
            "ODTP_CHAOS", "ODTP_ASYNC_STALENESS", "ODTP_ASYNC_DECAY",
            "ODTP_ASYNC_PATIENCE_S",
        )
    }
    legs: dict[str, list] = {}
    try:
        os.environ["ODTP_CHAOS"] = f"seed=1;{skew_spec}"
        chaos.reset()
        os.environ.pop("ODTP_ASYNC_STALENESS", None)
        t0 = time.time()
        legs["standalone"] = _async_galaxy(
            n, async_epochs, local_steps, base_dt, tok_per_step, model,
            gossip=False,
        )
        print(f"  [standalone: {time.time() - t0:.1f}s wall]")
        t0 = time.time()
        legs["lockstep"] = _async_galaxy(
            n, [lock_epochs] * n, local_steps, base_dt, tok_per_step,
            model, gossip=True,
        )
        print(f"  [lockstep: {time.time() - t0:.1f}s wall]")
        os.environ["ODTP_ASYNC_STALENESS"] = str(window)
        os.environ["ODTP_ASYNC_DECAY"] = str(decay)
        os.environ["ODTP_ASYNC_PATIENCE_S"] = str(patience)
        t0 = time.time()
        legs["async"] = _async_galaxy(
            n, async_epochs, local_steps, base_dt, tok_per_step, model,
            gossip=True,
        )
        print(f"  [async: {time.time() - t0:.1f}s wall]")
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        chaos.reset()

    errors = [
        r for rows in legs.values() for r in rows if r is None or "error" in r
    ]
    agg = {
        leg: round(sum(r["tokens_per_s"] for r in rows), 1)
        for leg, rows in legs.items()
        if not any(r is None or "error" in r for r in rows)
    }
    slowest = (
        min(r["tokens_per_s"] for r in legs["standalone"])
        if "standalone" in agg else 0.0
    )
    summary = {
        leg: {
            "aggregate_tokens_per_s": agg.get(leg),
            "rows": rows,
        }
        for leg, rows in legs.items()
    }
    doc = {
        "bench": "async",
        "workers": n,
        "model": model,
        "local_steps": local_steps,
        "base_inner_step_s": base_dt,
        "tok_per_step": tok_per_step,
        "skew": skews,
        "chaos_spec": skew_spec,
        "window": window,
        "decay": decay,
        "patience_s": patience,
        "selftest": bool(args.selftest),
        "legs": summary,
        "slowest_standalone_tokens_per_s": slowest,
        "errors": [r for r in errors if r is not None],
        "updated": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "host": {
            "cores": os.cpu_count(), "loadavg": round(os.getloadavg()[0], 2)
        },
        **HOST_ONLY,
    }
    if "standalone" in agg and "async" in agg and "lockstep" in agg:
        doc["async_vs_standalone_sum"] = round(
            agg["async"] / agg["standalone"], 3
        )
        doc["lockstep_vs_standalone_sum"] = round(
            agg["lockstep"] / agg["standalone"], 3
        )
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    for leg in ("standalone", "lockstep", "async"):
        print(
            f"{leg:>11}: aggregate "
            f"{agg.get(leg, float('nan')):10.1f} tok/s"
        )
    print(f"banked {out_path}")
    if errors:
        raise SystemExit(f"async bench produced error rows: {errors}")
    if args.selftest:
        return
    # acceptance: async holds near the SUM of standalone rates; lockstep
    # is bounded by the slowest worker's rate (x n, with drift slack for
    # fast-fast pairs running ahead inside the matching's elasticity)
    if agg["async"] < 0.8 * agg["standalone"]:
        raise SystemExit(
            f"async aggregate {agg['async']:.0f} tok/s below 0.8x the "
            f"standalone sum {agg['standalone']:.0f}"
        )
    if agg["lockstep"] > 1.5 * n * slowest:
        raise SystemExit(
            f"lockstep aggregate {agg['lockstep']:.0f} tok/s not bounded "
            f"by the slowest worker ({n} x {slowest:.0f} x 1.5)"
        )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--peers", type=int, default=2)
    ap.add_argument("--model", default="150m")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--group-cap", type=int, default=0,
                    help="gossip mode: partition matchmade joiners into "
                    "groups of at most this size (0 = one global group); "
                    "--peers must divide evenly")
    ap.add_argument("--codecs", default=",".join(ALL_CODECS),
                    help="comma list from: " + ",".join(ALL_CODECS))
    ap.add_argument(
        "--bandwidth", default="0",
        help="comma list of per-worker egress caps (token bucket in the "
        "bulk plane), e.g. '0,1gbps,100mbps'; 0 = unlimited. The caps make "
        "the codec tradeoff measurable: on a constrained link the 8-bit "
        "wire beats raw fp32 even after paying encode/decode",
    )
    ap.add_argument(
        "--pipeline", default="both", choices=["both", "on", "off"],
        help="data-plane mode per codec: 'on' = chunk-pipelined (the "
        "production default), 'off' = serial whole-part frames, 'both' = "
        "bench the pair and report the pipelined speedup",
    )
    ap.add_argument(
        "--fresh", action="store_true",
        help="start OUTER_BENCH.json from scratch instead of appending",
    )
    ap.add_argument(
        "--boundary", action="store_true",
        help="bench the outer BOUNDARY (d2h/apply/h2d per outer_placement) "
        "instead of the wire: in-process host-vs-device sweep over "
        "--codecs, banks BOUNDARY_BENCH.json",
    )
    ap.add_argument(
        "--hetero", action="store_true",
        help="bandwidth-skewed galaxy A/B: chaos-cap worker 0's egress at "
        "1/4 of the others and bench uniform vs ODTP_LINK_ADAPT adaptive "
        "partitioning; banks HETERO_BENCH.json",
    )
    ap.add_argument(
        "--stream", action="store_true",
        help="streaming eager outer sync A/B/C: blocking vs delayed vs "
        "staggered streaming-eager fragment sync on an in-process "
        "8-worker loopback galaxy under emulated WAN latency; reports "
        "outer-overhead-%% of the inner phase per mode and banks "
        "STREAM_BENCH.json",
    )
    ap.add_argument(
        "--compress", action="store_true",
        help="sub-8-bit codec A/B on the 4:1-skewed galaxy: uniform8bit vs "
        "blockwise4bit/topk with error feedback; banks COMPRESS_BENCH.json",
    )
    ap.add_argument(
        "--hier", action="store_true",
        help="hierarchical galaxy A/B: flat butterfly vs the two-level "
        "planner round (ODTP_HIER) on an emulated 2-site topology with "
        "chaos wan_bps uplink shaping; banks HIER_BENCH.json",
    )
    ap.add_argument(
        "--gossip", action="store_true",
        help="barrier-free NoLoCo pair rounds vs the global collective "
        "across growing single-host loopback galaxies; banks "
        "GOSSIP_BENCH.json",
    )
    ap.add_argument(
        "--async", action="store_true", dest="async_bench",
        help="lockstep vs bounded-staleness async gossip rounds on a "
        "2x/4x inner-step-skewed loopback galaxy (chaos "
        "straggle_inner_x); banks ASYNC_BENCH.json",
    )
    ap.add_argument(
        "--selftest", action="store_true",
        help="with --hetero/--stream/--compress/--hier/--gossip/--async: "
        "small/fast CI shape that checks the loop works without "
        "asserting the speedup/overhead line",
    )
    args = ap.parse_args()
    if args.async_bench:
        async_main(args)
        return
    if args.gossip:
        gossip_main(args)
        return
    if args.stream:
        stream_main(args)
        return
    if args.hetero:
        hetero_main(args)
        return
    if args.compress:
        compress_main(args)
        return
    if args.hier:
        hier_main(args)
        return
    if args.boundary:
        if os.environ.get("MALLOC_MMAP_THRESHOLD_") is None:
            # glibc mmaps (and munmaps on free) every model-sized chunk by
            # default, so each boundary round re-faults ~1 GB of pages --
            # measured +400 ms/round on BOTH placements. Keep large frees
            # on the heap instead; env is only read at process start, so
            # re-exec
            os.environ["MALLOC_MMAP_THRESHOLD_"] = str(1 << 30)
            os.environ["MALLOC_TRIM_THRESHOLD_"] = str(1 << 30)
            os.execv(sys.executable, [sys.executable] + sys.argv)
        if args.fresh and os.path.exists(_BOUNDARY_OUT):
            os.remove(_BOUNDARY_OUT)
        if args.codecs == ",".join(ALL_CODECS):
            # the boundary sweep's codec axis is the device pre-cast (wire
            # width of the D2H fetch); only none/fp16 differ there
            args.codecs = "none,fp16"
        boundary_main(args)
        return
    if args.fresh and os.path.exists(_OUT):
        os.remove(_OUT)
    if args.group_cap and args.peers % args.group_cap:
        # the rendezvous would hand the remainder a smaller (possibly solo)
        # group by design -- which benches nothing; require even gossip
        # groups instead of recording nondeterministic partial-round errors
        ap.error(
            f"--peers {args.peers} must divide evenly by "
            f"--group-cap {args.group_cap}"
        )

    from opendiloco_tpu.diloco.rendezvous import RendezvousServer
    from opendiloco_tpu.models.hf_io import load_config
    from opendiloco_tpu.models.llama import shapes
    import jax

    cfg = load_config(args.model)
    nbytes = sum(
        int(np.prod(s.shape)) * 4 for s in jax.tree.leaves(shapes(cfg))
    )
    print(
        f"model {args.model}: {nbytes / 1e6:.0f} MB fp32, {args.peers} peers, "
        f"{args.rounds} rounds, cores={os.cpu_count()}"
    )

    base_env = dict(os.environ)
    base_env["PYTHONPATH"] = REPO + os.pathsep + base_env.get("PYTHONPATH", "")

    server = RendezvousServer(host="127.0.0.1", port=0).start_in_thread()
    try:
        for bw_spec in args.bandwidth.split(","):
            cap_bps = _parse_bandwidth(bw_spec)
            run_sweep(args, server, nbytes, base_env, cap_bps)
    finally:
        server.stop()


def run_sweep(args, server, nbytes, base_env, cap_bps: float) -> None:
    # generous per-round budget on a throttled box: quantile encode of a
    # 4 GB buffer on one core is minutes, not seconds. Under an egress cap
    # the fp32 wire alone needs ~nbytes/cap per phase; budget 4x that.
    round_timeout = max(600.0, nbytes / 20e6)
    if cap_bps > 0:
        round_timeout = max(round_timeout, 4.0 * nbytes / cap_bps)
    # includes the workers' own peer-scaled assembly deadline: the parent
    # must outwait a worker's fail-loud exit, not preempt it with a kill
    # (which loses the diagnosable output AND leaves stale registrations)
    proc_timeout = args.rounds * round_timeout + 300.0 + 60 + 60 * args.peers
    env = dict(base_env)
    if cap_bps > 0:
        env["ODTP_BULK_BANDWIDTH_BPS"] = str(int(cap_bps))
        print(f"-- egress cap {cap_bps * 8 / 1e6:.0f} Mbps per worker --")
    else:
        env.pop("ODTP_BULK_BANDWIDTH_BPS", None)
    cap_note = (
        {"bandwidth_mbps": round(cap_bps * 8 / 1e6)} if cap_bps > 0 else {}
    )
    # serial ("0") first so the pipelined row can record its speedup
    modes = {"both": ["0", "1"], "on": ["1"], "off": ["0"]}[args.pipeline]
    for compression in args.codecs.split(","):
        serial_mean = None  # this codec's serial trimmed_mean_s, if benched
        for mode in modes:
            pipelined = mode == "1"
            label = f"{compression}[{'pipe' if pipelined else 'serial'}]"
            plane = {"pipelined": pipelined}
            ceiling = loopback_ceiling_gbps()
            procs = [
                subprocess.Popen(
                    [
                        sys.executable, os.path.abspath(__file__), "--worker",
                        "--rendezvous", server.address, "--rank", str(i),
                        "--model", args.model, "--compression", compression,
                        "--rounds", str(args.rounds),
                        "--peers", str(args.peers),
                        "--timeout", str(round_timeout),
                        "--sweep-start", str(time.time()),
                        "--group-cap", str(args.group_cap),
                        "--pipeline", mode,
                    ],
                    stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT,  # tracebacks -> detail
                    text=True,
                    env=env,
                )
                for i in range(args.peers)
            ]
            try:
                outs = [p.communicate(timeout=proc_timeout)[0] for p in procs]
            except subprocess.TimeoutExpired:
                for p in procs:
                    p.kill()
                for p in procs:  # reap; drain pipes so fds don't leak
                    try:
                        p.communicate(timeout=10)
                    except Exception:
                        pass
                print(f"{label:>22}: TIMEOUT")
                _append_row({
                    "model": args.model, "peers": args.peers,
                    "codec": compression, **plane, "error": "timeout",
                    **cap_note,
                })
                continue
            line = next(
                (l for o in outs for l in o.splitlines()
                 if l.startswith("RESULT")),
                None,
            )
            # elastic rounds (partial groups that survived the in-worker
            # retries) are DATA, not errors: every worker prints a HEALTH
            # line with its per-round group sizes + fault counters, and
            # the row records them alongside the timings
            want = expected_group(args.peers, args.group_cap)
            healths = [
                json.loads(l.split(None, 1)[1])
                for o in outs for l in o.splitlines()
                if l.startswith("HEALTH ")
            ]
            if line is None or any(p.returncode for p in procs):
                print(f"{label:>22}: FAILED")
                _append_row({
                    "model": args.model, "peers": args.peers,
                    "codec": compression, **plane,
                    "error": "worker failure", **cap_note,
                    # last lines of each worker so the row is diagnosable
                    "detail": [
                        " | ".join(o.splitlines()[-3:])[-400:] for o in outs
                    ],
                })
                continue
            tline = next(
                (l for o in outs for l in o.splitlines()
                 if l.startswith("TIMINGS")),
                None,
            )
            timings = json.loads(tline.split(None, 1)[1]) if tline else {}
            tokens = line.split()[1:]
            kv = dict(t.split("=", 1) for t in tokens if "=" in t)
            times = [float(x) for x in tokens if "=" not in x]
            best = min(times)
            eff = nbytes / best / 1e9
            # normalize against whichever is binding: the box's socket
            # ceiling or the emulated link cap
            norm_base = min(ceiling, cap_bps / 1e9) if cap_bps > 0 else ceiling
            trimmed = round(
                statistics.fmean(
                    # drop the worst round (and the best too at >=5
                    # rounds): on a 1-core box one descheduled worker
                    # poisons a single round and the plain median of 3
                    # still carries it half the time
                    sorted(times)[1:-1] if len(times) >= 5
                    else sorted(times)[:-1] if len(times) >= 2
                    else times
                ),
                3,
            )
            rank0_health = next(
                (h for h in healths if h.get("rank") == 0), {}
            )
            group_sizes = rank0_health.get("group_sizes") or []
            elastic_rounds = max(
                (h.get("elastic_rounds", 0) for h in healths), default=0
            )
            faults: dict[str, int] = {}
            for h in healths:
                for k, v in (h.get("faults") or {}).items():
                    faults[k] = faults.get(k, 0) + v
            row = {
                "model": args.model, "mb_fp32": round(nbytes / 1e6),
                "peers": args.peers, "codec": compression, **plane,
                **(
                    {"chunk_mb": int(
                        env.get("ODTP_PIPELINE_CHUNK_MB", 8) or 8)}
                    if pipelined else {}
                ),
                **({"group_cap": args.group_cap} if args.group_cap else {}),
                "rounds_s": [round(t, 3) for t in times],
                "best_s": round(best, 3),
                "median_s": round(statistics.median(times), 3),
                "trimmed_mean_s": trimmed,
                **(
                    {"matchmaking_retries": int(kv["retries"])}
                    if kv.get("retries", "0") != "0"
                    else {}
                ),
                "group_size": int(kv.get("n", want) or want),
                "elastic": bool(elastic_rounds),
                **(
                    {
                        "group_sizes": group_sizes,
                        "elastic_rounds": elastic_rounds,
                    }
                    if elastic_rounds
                    else {}
                ),
                **({"faults": faults} if faults else {}),
                "eff_gbps": round(eff, 3),
                "loopback_ceiling_gbps": round(ceiling, 3),
                "normalized_eff": round(eff / norm_base, 4),
                "last_round_timings": timings,
                **cap_note,
            }
            speed_note = ""
            if pipelined and serial_mean:
                row["speedup_vs_serial"] = round(serial_mean / trimmed, 3)
                speed_note = f"  {serial_mean / trimmed:4.2f}x vs serial"
            if not pipelined:
                serial_mean = trimmed
            _append_row(row)
            elastic_note = (
                f"  [elastic: {elastic_rounds} partial round(s), "
                f"groups {group_sizes}]"
                if elastic_rounds else ""
            )
            print(
                f"{label:>22}: {best * 1e3:8.0f} ms/round best  "
                f"({eff:5.2f} GB/s eff, ceiling {ceiling:5.2f} GB/s, "
                f"normalized {eff / norm_base:5.1%}){speed_note}{elastic_note}"
            )


if __name__ == "__main__":
    if "--worker" in sys.argv:
        worker_main()
    else:
        main()
