#!/usr/bin/env python
"""Chaos soak: an 8-worker DiLoCo galaxy trained under scripted fire.

Real TCP data plane (one ``python -m opendiloco_tpu.train`` process per
worker + one rendezvous daemon), 2m model on the learnable ramp stream
(``--fake-data-mode ramp``: uniform-random fake data sits at its entropy
floor, making a loss-descent gate a coin flip), with the ODTP_CHAOS
fault plane armed end to end:

- every worker injects random connection drops + RPC latency
  (``drop_conn``/``delay_ms``, per-rank seed so runs replay);
- the rendezvous daemon blacks out mid-soak (``blackout_rdv``) and the
  workers must failover/backoff through it;
- the galaxy runs the HIERARCHICAL outer round (``ODTP_HIER=1``, two
  explicit sites) with the SIGKILL target pinned as a preferred
  aggregator (``ODTP_HIER_AGG``), so the kill lands on an elected
  aggregator and the survivors must re-elect without a hang;
- the parent SIGKILLs that worker mid-run and restarts it WITHOUT
  ``--diloco.skip-load-from-peers`` so the straggler re-onboards through
  the (fp16-compressed) fetch_state path.

The soak also runs with the OBSERVABILITY plane armed (``ODTP_OBS=1``)
and gates that the galaxy overseer + flight recorders actually caught
the injected trouble:

- one rank runs with ``straggle_inner_ms`` chaos (slow-host emulation)
  and must be named by an ``anomaly_straggler`` trip somewhere in the
  galaxy (the tokens/s signal gossips via the overseer roll-ups);
- the SIGKILLed rank must be named by an ``anomaly_dead_peer`` trip on
  a survivor (an elastic round missing a previously-grouped peer);
- every worker -- including the killed incarnation -- must leave a
  ``blackbox-*.json`` flight-recorder dump, and the merged postmortem
  (scripts/odtp_postmortem.py) must cover every completed round;
- some survivor's own overseer matrix must converge to all N workers.

The obs verdict + galaxy matrix + merged timeline is banked to
OBS_GALAXY.json next to CHAOS_SOAK.json.

The soak passes iff every outer round completed (full or elastic), loss
descended, a replacement aggregator was elected while the killed one was
down, there are zero error rows, and the observability gates hold. The
verdict + per-worker round/fault accounting is banked to CHAOS_SOAK.json
at the repo root:

    python scripts/chaos_soak.py [--workers 8] [--rounds 6] [--out ...]
    python scripts/chaos_soak.py --selftest   # 4-worker CI variant

``--gossip`` runs the barrier-free NoLoCo pair-round leg instead: an
in-process loopback galaxy under membership churn (one worker leaves
mid-soak, one joins in its place) plus stale-view probe rounds against
the departed worker, gating zero error rows and exact error-feedback
residual conservation across every dropped round. Banked additively
into CHAOS_SOAK.json under ``"gossip_leg"``.
"""
import argparse
import glob
import importlib.util
import json
import os
import pickle
import re
import shutil
import signal
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

WORKER_CHAOS = "seed={seed};drop_conn=0.05;delay_ms=5..30"
DAEMON_CHAOS = "seed=99;blackout_rdv=r3;blackout_s=2.0"
# slow-host emulation for ONE rank: injected inside the inner step, so its
# tokens/s collapses asymmetrically (what the straggler watchdog keys on).
# the sleep must dominate the multi-second step times a CPU-contended
# loopback galaxy already has, or the signal drowns in scheduler noise
STRAGGLE_INNER = "straggle_inner_ms=8000..10000"
# outer-send delay for the kill target: widens its in-round window so the
# SIGKILL reliably lands mid-round and the black box keeps a partial round
KILL_RANK_EXTRA = "straggle_ms=800..1500"


def hier_sites(workers: int) -> tuple[str, str]:
    """Two-site galaxy over the train peer ids (``worker-<rank>``):
    first half / second half, with the LAST rank of each site the
    preferred aggregator -- so the soak's default SIGKILL target (the
    last rank) is an elected aggregator and the kill exercises
    re-election, not just elastic rescale."""
    ids = [f"worker-{r}" for r in range(workers)]
    half = max(1, workers // 2)
    sites = [ids[:half], ids[half:]] if workers >= 2 else [ids]
    site_spec = ";".join("|".join(s) for s in sites)
    agg_spec = "|".join(s[-1] for s in sites)
    return site_spec, agg_spec


# a choice, not a fallback: the soak exercises the socket plane, and its
# worker subprocesses train on a 4-device CPU mesh wherever it runs. Every
# report this script writes carries the stamp.
WORKER_DEVICES = {"platform": "cpu", "device_kind": "cpu", "device_count": 4}
# the gossip/async legs are host-only: numpy payloads over loopback
# backends, no device work at all
from opendiloco_tpu.utils.device import HOST_ONLY  # noqa: E402


def worker_env(
    rank: int, workers: int, obs_dir: str, straggle_rank: int, kill_rank: int
) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = WORKER_DEVICES["platform"]
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        f"{WORKER_DEVICES['device_count']}"
    )
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    spec = WORKER_CHAOS.format(seed=7 + rank)
    if rank == straggle_rank:
        spec += ";" + STRAGGLE_INNER
    if rank == kill_rank:
        spec += ";" + KILL_RANK_EXTRA
    env["ODTP_CHAOS"] = spec
    # observability plane: overseer roll-ups gossip on the rendezvous
    # channels, watchdogs run per round, and the flight recorder autodumps
    # every 0.5s-rate-limited trigger -- tight enough that a SIGKILLed
    # worker's on-disk black box is at most half a second stale
    env["ODTP_OBS"] = "1"
    env["ODTP_OBS_DIR"] = obs_dir
    env["ODTP_OBS_BLACKBOX_FLUSH_S"] = "0.5"
    env["ODTP_WATCHDOG_STRAGGLER_X"] = "1.5"
    env["ODTP_WATCHDOG_STALL_S"] = "240"
    # close matchmaking on the full galaxy when everyone is alive, so
    # elastic (partial) rounds appear exactly when a worker is down --
    # which is what the re-election assertion below keys on
    env["ODTP_EXPECT_PEERS"] = str(workers)
    site_spec, agg_spec = hier_sites(workers)
    env["ODTP_HIER"] = "1"
    env["ODTP_SITES"] = site_spec
    env["ODTP_HIER_AGG"] = agg_spec
    return env


def spawn_daemon() -> tuple[subprocess.Popen, str]:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["ODTP_CHAOS"] = DAEMON_CHAOS
    d = subprocess.Popen(
        [
            sys.executable, "-m", "opendiloco_tpu.diloco.rendezvous",
            "--host", "127.0.0.1", "--port", "0",
        ],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO,
    )
    while True:
        line = d.stdout.readline()
        assert line, "rendezvous daemon died before announcing its port"
        if "initial_peers =" in line:
            return d, line.strip().split()[-1].replace("0.0.0.0", "127.0.0.1")


def spawn_worker(
    rank: int, address: str, log_path: str, args, *, onboard: bool
) -> subprocess.Popen:
    cli = [
        sys.executable, "-m", "opendiloco_tpu.train",
        "--path-model", args.model,
        "--fake-data",
        "--fake-data-mode", "ramp",
        "--seq-length", "64",
        "--per-device-train-batch-size", "4",
        "--total-batch-size", "32",
        "--lr", "3e-3",
        "--warmup-steps", "4",
        "--total-steps", str(args.rounds * args.local_steps),
        "--precision", "fp32",
        "--metric-logger-type", "dummy",
        "--project", log_path,
        "--no-ckpt.interval",
        "--diloco.local-steps", str(args.local_steps),
        "--diloco.initial-peers", address,
        "--diloco.world-rank", str(rank),
        "--diloco.galaxy-size", str(args.workers),
        "--diloco.matchmaking-time", "3.0",
        "--diloco.averaging-timeout", "60",
        "--diloco.all-reduce-strategy", "no_wait",
        "--diloco.backend", "tcp",
    ]
    if not onboard:
        cli.append("--diloco.skip-load-from-peers")
    return subprocess.Popen(
        cli, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=worker_env(
            rank, args.workers, args.obs_dir, args.straggle_rank,
            args.kill_rank,
        ),
        cwd=REPO,
    )


def wait_for_midround_evidence(
        obs_dir: str, rank: int, after_first_round_s: float) -> bool:
    """Block until rank's own flight recorder PROVES it is mid-round: a
    round-tagged span whose round its health rows don't contain yet.
    Killing at that moment guarantees the partial-round evidence the
    postmortem gate wants is already on disk (the 0.5s-flushed dump we
    just read IS the file a SIGKILL leaves behind). A blind sleep can
    land before the first (compile-dominated) round even completes.

    Phase 1 waits for the first completed round with only a coarse
    backstop (compile time varies wildly across hosts); phase 2 gives up
    ``after_first_round_s`` later so a kill always happens."""
    def box():
        for p in glob.glob(os.path.join(obs_dir, f"blackbox-{rank}-*.json")):
            try:
                with open(p) as f:
                    return json.load(f)
            except Exception:
                continue
        return None

    deadline = None
    backstop = time.time() + 1800.0  # a worker that never rounds at all
    while time.time() < backstop:
        b = box()
        if b is not None:
            done = {str(h.get("round")) for h in b.get("health", [])}
            if done:
                if deadline is None:
                    deadline = time.time() + after_first_round_s
                for e in b.get("events", []):
                    r = (e.get("args") or {}).get("round")
                    if r and str(r).split(":")[0] not in done:
                        print(f"rank {rank} mid-round "
                              f"({str(r).split(':')[0]}): killing now")
                        return True
        if deadline is not None and time.time() > deadline:
            print(f"rank {rank}: no mid-round evidence within "
                  f"{after_first_round_s:.0f}s of its first round; "
                  "killing anyway")
            return False
        time.sleep(0.25)
    print(f"rank {rank}: never completed a round; killing anyway")
    return False


def gossip_leg(args) -> int:
    """Barrier-free NoLoCo pair-round soak under membership churn.

    An in-process loopback galaxy runs ``--rounds`` gossip epochs on the
    4-bit + error-feedback wire. At the mid-soak boundary one worker
    LEAVES (closes without announcing) and a new worker JOINS in its
    place — the survivors' next schedules must simply pair over the new
    membership view, no rendezvous, no barrier in the data plane (the
    epoch barrier here is test scaffolding that makes the churn boundary
    deterministic, not part of the protocol). Afterwards a survivor runs
    probe rounds against the DEAD worker through a deliberately stale
    membership view — the churn-outruns-view case — which must resolve
    as dropped-round non-events.

    Gates: every surviving worker (and the joiner) completes all its
    epochs; zero error rows (drops are non-events, exceptions are not);
    the per-partner error-feedback residual mass is EXACTLY conserved
    across every dropped round; every round is a pair (group <= 2); the
    pair mailbox ends empty. Banked additively into CHAOS_SOAK.json
    under ``"gossip_leg"``.
    """
    import threading

    from opendiloco_tpu.diloco.gossip import GossipPlane
    from opendiloco_tpu.diloco.loopback import LoopbackBackend, LoopbackWorld
    from opendiloco_tpu.diloco.outer_optimizer import noloco_step

    n = min(args.workers, 4) if args.selftest else min(args.workers, 6)
    n -= n % 2  # keep membership even so self-rounds stay a non-factor
    rounds = args.rounds
    churn_at = max(1, rounds // 2)
    shapes = ((64, 8), (33,), (16, 4))
    idxs = list(range(len(shapes)))
    t0 = time.time()

    # latency jitter + transient connection drops on the pair exchanges,
    # same fault plane the TCP soak arms (seeded: runs replay)
    prev_chaos = os.environ.get("ODTP_CHAOS")
    os.environ["ODTP_CHAOS"] = "seed=13;drop_conn=0.05;delay_ms=1..15"

    world = LoopbackWorld(n, compression="blockwise4bit")
    backends = world.make_backends()
    planes = [
        GossipPlane(
            b, len(shapes), compression="blockwise4bit", error_feedback=True
        )
        for b in backends
    ]
    leave_rank = n - 1
    leaver_gone = threading.Event()
    joinbox: dict = {}

    def admit_joiner():
        # barrier action at the churn epoch: runs once, after every party
        # arrived and before any is released — so epoch ``churn_at``'s
        # membership view is the same for every scheduler
        leaver_gone.wait(timeout=60.0)
        b = LoopbackBackend(world, f"peer-{n}")
        joinbox["backend"] = b
        joinbox["plane"] = GossipPlane(
            b, len(shapes), compression="blockwise4bit", error_feedback=True
        )

    barriers = [
        threading.Barrier(n, action=admit_joiner if e == churn_at else None)
        for e in range(rounds)
    ]

    errors: list[str] = []
    ef_violations: list[str] = []
    dropped = [0]
    completed: dict[str, int] = {}
    stat_lock = threading.Lock()

    def guarded_exchange(plane, **kw):
        before = plane.residual_mass()
        res = plane.exchange(**kw)
        if res is None:
            after = plane.residual_mass()
            with stat_lock:
                dropped[0] += 1
                if after != before:
                    ef_violations.append(
                        f"{plane.backend.peer_id}: dropped round changed "
                        f"residual mass {before!r} -> {after!r}"
                    )
        return res

    def run_epochs(backend, plane, rank_seed, first, last, skip_first=False):
        rng = np.random.default_rng(100 + rank_seed)
        masters = [rng.standard_normal(s).astype(np.float32) for s in shapes]
        bufs = [np.zeros_like(m) for m in masters]
        done = 0
        for e in range(first, last):
            if not (skip_first and e == first):
                barriers[e].wait()
            pgs = [
                (rng.standard_normal(s) * 0.01).astype(np.float32)
                for s in shapes
            ]
            res = guarded_exchange(
                plane, epoch=e, frag_id=0, idxs=idxs, masters=masters,
                bufs=bufs, pgs=pgs, timeout=30.0,
            )
            if res is not None:
                mix_m, mix_b, avg_g, _partner, _grp = res
                masters, bufs = noloco_step(
                    mix_m, mix_b, avg_g, lr=0.7, momentum=0.9, nesterov=True
                )
            done += 1
        if not all(np.isfinite(m).all() for m in masters):
            raise RuntimeError(f"{backend.peer_id}: non-finite master")
        with stat_lock:
            completed[backend.peer_id] = done

    def original_worker(rank):
        try:
            last = churn_at if rank == leave_rank else rounds
            run_epochs(backends[rank], planes[rank], rank, 0, last)
            if rank == leave_rank:
                backends[rank].close()  # leaves without announcing
                leaver_gone.set()
        except Exception as exc:  # pragma: no cover - banked as evidence
            with stat_lock:
                errors.append(f"{backends[rank].peer_id}: {exc!r}")
            leaver_gone.set()

    def joiner_worker():
        try:
            # the backend is created by the barrier action the moment the
            # churn epoch's barrier trips; the first wait is what admits
            # us, so the churn epoch itself is exchanged without another
            barriers[churn_at].wait()
            run_epochs(
                joinbox["backend"], joinbox["plane"], n, churn_at, rounds,
                skip_first=True,
            )
        except Exception as exc:  # pragma: no cover - banked as evidence
            with stat_lock:
                errors.append(f"joiner: {exc!r}")

    threads = [
        threading.Thread(target=original_worker, args=(r,)) for r in range(n)
    ] + [threading.Thread(target=joiner_worker)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    # stale-view probes: a survivor keeps scheduling against the DEAD
    # worker (its view outran by churn) — every probe must drop,
    # conserving the residual it already holds.
    probe_drops = 0
    if not errors:
        survivor_b, survivor_p = backends[0], planes[0]
        dead_id = backends[leave_rank].peer_id
        orig_view = survivor_b.gossip_view
        survivor_b.gossip_view = lambda: (
            sorted([survivor_b.peer_id, dead_id]), None
        )
        try:
            rng = np.random.default_rng(999)
            for i in range(3):
                pgs = [
                    (rng.standard_normal(s) * 0.01).astype(np.float32)
                    for s in shapes
                ]
                masters = [np.zeros(s, np.float32) for s in shapes]
                res = guarded_exchange(
                    survivor_p, epoch=10_000 + i, frag_id=0, idxs=idxs,
                    masters=masters, bufs=None, pgs=pgs, timeout=10.0,
                )
                if res is None:
                    probe_drops += 1
        finally:
            survivor_b.gossip_view = orig_view

    if prev_chaos is None:
        os.environ.pop("ODTP_CHAOS", None)
    else:
        os.environ["ODTP_CHAOS"] = prev_chaos

    ledgers = [b.round_ledger for b in backends] + (
        [joinbox["backend"].round_ledger] if "backend" in joinbox else []
    )
    all_pairs = all(
        h.get("group_size", 0) <= 2 for led in ledgers for h in led
    )
    joiner_paired = any(
        h.get("group_size") == 2
        for h in (joinbox["backend"].round_ledger if "backend" in joinbox
                  else [])
    )
    expected = {backends[r].peer_id: (churn_at if r == leave_rank else rounds)
                for r in range(n)}
    if "backend" in joinbox:
        expected[joinbox["backend"].peer_id] = rounds - churn_at
    residual_mass = round(
        sum(p.residual_mass() for p in planes)
        + (joinbox["plane"].residual_mass() if "plane" in joinbox else 0.0), 6
    )
    gates = {
        "all_epochs_completed": completed == expected,
        "zero_error_rows": not errors,
        "every_probe_dropped_not_errored": probe_drops == 3,
        "ef_mass_conserved_across_drops": not ef_violations,
        "every_round_is_a_pair": all_pairs,
        "joiner_got_paired": joiner_paired,
        "pair_mailbox_empty": not world._pairbox,
    }
    ok = all(gates.values())
    report = {
        "bench": "gossip_chaos_leg",
        **HOST_ONLY,
        "workers": n,
        "rounds": rounds,
        "churn_epoch": churn_at,
        "left": backends[leave_rank].peer_id,
        "joined": joinbox["backend"].peer_id if "backend" in joinbox else None,
        "chaos": "seed=13;drop_conn=0.05;delay_ms=1..15",
        "compression": "blockwise4bit",
        "error_feedback": True,
        "gates": gates,
        "passed": ok,
        "dropped_rounds": dropped[0],
        "stale_view_probe_drops": probe_drops,
        "ef_violations": ef_violations,
        "errors": errors,
        "completed": completed,
        "expected": expected,
        "final_residual_mass": residual_mass,
        "elapsed_s": round(time.time() - t0, 1),
    }
    try:
        with open(args.out) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {}
    doc["gossip_leg"] = report
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(json.dumps(report, indent=2))
    print("GOSSIP CHAOS LEG " + ("PASSED" if ok else "FAILED"))
    return 0 if ok else 1


def async_leg(args) -> int:
    """Bounded-staleness ASYNC gossip soak under churn: the free-running
    round clock (ODTP_ASYNC_STALENESS) on a skewed loopback galaxy where
    half the workers run their inner phase at half speed — so epoch
    clocks genuinely drift — and one worker leaves mid-soak WITHOUT
    announcing. No barrier anywhere: workers match whoever is in-window
    when they arrive, self-round after patience otherwise, and the
    leaver's absence must surface only as self-rounds or dropped-round
    non-events, never as an error.

    Gates: every worker completes its full epoch budget (the leaver its
    truncated one); zero error rows; per-partner EF residual mass is
    EXACTLY conserved across every dropped and self round; matching
    still paired workers (the async plane did real mixing, not a galaxy
    of hermits); every round is a pair. Banked additively into
    CHAOS_SOAK.json under ``"async_leg"``.
    """
    import threading

    from opendiloco_tpu.diloco.gossip import GossipPlane
    from opendiloco_tpu.diloco.loopback import LoopbackWorld
    from opendiloco_tpu.diloco.outer_optimizer import noloco_step

    n = 4 if args.selftest else 6
    window, patience = 2, 0.3
    epochs_1x = max(6, args.rounds * 2)
    # half-speed inner phases on the odd ranks: the epoch clocks drift by
    # construction, so matching exercises the staleness window for real
    skews = [1 if r % 2 == 0 else 2 for r in range(n)]
    budgets = [max(3, epochs_1x // x) for x in skews]
    leave_rank = n - 1
    budgets[leave_rank] = max(2, budgets[leave_rank] // 2)
    inner_s = 0.02
    shapes = ((64, 8), (33,), (16, 4))
    idxs = list(range(len(shapes)))
    t0 = time.time()

    chaos_spec = "seed=17;drop_conn=0.05;delay_ms=1..15"
    saved = {
        k: os.environ.get(k)
        for k in ("ODTP_CHAOS", "ODTP_ASYNC_STALENESS",
                  "ODTP_ASYNC_PATIENCE_S")
    }
    os.environ["ODTP_CHAOS"] = chaos_spec
    os.environ["ODTP_ASYNC_STALENESS"] = str(window)
    os.environ["ODTP_ASYNC_PATIENCE_S"] = str(patience)

    world = LoopbackWorld(n, compression="blockwise4bit")
    backends = world.make_backends()
    planes = [
        GossipPlane(
            b, len(shapes), compression="blockwise4bit", error_feedback=True
        )
        for b in backends
    ]

    errors: list[str] = []
    ef_violations: list[str] = []
    completed: dict[str, int] = {}
    paired = [0] * n
    selfed = [0] * n
    dropped = [0] * n
    lags: list[int] = []
    stat_lock = threading.Lock()

    def worker(rank: int) -> None:
        try:
            rng = np.random.default_rng(300 + rank)
            masters = [
                rng.standard_normal(s).astype(np.float32) for s in shapes
            ]
            bufs = [np.zeros_like(m) for m in masters]
            plane = planes[rank]
            for e in range(budgets[rank]):
                time.sleep(inner_s * skews[rank])  # the skewed inner phase
                pgs = [
                    (rng.standard_normal(s) * 0.01).astype(np.float32)
                    for s in shapes
                ]
                before = plane.residual_mass()
                res = plane.exchange(
                    epoch=e, frag_id=0, idxs=idxs, masters=masters,
                    bufs=bufs, pgs=pgs, timeout=15.0,
                )
                with stat_lock:
                    if res is None:
                        dropped[rank] += 1
                    elif res[4] == 1:
                        selfed[rank] += 1
                    else:
                        paired[rank] += 1
                        lag = backends[rank].last_round_health.get("pair_lag")
                        if lag is not None:
                            lags.append(int(lag))
                    if res is None or res[4] == 1:
                        # neither a drop nor a self-round may touch the
                        # per-partner residual — conservation is exact
                        after = plane.residual_mass()
                        if after != before:
                            ef_violations.append(
                                f"{backends[rank].peer_id}: non-pair round "
                                f"changed residual {before!r} -> {after!r}"
                            )
                if res is not None:
                    mix_m, mix_b, avg_g, _partner, _grp = res
                    masters, bufs = noloco_step(
                        mix_m, mix_b, avg_g, lr=0.7, momentum=0.9,
                        nesterov=True,
                    )
            if not all(np.isfinite(m).all() for m in masters):
                raise RuntimeError(f"{backends[rank].peer_id}: non-finite")
            if rank == leave_rank:
                backends[rank].close()  # leaves without announcing
            with stat_lock:
                completed[backends[rank].peer_id] = budgets[rank]
        except Exception as exc:  # pragma: no cover - banked as evidence
            with stat_lock:
                errors.append(f"{backends[rank].peer_id}: {exc!r}")

    threads = [threading.Thread(target=worker, args=(r,)) for r in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v

    all_pairs = all(
        h.get("group_size", 0) <= 2 for b in backends for h in b.round_ledger
    )
    expected = {backends[r].peer_id: budgets[r] for r in range(n)}
    gates = {
        "all_epochs_completed": completed == expected,
        "zero_error_rows": not errors,
        "ef_mass_conserved_across_drops": not ef_violations,
        "async_matching_paired_workers": sum(paired) > 0,
        "every_round_is_a_pair": all_pairs,
        "pair_mailbox_empty": not world._pairbox,
    }
    ok = all(gates.values())
    report = {
        "bench": "async_chaos_leg",
        **HOST_ONLY,
        "workers": n,
        "window": window,
        "patience_s": patience,
        "inner_step_s": inner_s,
        "skews": skews,
        "epoch_budgets": budgets,
        "left_early": backends[leave_rank].peer_id,
        "chaos": chaos_spec,
        "compression": "blockwise4bit",
        "error_feedback": True,
        "gates": gates,
        "passed": ok,
        "paired_rounds": sum(paired),
        "self_rounds": sum(selfed),
        "dropped_rounds": sum(dropped),
        "pair_lags_observed": sorted(set(lags)),
        "max_pair_lag": max(lags) if lags else None,
        "ef_violations": ef_violations,
        "errors": errors,
        "completed": completed,
        "expected": expected,
        "elapsed_s": round(time.time() - t0, 1),
    }
    try:
        with open(args.out) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {}
    doc["async_leg"] = report
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(json.dumps(report, indent=2))
    print("ASYNC CHAOS LEG " + ("PASSED" if ok else "FAILED"))
    return 0 if ok else 1


_FAULT_RE = re.compile(r"chaos: injected (\w+)")


def fault_counts(*texts: str) -> dict:
    counts: dict[str, int] = {}
    for t in texts:
        for m in _FAULT_RE.finditer(t or ""):
            counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


def read_rows(path: str) -> list[dict]:
    try:
        with open(path, "rb") as f:
            return pickle.load(f)
    except Exception:
        return []


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--model", default="2m")
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--local-steps", type=int, default=3)
    ap.add_argument("--kill-rank", type=int, default=-1,
                    help="rank to SIGKILL+restart (default: last)")
    ap.add_argument("--kill-after-s", type=float, default=50.0,
                    help="SIGKILL deadline after the kill rank's first "
                    "completed round; the kill fires as soon as its flight "
                    "recorder shows mid-round evidence (usually seconds)")
    ap.add_argument("--restart-delay-s", type=float, default=8.0,
                    help="downtime before the killed rank restarts, so "
                    "survivors provably complete elastic rounds without it "
                    "(what the dead-peer watchdog keys on)")
    ap.add_argument("--straggle-rank", type=int, default=1,
                    help="rank that runs with straggle_inner_ms chaos (the "
                    "straggler the watchdogs must name)")
    ap.add_argument("--timeout", type=float, default=1200.0)
    ap.add_argument("--out", default=os.path.join(REPO, "CHAOS_SOAK.json"))
    ap.add_argument("--obs-out", default=os.path.join(REPO, "OBS_GALAXY.json"))
    ap.add_argument("--workdir", default="/tmp/odtp_chaos_soak")
    ap.add_argument(
        "--selftest", action="store_true",
        help="small galaxy (4 workers, 4 rounds), artifacts under the "
        "workdir, same hard gates incl. blackbox dumps + postmortem (CI)",
    )
    ap.add_argument(
        "--gossip", action="store_true",
        help="run the NoLoCo gossip churn legs instead (in-process pair "
        "rounds, leave+join mid-soak, EF conservation gates, plus the "
        "bounded-staleness async-matching leg under skew + churn); banked "
        "additively under CHAOS_SOAK.json \"gossip_leg\"/\"async_leg\"",
    )
    args = ap.parse_args()
    if args.selftest:
        args.workers = min(args.workers, 4)
        args.rounds = min(args.rounds, 4)
        args.local_steps = min(args.local_steps, 2)
        args.kill_after_s = min(args.kill_after_s, 30.0)
        args.out = os.path.join(args.workdir, "CHAOS_SOAK.json")
        args.obs_out = os.path.join(args.workdir, "OBS_GALAXY.json")
    kill_rank = args.kill_rank if args.kill_rank >= 0 else args.workers - 1
    args.kill_rank = kill_rank
    if args.straggle_rank == kill_rank:
        args.straggle_rank = (kill_rank + 1) % args.workers
    args.obs_dir = os.path.join(args.workdir, "obs")
    if args.gossip:
        os.makedirs(args.workdir, exist_ok=True)
        rc = gossip_leg(args)
        return max(rc, async_leg(args))

    os.makedirs(args.workdir, exist_ok=True)
    shutil.rmtree(args.obs_dir, ignore_errors=True)  # stale dumps poison gates
    os.makedirs(args.obs_dir, exist_ok=True)
    t0 = time.time()
    daemon, address = spawn_daemon()
    print(f"rendezvous (blackout-armed) at {address}")

    logs = {
        r: os.path.join(args.workdir, f"soak_w{r}.pkl")
        for r in range(args.workers)
    }
    procs = {
        r: spawn_worker(r, address, logs[r], args, onboard=False)
        for r in range(args.workers)
    }
    print(f"{args.workers} workers up; SIGKILL of rank {kill_rank} "
          f"(preferred aggregator of its site) once its flight recorder "
          f"shows it mid-round, deadline {args.kill_after_s:.0f}s after "
          "first round")

    wait_for_midround_evidence(args.obs_dir, kill_rank, args.kill_after_s)
    procs[kill_rank].send_signal(signal.SIGKILL)
    killed_out, killed_err = procs[kill_rank].communicate(timeout=30)
    print(f"rank {kill_rank} SIGKILLed; restart in "
          f"{args.restart_delay_s:.0f}s (downtime window for the dead-peer "
          "watchdog) with peer onboarding")
    time.sleep(args.restart_delay_s)
    restart_log = os.path.join(args.workdir, f"soak_w{kill_rank}_restart.pkl")
    restart = spawn_worker(
        kill_rank, address, restart_log, args, onboard=True
    )

    outs: dict[int, tuple[str, str]] = {}
    deadline = time.time() + args.timeout
    fails: list[str] = []
    survivors = {r: p for r, p in procs.items() if r != kill_rank}
    survivors[kill_rank] = restart
    for r, p in sorted(survivors.items()):
        try:
            outs[r] = p.communicate(timeout=max(10.0, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()
            o, e = p.communicate(timeout=30)
            outs[r] = (o, e)
            fails.append(f"rank {r}: timed out")
        if p.returncode != 0 and f"rank {r}" not in " ".join(fails):
            fails.append(
                f"rank {r}: exit {p.returncode}\n{outs[r][1][-1500:]}"
            )
    daemon.terminate()
    try:
        daemon_out = daemon.communicate(timeout=15)[0]
    except subprocess.TimeoutExpired:
        daemon.kill()
        daemon_out = daemon.communicate()[0]

    # -- verdict ------------------------------------------------------------
    per_worker = []
    error_rows = 0
    for r in range(args.workers):
        rows = read_rows(restart_log if r == kill_rank else logs[r])
        finite = [row for row in rows if np.isfinite(row.get("Loss", np.nan))]
        error_rows += len(rows) - len(finite)
        elastic = sum(1 for row in rows if row.get("elastic"))
        # mean over the first/last 3 rows: single-step loss on fake data
        # is noise-dominated and a one-row comparison flaps
        losses = [row["Loss"] for row in finite]
        per_worker.append({
            "rank": r,
            "restarted": r == kill_rank,
            "steps": len(rows),
            "final_outer_epoch": rows[-1]["outer_epoch"] if rows else None,
            "loss_first": round(float(np.mean(losses[:3])), 4)
            if losses else None,
            "loss_last": round(float(np.mean(losses[-3:])), 4)
            if losses else None,
            "elastic_rounds_seen": elastic,
            "faults": fault_counts(*(outs.get(r) or ("", ""))),
        })

    # aggregator re-election: the metric rows carry the hier plan's
    # aggregator list per landed round. While the killed rank was down,
    # survivors must have elected a replacement (elastic rows without the
    # kill peer); once it rejoined, the preferred-aggregator pin should
    # win again (last full-group row has it back).
    kill_peer = f"worker-{kill_rank}"
    agg_rows: list[tuple[bool, list]] = []
    for r in range(args.workers):
        if r == kill_rank:
            continue
        for row in read_rows(logs[r]):
            if row.get("hier_aggregators"):
                agg_rows.append(
                    (bool(row.get("elastic")), row["hier_aggregators"])
                )
    kill_was_aggregator = any(kill_peer in aggs for _, aggs in agg_rows)
    reelected = any(
        kill_peer not in aggs for el, aggs in agg_rows if el
    )
    last_aggs = next(
        (row["hier_aggregators"]
         for row in reversed(read_rows(logs[0]))
         if row.get("hier_aggregators")), [],
    )
    aggregator_reelected = kill_was_aggregator and reelected

    # -- observability verdict: did the overseer/watchdogs catch it? --------
    pm_spec = importlib.util.spec_from_file_location(
        "odtp_postmortem", os.path.join(REPO, "scripts", "odtp_postmortem.py")
    )
    pm_mod = importlib.util.module_from_spec(pm_spec)
    pm_spec.loader.exec_module(pm_mod)
    boxes = pm_mod.load_boxes(args.obs_dir)
    pm = pm_mod.merge_postmortem(boxes) if boxes else {}
    anomalies = pm.get("anomalies") or []
    anomaly_counters = pm.get("anomaly_counters") or {}
    timeline = pm.get("timeline") or []
    straggle_peer = f"worker-{args.straggle_rank}"

    ranks_with_box = {str(b.get("worker")) for b in boxes}
    blackbox_all = {str(r) for r in range(args.workers)} <= ranks_with_box
    # pid-suffixed dumps: the killed incarnation's black box must still be
    # on disk next to its replacement's
    killed_box_preserved = sum(
        1 for b in boxes if str(b.get("worker")) == str(kill_rank)
    ) >= 2
    sigkill_detected = any(
        a.get("kind") == "dead_peer" and a.get("subject") == kill_peer
        for a in anomalies
    )
    straggler_detected = any(
        a.get("kind") == "straggler" and a.get("subject") == straggle_peer
        for a in anomalies
    )
    counters_nonzero = (
        any(k.startswith("anomaly_dead_peer") for k in anomaly_counters)
        and any(k.startswith("anomaly_straggler") for k in anomaly_counters)
    )
    matrix_full = len(pm.get("galaxy") or {}) >= args.workers
    converged = max(
        (len(b.get("galaxy") or {}) for b in boxes), default=0
    ) >= args.workers
    grads_epochs = sorted({
        int(m.group(1))
        for row in timeline if row["workers_completed"]
        # a blocking round is an all-reduce a piece: grads-p0-epoch-N, ...
        for m in [re.match(r"grads(?:-p\d+)?-epoch-(\d+)$", row["round"])] if m
    })
    rounds_covered = bool(grads_epochs) and (
        len(grads_epochs) >= args.rounds
        and grads_epochs
        == list(range(grads_epochs[0], grads_epochs[0] + len(grads_epochs)))
    )
    killed_partial = any(
        str(kill_rank) in row["workers_partial"] for row in timeline
    )
    obs_gates = {
        "blackbox_dump_per_worker": blackbox_all,
        "killed_incarnation_box_preserved": killed_box_preserved,
        "sigkill_detected_as_dead_peer": sigkill_detected,
        "straggler_detected": straggler_detected,
        "anomaly_counters_nonzero": counters_nonzero,
        "galaxy_matrix_full": matrix_full,
        "some_worker_converged_to_full_matrix": converged,
        "postmortem_covers_every_completed_round": rounds_covered,
        "killed_worker_final_partial_round": killed_partial,
    }
    obs_ok = all(
        v for k, v in obs_gates.items()
        # the partial-round gate needs the kill to land mid-exchange; the
        # widened in-round window makes that near-certain at full scale,
        # but the 4-worker selftest keeps it informational
        if not (args.selftest and k == "killed_worker_final_partial_round")
    )
    obs_report = {
        "bench": "obs_galaxy",
        **WORKER_DEVICES,
        "model": args.model,
        "workers": args.workers,
        "rounds": args.rounds,
        "backend": "tcp",
        "chaos": {
            "sigkill_rank": kill_rank,
            "restart_delay_s": args.restart_delay_s,
            "straggle_rank": args.straggle_rank,
            "straggle_spec": STRAGGLE_INNER,
            "kill_rank_extra": KILL_RANK_EXTRA,
        },
        "obs_env": {
            "ODTP_OBS_BLACKBOX_FLUSH_S": "0.5",
            "ODTP_WATCHDOG_STRAGGLER_X": "1.5",
            "ODTP_WATCHDOG_STALL_S": "240",
        },
        "gates": obs_gates,
        "passed": obs_ok,
        "workers_in_matrix": len(pm.get("galaxy") or {}),
        "matrix_coverage_per_dump": {
            b["_file"]: len(b.get("galaxy") or {}) for b in boxes
        },
        "anomaly_counters": anomaly_counters,
        "grads_epochs_on_timeline": grads_epochs,
        "postmortem": pm,
    }
    with open(args.obs_out, "w") as f:
        json.dump(obs_report, f, indent=1)
        f.write("\n")
    print(
        f"banked {args.obs_out}: {obs_report['workers_in_matrix']} workers "
        f"in matrix, {len(timeline)} rounds on the merged timeline, "
        f"anomaly counters {anomaly_counters}"
    )

    ref = per_worker[0]
    rounds_completed = ref["final_outer_epoch"] or 0
    every_round_completed = (
        not fails
        and error_rows == 0
        and rounds_completed >= args.rounds
        and all(
            w["steps"] == args.rounds * args.local_steps for w in per_worker
        )
    )
    loss_descended = bool(
        ref["loss_first"] is not None
        and ref["loss_last"] is not None
        and ref["loss_last"] < ref["loss_first"]
    )
    daemon_faults = fault_counts(daemon_out)
    report = {
        "bench": "chaos_soak",
        **WORKER_DEVICES,
        "model": args.model,
        "data": "fake ramp stream (learnable; loss gate is real descent)",
        "workers": args.workers,
        "rounds": args.rounds,
        "local_steps": args.local_steps,
        "backend": "tcp",
        "chaos": {
            "worker_spec": WORKER_CHAOS.format(seed="7+rank"),
            "daemon_spec": DAEMON_CHAOS,
            "sigkill": {"rank": kill_rank, "after_s": args.kill_after_s,
                        "restarted_with_onboarding": True},
            "hier": {
                "sites": hier_sites(args.workers)[0],
                "preferred_aggregators": hier_sites(args.workers)[1],
                "killed_peer": kill_peer,
            },
        },
        "every_round_completed": every_round_completed,
        "loss_descended": loss_descended,
        "aggregator_reelected": aggregator_reelected,
        "kill_was_aggregator": kill_was_aggregator,
        "final_aggregators": last_aggs,
        "hier_rounds_observed": len(agg_rows),
        "error_rows": error_rows,
        "failures": fails,
        "daemon_faults": daemon_faults,
        "total_faults_injected": sum(
            sum(w["faults"].values()) for w in per_worker
        ) + sum(daemon_faults.values()) + sum(
            fault_counts(killed_out, killed_err).values()
        ),
        "per_worker": per_worker,
        "obs": {"gates": obs_gates, "passed": obs_ok,
                "report": os.path.basename(args.obs_out)},
        "elapsed_s": round(time.time() - t0, 1),
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps(report, indent=2))
    ok = (every_round_completed and loss_descended and aggregator_reelected
          and obs_ok)
    print("CHAOS SOAK " + ("PASSED" if ok else "FAILED"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
