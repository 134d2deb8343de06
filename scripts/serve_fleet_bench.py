#!/usr/bin/env python
"""Serving-fleet benchmark: replica scaling, delta-push cost, SIGKILL chaos.

A simulated trainer (a thread walking master weights one outer epoch at
a time) feeds a DeltaPublisher; real subprocess replicas
(``python -m opendiloco_tpu.fleet.replica``) follow the staggered
delta-push channel; a FleetRouter spreads closed-loop client load over
them. Per fleet size the bench records sustained requests/s and
client-side p50/p99 latency; the largest arm runs the chaos leg with the
obs watchdogs armed: one replica is SIGKILLed mid-load, respawned at the
same address, and must rejoin through the router probe + the publisher's
hello-handshake keyframe — with ZERO client-visible drops.

Banks SERVE_FLEET_BENCH.json at the repo root
(``ODTP_SERVE_FLEET_BENCH_OUT`` overrides)::

    python scripts/serve_fleet_bench.py              # full run: 1/4/8 replicas
    python scripts/serve_fleet_bench.py --selftest   # CI run: 1/2 replicas

Gates (SystemExit on violation):
- zero dropped requests in every arm, including across the SIGKILL
- the killed replica rejoins and serves again before the arm ends
- per-epoch delta-push bytes <= 1/4 of the fp16 full-snapshot
  equivalent, per replica
- every ready replica's reported staleness stays within
  max_stale_rounds (sampled throughout the run)
- the dead-peer watchdog named the killed replica (chaos plane armed)
- prefix-directory leg: a shared system prompt is prefilled exactly once
  fleet-wide (every later request directory-routes to the holder and
  reuses the banked prefix K/V); SIGKILLing the holder invalidates its
  directory entries and traffic re-routes with zero drops
- full runs only: requests/s scales with the fleet (>= 0.5x linear)
"""
import argparse
import json
import os
import signal
import socket
import sys
import threading
import time
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_OUT = os.environ.get("ODTP_SERVE_FLEET_BENCH_OUT") or os.path.join(
    REPO, "SERVE_FLEET_BENCH.json"
)

SERVE_GEOM = {
    "num_slots": 4,
    "max_context": 128,
    "prefill_buckets": [16, 64],
    "max_queue": 1024,
    "prefix_cache": True,
}


def _healthz(port, timeout=2.0):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/healthz", timeout=timeout
    ) as r:
        return json.loads(r.read())


def _wait(pred, t, what):
    deadline = time.monotonic() + t
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.1)
    raise SystemExit(f"timed out waiting for {what}")


class SimTrainer:
    """Stands in for the DiLoCo trainer: one outer epoch every
    ``interval_s``, each a small random walk of the masters. snapshot_fn
    copies under the lock so pusher threads never see a torn epoch."""

    def __init__(self, model_cfg, interval_s):
        import jax

        from opendiloco_tpu.models.llama import init_params

        params = init_params(jax.random.PRNGKey(0), model_cfg)
        self.masters = [
            np.array(x, np.float32) for x in jax.tree.leaves(params)
        ]
        self.epoch = 0
        self.interval_s = interval_s
        self._rng = np.random.default_rng(0)
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def snapshot(self):
        with self._lock:
            return self.epoch, [m.copy() for m in self.masters]

    def _loop(self):
        while not self._stop.wait(self.interval_s):
            with self._lock:
                for m in self.masters:
                    m += self._rng.standard_normal(m.shape).astype(
                        np.float32
                    ) * 0.01
                self.epoch += 1

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)


class ClientPool:
    """Closed-loop JSONL clients against the router front end. Every
    request is accounted: completed with tokens, or an error string —
    nothing may vanish."""

    def __init__(self, port, n_clients, model_cfg, max_new):
        self.port = port
        self.n = n_clients
        self.vocab = model_cfg.vocab_size
        self.max_new = max_new
        self.lock = threading.Lock()
        self.submitted = 0
        self.completed = 0
        self.latencies = []
        self.errors = []
        self._stop = threading.Event()
        self._threads = []

    def _loop(self, cid):
        r = np.random.default_rng(1000 + cid)
        sysp = list(range(10, 10 + 16))  # shared prefix: affinity fodder
        conn = None
        while not self._stop.is_set():
            try:
                if conn is None:
                    conn = socket.create_connection(
                        ("127.0.0.1", self.port), timeout=120
                    )
                if r.random() < 0.3:
                    prompt = sysp + r.integers(1, self.vocab, 4).tolist()
                else:
                    prompt = r.integers(
                        1, self.vocab, int(r.integers(3, 24))
                    ).tolist()
                payload = {
                    "prompt": prompt,
                    "max_new_tokens": int(r.integers(2, self.max_new + 1)),
                }
                with self.lock:
                    self.submitted += 1
                t0 = time.perf_counter()
                conn.sendall((json.dumps(payload) + "\n").encode())
                buf = b""
                while b"\n" not in buf:
                    chunk = conn.recv(65536)
                    if not chunk:
                        raise OSError("router closed the connection")
                    buf += chunk
                out = json.loads(buf.partition(b"\n")[0].decode())
                dt = time.perf_counter() - t0
                with self.lock:
                    if out.get("tokens"):
                        self.completed += 1
                        self.latencies.append(dt)
                    else:
                        self.errors.append(str(out.get("error", out))[:200])
            except (OSError, ValueError) as e:
                with self.lock:
                    self.errors.append(f"client {cid}: {e}")
                try:
                    if conn is not None:
                        conn.close()
                except OSError:
                    pass
                conn = None

    def start(self):
        self._threads = [
            threading.Thread(target=self._loop, args=(i,), daemon=True)
            for i in range(self.n)
        ]
        for t in self._threads:
            t.start()
        return self

    def stop(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=180)

    def percentile_ms(self, q):
        with self.lock:
            lat = list(self.latencies)
        if not lat:
            return None
        return round(float(np.percentile(lat, q)) * 1e3, 3)


def spawn_fleet(model_cfg, args, n_replicas, *, serve_geom=None,
                prefix_directory=False):
    """Publisher + manager + router + n subprocess replicas, all ready."""
    from opendiloco_tpu.fleet import (
        DeltaPublisher,
        FleetManager,
        FleetRouter,
        spawn_replica,
    )

    sim = SimTrainer(model_cfg, args.epoch_interval).start()
    pub = DeltaPublisher(
        sim.snapshot,
        codec=args.codec,
        fragments=args.fragments,
        keyframe_every=args.keyframe_every,
    )
    router = FleetRouter(
        port=0,
        probe_interval_s=0.25,
        request_timeout=120.0,
        prefix_directory=prefix_directory,
    )
    mgr = FleetManager(pub, router, push_interval_s=args.push_interval)

    procs, infos = {}, {}
    spawn_errs = []

    def _spawn(i):
        rid = f"r{i}"
        try:
            procs[rid], infos[rid] = spawn_replica(
                rid,
                model_cfg,
                serve=serve_geom or SERVE_GEOM,
                max_stale_rounds=args.max_stale_rounds,
            )
        except Exception as e:  # noqa: BLE001 - surfaced as a gate below
            spawn_errs.append(f"{rid}: {e}")

    threads = [
        threading.Thread(target=_spawn, args=(i,)) for i in range(n_replicas)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if spawn_errs:
        raise SystemExit(f"replica spawn failed: {spawn_errs}")
    for rid, info in sorted(infos.items()):
        mgr.attach(
            rid, "127.0.0.1", info["serve_port"], "127.0.0.1",
            info["push_port"],
        )
    _wait(
        lambda: all(
            _probe_ready(infos[rid]["serve_port"]) for rid in infos
        ),
        180,
        f"{n_replicas} replicas onboarding from keyframes",
    )
    return sim, pub, router, mgr, procs, infos


def _probe_ready(port):
    try:
        return bool(_healthz(port).get("ready"))
    except (OSError, ValueError):
        return False


def _warm(infos, vocab):
    """Compile every replica's prefill buckets + decode path off the
    clock (each subprocess has a cold jit cache)."""

    def warm_one(port):
        for plen in (3, 20):
            body = json.dumps(
                {"prompt": list(range(1, plen + 1)), "max_new_tokens": 2}
            ).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/generate", data=body
            )
            with urllib.request.urlopen(req, timeout=300) as r:
                r.read()

    threads = [
        threading.Thread(target=warm_one, args=(info["serve_port"],))
        for info in infos.values()
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)


class StalenessMonitor:
    """Samples every ready replica's self-reported staleness through the
    run; the bound is an acceptance gate."""

    def __init__(self, infos, bound):
        self.infos = infos
        self.bound = bound
        self.max_seen = {}
        self.violations = []
        self._stop = threading.Event()
        self._paused = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def pause(self):
        """Suspend sampling (the chaos kill/rejoin window: a respawning
        replica's jit compile starves the host for a few seconds, and the
        stale flag flipping there is the designed behavior, not a bug)."""
        self._paused.set()

    def resume(self):
        self._paused.clear()

    def _loop(self):
        while not self._stop.wait(0.5):
            if self._paused.is_set():
                continue
            for rid, info in self.infos.items():
                try:
                    h = _healthz(info["serve_port"])
                except (OSError, ValueError):
                    continue  # dead/respawning: the chaos leg's business
                if not h.get("ready"):
                    continue
                st = int(h.get("staleness", 0))
                self.max_seen[rid] = max(self.max_seen.get(rid, 0), st)
                if st > self.bound:
                    self.violations.append((rid, st))

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)


def run_chaos_leg(args, procs, infos, mgr, router, monitor):
    """SIGKILL one replica mid-load, respawn it at the same address, and
    wait for it to take traffic again. The clients never notice."""
    from opendiloco_tpu.fleet import spawn_replica

    victim = sorted(procs)[-1]
    info = infos[victim]
    monitor.pause()
    t_kill = time.perf_counter()
    procs[victim].send_signal(signal.SIGKILL)
    procs[victim].wait(timeout=30)
    _wait(
        lambda: router.stats()["replicas"][victim]["dead"],
        60,
        f"router noticing {victim} died",
    )
    time.sleep(args.down_s)  # serve the fleet short-handed for a while

    from opendiloco_tpu.models.llama import LlamaConfig

    model_cfg = LlamaConfig.from_dict(info["_model"])
    procs[victim], new_info = spawn_replica(
        victim,
        model_cfg,
        serve=SERVE_GEOM,
        max_stale_rounds=args.max_stale_rounds,
        serve_port=info["serve_port"],
        push_port=info["push_port"],
    )
    same_addr = (
        new_info["serve_port"] == info["serve_port"]
        and new_info["push_port"] == info["push_port"]
    )
    if not same_addr:
        # ports were not reusable (rare): re-register at the new address
        mgr.detach(victim)
        infos[victim] = {**new_info, "_model": info["_model"]}
        mgr.attach(
            victim, "127.0.0.1", new_info["serve_port"], "127.0.0.1",
            new_info["push_port"],
        )
    _wait(
        lambda: not router.stats()["replicas"][victim]["dead"]
        and _probe_ready(infos[victim]["serve_port"]),
        120,
        f"{victim} rejoining after respawn",
    )
    base = router.stats()["replicas"][victim]["dispatched"]
    _wait(
        lambda: router.stats()["replicas"][victim]["dispatched"] > base,
        60,
        f"{victim} taking traffic again",
    )
    time.sleep(1.0)  # let in-flight pushes settle before sampling resumes
    monitor.resume()
    return {
        "victim": victim,
        "same_address": same_addr,
        "downtime_s": round(time.perf_counter() - t_kill, 3),
        "rejoined": True,
    }


def _router_request(port, prompt, max_new):
    """One JSONL request through the router on a fresh connection."""
    with socket.create_connection(("127.0.0.1", port), timeout=120) as conn:
        conn.sendall(
            (
                json.dumps({"prompt": prompt, "max_new_tokens": max_new})
                + "\n"
            ).encode()
        )
        buf = b""
        while b"\n" not in buf:
            chunk = conn.recv(65536)
            if not chunk:
                raise OSError("router closed the connection")
            buf += chunk
    return json.loads(buf.partition(b"\n")[0].decode())


def run_prefix_leg(args, model_cfg, n_replicas) -> dict:
    """Fleet prefix-cache directory (PR 20): a shared system prompt is
    prefilled ONCE fleet-wide — the first request cold-prefills it, the
    replica banks the prefix K/V in its host tier and advertises the hash
    through its health frames, and the router's directory sends every
    later shared-prefix request to the holder, which reuses the pages.
    SIGKILLing the holder must drop its directory entries and re-route
    the traffic to the survivors with zero drops."""
    sim, pub, router, mgr, procs, infos = spawn_fleet(
        model_cfg, args, n_replicas,
        serve_geom={
            **SERVE_GEOM,
            "kv_tier": True,
            "kv_host_slots": 16,
            # shared prefix (64) + unique suffix (8) needs a bucket past
            # the load-arm geometry's 64
            "prefill_buckets": [16, 64, 96],
        },
        prefix_directory=True,
    )
    try:
        # freeze the outer loop: prefix K/V is invalidated on every weight
        # swap (by design — cached pages must match the serving epoch), and
        # the sim trainer's 1 s epochs would purge entries faster than any
        # client could reuse them. Real fleets reuse a system prompt within
        # an outer epoch, which is minutes long; the swap-invalidation path
        # itself is pinned by the kv-tier unit tests.
        sim.stop()
        _warm(infos, model_cfg.vocab_size)
        rng = np.random.default_rng(7)
        shared = rng.integers(1, model_cfg.vocab_size, 64).tolist()

        def ask(seed):
            sr = np.random.default_rng(4000 + seed)
            prompt = shared + sr.integers(1, model_cfg.vocab_size, 8).tolist()
            out = _router_request(router.port, prompt, args.max_new)
            if not out.get("tokens"):
                raise SystemExit(f"prefix leg: request {seed} failed: {out}")

        def fleet_prefix_stats():
            per = {}
            for rid, info in infos.items():
                try:
                    with urllib.request.urlopen(
                        f"http://127.0.0.1:{info['serve_port']}/stats",
                        timeout=5,
                    ) as r:
                        s = json.loads(r.read())
                except (OSError, ValueError):
                    continue  # dead (the kill phase's business)
                tier = s.get("tier") or {}
                per[rid] = {
                    "hits": s["prefix"]["hits"] + s["prefix"]["host_hits"],
                    "stores": tier.get("prefix_stores", 0),
                }
            return per

        def dir_entries():
            return (router.stats()["prefix_directory"] or {}).get("entries", 0)

        # let the warm prompts' own prefix advertisements settle so the
        # seed request's entry is measured against a quiet baseline
        time.sleep(args.push_interval * 2 + 0.5)
        entries0 = dir_entries()
        base = fleet_prefix_stats()

        # -- seed: ONE cold prefill of the shared prompt, fleet-wide ------
        ask(0)
        _wait(
            lambda: dir_entries() > entries0,
            30,
            "the seeded prefix reaching the router directory",
        )
        seeded = fleet_prefix_stats()
        seed_stores = {
            rid: seeded[rid]["stores"] - base[rid]["stores"] for rid in seeded
        }
        holders = [rid for rid, n in seed_stores.items() if n > 0]

        # -- flood: every request must reuse the seeded prefill -----------
        flood_n = 12
        for i in range(1, flood_n + 1):
            ask(i)
        flooded = fleet_prefix_stats()
        flood_hits = sum(
            flooded[rid]["hits"] - seeded[rid]["hits"] for rid in flooded
        )
        flood_stores = sum(
            flooded[rid]["stores"] - seeded[rid]["stores"] for rid in flooded
        )
        rstats = router.stats()
        dir_hits = (rstats["prefix_directory"] or {}).get("hits", 0)

        # -- kill the holder: entries drop, traffic re-routes -------------
        victim = holders[0] if holders else sorted(infos)[0]
        entries_before_kill = dir_entries()
        procs[victim].send_signal(signal.SIGKILL)
        procs[victim].wait(timeout=30)
        _wait(
            lambda: router.stats()["replicas"][victim]["dead"],
            60,
            f"router noticing prefix holder {victim} died",
        )
        entries_after_kill = dir_entries()
        refill_n = 6
        ask(100)  # re-seeds the prefix on a survivor (zero drops: ask()
        # raises on any error). Wait for the survivor's advertisement so
        # the remaining traffic routes by directory, not by luck.
        _wait(
            lambda: dir_entries() > entries_after_kill,
            30,
            "a survivor advertising the re-seeded prefix",
        )
        for i in range(101, 100 + refill_n):
            ask(i)
        refilled = fleet_prefix_stats()
        refill_stores = sum(
            refilled[rid]["stores"] - flooded[rid]["stores"]
            for rid in refilled
        )
        return {
            "replicas": n_replicas,
            "shared_prefix_tokens": len(shared),
            "holder": victim,
            "seed_stores": sum(seed_stores.values()),
            "flood": {
                "requests": flood_n,
                "prefix_hits": flood_hits,
                "cold_stores": flood_stores,
                "directory_hits": dir_hits,
            },
            "kill": {
                "directory_entries_before": entries_before_kill,
                "directory_entries_after": entries_after_kill,
                "rerouted_requests": refill_n,
                "reroute_stores": refill_stores,
            },
        }
    finally:
        mgr.stop()
        router.stop()
        sim.stop()
        for p in procs.values():
            try:
                p.kill()
                p.wait(timeout=10)
            except OSError:
                pass


def run_arm(args, model_cfg, n_replicas, with_chaos) -> dict:
    from opendiloco_tpu import obs
    from opendiloco_tpu.obs import reqtrace

    obs.reset()  # counters cover this arm only
    sim, pub, router, mgr, procs, infos = spawn_fleet(
        model_cfg, args, n_replicas
    )
    for rid in infos:
        infos[rid]["_model"] = model_cfg.to_dict()
    chaos = None
    try:
        _warm(infos, model_cfg.vocab_size)
        monitor = StalenessMonitor(infos, args.max_stale_rounds).start()
        clients = ClientPool(
            router.port, args.clients_per_replica * n_replicas,
            model_cfg, args.max_new,
        ).start()
        t0 = time.perf_counter()
        if with_chaos:
            time.sleep(args.duration * 0.25)  # steady-state first
            chaos = run_chaos_leg(args, procs, infos, mgr, router, monitor)
        deadline = t0 + args.duration
        while time.perf_counter() < deadline:
            time.sleep(0.2)
        clients.stop()
        elapsed = time.perf_counter() - t0
        monitor.stop()

        rstats = router.stats()
        pstats = pub.stats()
        tr = obs.tracer()
        # tracer counter keys are (name, ((label, value), ...)) tuples;
        # fold label sets together per counter name
        counters: dict = {}
        if tr is not None:
            for (cname, _labels), v in tr.counters().items():
                counters[cname] = counters.get(cname, 0) + v
        arm = {
            "replicas": n_replicas,
            "replica_platforms": sorted(
                {str(i.get("platform")) for i in infos.values()}
            ),
            "clients": clients.n,
            "duration_s": round(elapsed, 3),
            "requests_per_s": round(clients.completed / elapsed, 3),
            "completed": clients.completed,
            "submitted": clients.submitted,
            "dropped": clients.submitted - clients.completed
            - len(clients.errors),
            "client_errors": clients.errors[:5],
            "latency_ms": {
                "p50": clients.percentile_ms(50),
                "p99": clients.percentile_ms(99),
            },
            "router": {
                "redispatches": rstats["redispatches"],
                "deaths": rstats["deaths"],
                "dispatched": {
                    rid: b["dispatched"]
                    for rid, b in rstats["replicas"].items()
                },
                "affinity_hits": sum(
                    v
                    for k, v in counters.items()
                    if k.startswith("fleet_router_affinity_hits")
                ),
            },
            "staleness": {
                "bound": args.max_stale_rounds,
                "max_seen": monitor.max_seen,
                "violations": monitor.violations[:5],
            },
            "delta_push": _delta_accounting(pstats),
            "trainer_epochs": sim.epoch,
        }
        rt = reqtrace.ring()
        if rt is not None:
            # the router runs in THIS process, so its ring holds one
            # trace per dispatched request — including requests whose
            # first replica was SIGKILLed (same id, redispatches >= 1)
            traces = rt.traces()
            arm["reqtrace"] = {
                "completed": len(traces),
                "evicted": rt.evicted,
                "statuses": rt.report()["statuses"],
                "redispatched_traces": sum(
                    1 for t in traces
                    if (t.get("attrs") or {}).get("redispatches", 0) > 0
                ),
                "dangling_inflight": rt.inflight_ids(),
            }
        if chaos is not None:
            chaos["dead_peer_watchdog_tripped"] = any(
                k.startswith("anomaly_dead_peer") for k in counters
            )
            arm["chaos"] = chaos
        return arm
    finally:
        mgr.stop()
        router.stop()
        sim.stop()
        for p in procs.values():
            try:
                p.kill()
                p.wait(timeout=10)
            except OSError:
                pass


def _delta_accounting(pstats) -> dict:
    per = {}
    worst = 0.0
    for rid, ch in pstats["replicas"].items():
        if ch["delta_frames"]:
            ratio = (
                ch["delta_bytes"]
                / ch["delta_frames"]
                / pstats["fp16_snapshot_bytes"]
            )
            worst = max(worst, ratio)
        else:
            ratio = None
        per[rid] = {
            "delta_bytes": ch["delta_bytes"],
            "delta_frames": ch["delta_frames"],
            "keyframe_bytes": ch["keyframe_bytes"],
            "keyframe_frames": ch["keyframe_frames"],
            "delta_ratio_per_epoch": None
            if ratio is None
            else round(ratio, 5),
        }
    return {
        "fp16_snapshot_bytes": pstats["fp16_snapshot_bytes"],
        "codec": pstats["codec"],
        "keyframe_codec": pstats["keyframe_codec"],
        "per_replica": per,
        "max_delta_ratio_per_epoch": round(worst, 5),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--selftest", action="store_true",
                    help="tiny CI run: 1/2 replicas, artifact under $TMPDIR")
    ap.add_argument("--replicas", default="1,4,8",
                    help="comma-separated fleet sizes to sweep")
    ap.add_argument("--duration", type=float, default=30.0,
                    help="seconds of sustained load per arm")
    ap.add_argument("--clients-per-replica", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--epoch-interval", type=float, default=1.0,
                    help="seconds per simulated outer epoch")
    ap.add_argument("--push-interval", type=float, default=0.25)
    ap.add_argument("--codec", default="blockwise4bit")
    ap.add_argument("--fragments", type=int, default=4)
    ap.add_argument("--keyframe-every", type=int, default=8)
    ap.add_argument("--max-stale-rounds", type=int, default=2)
    ap.add_argument("--down-s", type=float, default=2.0,
                    help="seconds the SIGKILLed replica stays down")
    args = ap.parse_args()

    out_path = _OUT
    if args.selftest:
        args.replicas = "1,2"
        args.duration = min(args.duration, 10.0)
        out_path = os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "SERVE_FLEET_BENCH.selftest.json"
        )
    sizes = [int(x) for x in args.replicas.split(",") if x.strip()]

    os.environ.setdefault("ODTP_OBS", "fleet-bench")  # chaos plane armed
    # big completed ring: post-kill traffic must not evict the SIGKILL
    # victims' traces before the gates inspect them
    os.environ.setdefault("ODTP_REQTRACE_CAP", "8192")

    from opendiloco_tpu.models.llama import LlamaConfig
    from opendiloco_tpu.utils.device import device_stamp
    model_cfg = LlamaConfig(
        vocab_size=256,
        hidden_size=args.hidden,
        intermediate_size=args.hidden * 2,
        num_hidden_layers=args.layers,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=256,
    )

    arms = {}
    for n in sizes:
        print(f"=== arm: {n} replica(s) ===")
        arms[str(n)] = run_arm(args, model_cfg, n, with_chaos=False)
        print(
            f"    {arms[str(n)]['requests_per_s']} req/s, "
            f"p99 {arms[str(n)]['latency_ms']['p99']} ms, "
            f"dropped {arms[str(n)]['dropped']}"
        )

    # chaos is its own arm so scaling numbers don't absorb the downtime
    chaos_arm = None
    chaos_n = max(max(sizes), 2)
    print(f"=== chaos arm: {chaos_n} replicas + SIGKILL ===")
    chaos_arm = run_arm(args, model_cfg, chaos_n, with_chaos=True)
    print(
        f"    {chaos_arm['requests_per_s']} req/s through the kill, "
        f"dropped {chaos_arm['dropped']}, "
        f"downtime {chaos_arm['chaos']['downtime_s']}s"
    )

    prefix_n = 2 if args.selftest else 3
    print(f"=== prefix-directory leg: {prefix_n} replicas ===")
    prefix_arm = run_prefix_leg(args, model_cfg, prefix_n)
    print(
        f"    seed_stores={prefix_arm['seed_stores']} "
        f"flood_hits={prefix_arm['flood']['prefix_hits']}/"
        f"{prefix_arm['flood']['requests']} "
        f"cold_stores={prefix_arm['flood']['cold_stores']} "
        f"reroute_stores={prefix_arm['kill']['reroute_stores']}"
    )

    base = arms[str(sizes[0])]["requests_per_s"] / sizes[0]
    scaling = {
        str(n): round(arms[str(n)]["requests_per_s"] / base, 3) if base else None
        for n in sizes
    }
    doc = {
        "schema": 1,
        "selftest": bool(args.selftest),
        "host": {"node": os.uname().nodename, "cpus": os.cpu_count()},
        # this (router + simulated trainer) process; each arm records the
        # platform its replica subprocesses reported on their ready lines
        **device_stamp(),
        "updated": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "model": {
            "hidden": model_cfg.hidden_size,
            "layers": model_cfg.num_hidden_layers,
            "vocab": model_cfg.vocab_size,
            "params": int(model_cfg.num_params()),
        },
        "fleet": {
            "codec": args.codec,
            "fragments": args.fragments,
            "keyframe_every": args.keyframe_every,
            "push_interval_s": args.push_interval,
            "epoch_interval_s": args.epoch_interval,
            "max_stale_rounds": args.max_stale_rounds,
        },
        "arms": arms,
        "chaos_arm": chaos_arm,
        "prefix_directory_arm": prefix_arm,
        "scaling_speedup": scaling,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print(f"wrote {out_path}")
    print("scaling:", json.dumps(scaling))

    # -- gates ---------------------------------------------------------------
    # every arm (clean + chaos): zero drops/errors, staleness within bound
    # (the chaos arm's monitor is paused across the kill/rejoin window — the
    # stale flag flipping there is designed behavior, not a violation), and
    # delta pushes <= 1/4 of the fp16 snapshot equivalent per epoch.
    for n, arm in {**arms, "chaos": chaos_arm}.items():
        if arm["dropped"] != 0:
            raise SystemExit(
                f"arm {n}: {arm['dropped']} requests vanished — acceptance is 0"
            )
        if arm["client_errors"]:
            raise SystemExit(f"arm {n}: client errors {arm['client_errors']}")
        if arm["staleness"]["violations"]:
            raise SystemExit(
                f"arm {n}: staleness bound exceeded: "
                f"{arm['staleness']['violations']}"
            )
        ratio = arm["delta_push"]["max_delta_ratio_per_epoch"]
        if ratio > 0.25:
            raise SystemExit(
                f"arm {n}: delta push {ratio} of an fp16 snapshot per epoch "
                "— acceptance is <= 0.25"
            )
        rq = arm.get("reqtrace")
        if rq:
            if rq["dangling_inflight"]:
                raise SystemExit(
                    f"arm {n}: request traces never terminated: "
                    f"{rq['dangling_inflight'][:5]} — every dispatch "
                    "(served, shed, or interrupted by SIGKILL) must finish "
                    "its trace"
                )
            if arm["router"]["redispatches"] > 0 and not rq[
                    "redispatched_traces"]:
                raise SystemExit(
                    f"arm {n}: router redispatched "
                    f"{arm['router']['redispatches']} request(s) but no "
                    "trace records a redispatch — a killed request's "
                    "history was lost across mark-dead -> re-dispatch"
                )
    chaos = chaos_arm["chaos"]
    if not chaos["rejoined"]:
        raise SystemExit("chaos arm: SIGKILLed replica never rejoined")
    if not chaos["dead_peer_watchdog_tripped"]:
        raise SystemExit("chaos arm: dead-peer watchdog never named the victim")
    # prefix-directory leg: the shared prompt was prefilled exactly once
    # fleet-wide, every flood request reused it via the directory, and the
    # holder's death dropped its entries and re-routed traffic (ask()
    # raised on any dropped/errored request, so reaching here = 0 drops)
    pfx = prefix_arm
    if pfx["seed_stores"] != 1:
        raise SystemExit(
            f"prefix leg: shared prompt cold-prefilled {pfx['seed_stores']} "
            "time(s) at seed — acceptance is exactly once fleet-wide"
        )
    if pfx["flood"]["cold_stores"] != 0:
        raise SystemExit(
            f"prefix leg: {pfx['flood']['cold_stores']} flood request(s) "
            "re-prefilled the shared prompt — every one must reuse the "
            "seeded prefill"
        )
    if pfx["flood"]["prefix_hits"] < pfx["flood"]["requests"]:
        raise SystemExit(
            f"prefix leg: only {pfx['flood']['prefix_hits']} of "
            f"{pfx['flood']['requests']} flood requests hit the cached "
            "prefix"
        )
    if pfx["flood"]["directory_hits"] < pfx["flood"]["requests"]:
        raise SystemExit(
            f"prefix leg: router directory routed only "
            f"{pfx['flood']['directory_hits']} of "
            f"{pfx['flood']['requests']} flood requests to the holder"
        )
    if pfx["kill"]["directory_entries_after"] >= pfx["kill"][
            "directory_entries_before"]:
        raise SystemExit(
            "prefix leg: the SIGKILLed holder's directory entries were "
            "not invalidated"
        )
    if pfx["kill"]["reroute_stores"] != 1:
        raise SystemExit(
            f"prefix leg: post-kill traffic re-prefilled the shared "
            f"prompt {pfx['kill']['reroute_stores']} time(s) on the "
            "survivors — acceptance is exactly once"
        )
    if not args.selftest and len(sizes) > 1:
        # ~linear scaling, honestly bounded by the host: N replicas cannot
        # beat the core count on a CPU rig, so the expectation is
        # min(N, cpus) and the artifact records both.
        top = sizes[-1]
        expect = min(top, os.cpu_count() or 1)
        if scaling[str(top)] < 0.5 * expect:
            raise SystemExit(
                f"requests/s at {top} replicas is {scaling[str(top)]}x the "
                f"1-replica arm — acceptance is >= {0.5 * expect}x "
                f"(~linear up to {os.cpu_count()} cores)"
            )
    print("all gates passed")


if __name__ == "__main__":
    main()
