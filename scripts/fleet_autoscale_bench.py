#!/usr/bin/env python
"""Fleet autoscaling + admission-control benchmark: SLO under swinging load.

One fleet (subprocess replicas, built through ``build_fleet`` with the
autoscaler armed) rides a load timeline with a 4x client swing:

- **baseline** — light closed-loop in-SLO load on the minimum fleet;
- **spike** — a short 4x burst: admission control carries it (in-SLO
  traffic served, hopeless-deadline traffic shed 503 at the edge) while
  the autoscaler reacts by promoting a pre-keyframed warm spare;
- **step** — the 4x load stays: the loop scales to the SLO and holds;
  mid-step one active replica is SIGKILLed — the autoscaler retires the
  corpse and restores capacity (spare adoption) with NO operator action;
- **step-down** — load returns to baseline: after its configured
  reluctance the loop shrinks the fleet again.

Two traffic classes run throughout:

- *in-SLO*: generous ``deadline_ms``, priority 0. Acceptance is total:
  every request completes with tokens, p99 within the declared SLO.
- *out-of-SLO*: a deadline that is provably unmeetable (0 ms, or below
  the router's observed latency floor). Acceptance is structural: every
  one is answered HTTP 503 + Retry-After at the edge, immediately —
  never queued, never a client-side timeout.

Banks AUTOSCALE_BENCH.json at the repo root (``ODTP_AUTOSCALE_BENCH_OUT``
overrides)::

    python scripts/fleet_autoscale_bench.py             # full run
    python scripts/fleet_autoscale_bench.py --selftest  # CI run, $TMPDIR

Gates (SystemExit on violation):
- zero dropped / errored in-SLO requests across the whole timeline,
  including the SIGKILL;
- in-SLO client p99 <= the declared SLO;
- every out-of-SLO request shed 503-with-Retry-After at the edge; zero
  queue timeouts;
- the decision log shows scale_up AND scale_down AND replace AND
  boot_spare, with at least one warm-spare adoption (spare_promotion);
- the fleet actually swung: max active replicas > min active replicas;
- the dead-peer watchdog named the SIGKILL victim and
  fleet_autoscale_decisions landed in the obs counters.
"""
import argparse
import json
import os
import signal
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_OUT = os.environ.get("ODTP_AUTOSCALE_BENCH_OUT") or os.path.join(
    REPO, "AUTOSCALE_BENCH.json"
)


def _wait(pred, t, what):
    deadline = time.monotonic() + t
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.1)
    raise SystemExit(f"timed out waiting for {what}")


class InSloClients:
    """Closed-loop JSONL clients with a generous deadline: the traffic
    the SLO is declared for. Every request must come back with tokens —
    anything else is a drop and a gate failure."""

    def __init__(self, port, model_cfg, max_new, deadline_ms):
        self.port = port
        self.vocab = model_cfg.vocab_size
        self.max_new = max_new
        self.deadline_ms = deadline_ms
        self.lock = threading.Lock()
        self.submitted = 0
        self.completed = 0
        self.latencies = []
        self.errors = []
        self._stops = []  # one event per client: ramps up AND down
        self._threads = []

    def _loop(self, cid, stop):
        r = np.random.default_rng(1000 + cid)
        conn = None
        while not stop.is_set():
            try:
                if conn is None:
                    conn = socket.create_connection(
                        ("127.0.0.1", self.port), timeout=120
                    )
                payload = {
                    "prompt": r.integers(
                        1, self.vocab, int(r.integers(3, 16))
                    ).tolist(),
                    "max_new_tokens": int(r.integers(2, self.max_new + 1)),
                    "priority": 0,
                    "deadline_ms": self.deadline_ms,
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

    def scale_to(self, n):
        """Ramp the live client count to n (the load shape knob)."""
        while len(self._stops) < n:
            stop = threading.Event()
            t = threading.Thread(
                target=self._loop, args=(len(self._stops), stop), daemon=True
            )
            self._stops.append(stop)
            self._threads.append(t)
            t.start()
        while len(self._stops) > n:
            self._stops.pop().set()

    def stop(self):
        self.scale_to(0)
        # join so every in-flight request finishes its accounting — the
        # zero-drop gate compares submitted vs completed exactly
        for t in self._threads:
            t.join(timeout=60)

    def percentile_ms(self, q):
        with self.lock:
            lat = list(self.latencies)
        if not lat:
            return None
        return round(float(np.percentile(lat, q)) * 1e3, 3)


class OutOfSloClients:
    """Open-loop doomed traffic over HTTP: deadlines of 0 ms (spent
    before arrival) and a few ms (below the router's latency floor).
    The contract under test: an immediate structured 503 + Retry-After
    at the edge, never a queue slot, never a client timeout."""

    def __init__(self, port, interval_s=0.25):
        self.port = port
        self.interval_s = interval_s
        self.lock = threading.Lock()
        self.submitted = 0
        self.shed_503 = 0
        self.retry_after_ok = 0
        self.served_200 = 0  # a doomed request that got tokens: violation
        self.timeouts = 0
        self.other = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        r = np.random.default_rng(7)
        while not self._stop.wait(self.interval_s):
            deadline_ms = 0 if r.random() < 0.5 else 1
            body = json.dumps({
                "prompt": r.integers(1, 200, 6).tolist(),
                "max_new_tokens": 4,
                "priority": 2,
                "deadline_ms": deadline_ms,
            }).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{self.port}/generate", data=body,
                method="POST",
            )
            with self.lock:
                self.submitted += 1
            try:
                with urllib.request.urlopen(req, timeout=10) as resp:
                    resp.read()
                with self.lock:
                    self.served_200 += 1
            except urllib.error.HTTPError as e:
                body = e.read()
                with self.lock:
                    if e.code == 503:
                        self.shed_503 += 1
                        ra = e.headers.get("Retry-After")
                        try:
                            if ra is not None and float(ra) > 0:
                                self.retry_after_ok += 1
                        except ValueError:
                            pass
                    else:
                        self.other.append(f"HTTP {e.code}: {body[:120]}")
            except (OSError, ValueError) as e:
                with self.lock:
                    if "timed out" in str(e).lower():
                        self.timeouts += 1
                    else:
                        self.other.append(str(e)[:120])

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=30)


class FleetSampler:
    """Samples the router's live replica count through the run — the
    swing evidence (and a nice plot) for the artifact."""

    def __init__(self, router):
        self.router = router
        self.samples = []
        self._t0 = time.monotonic()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _loop(self):
        while not self._stop.wait(0.25):
            st = self.router.stats()["replicas"]
            live = sum(1 for b in st.values() if not b["dead"])
            self.samples.append(
                (round(time.monotonic() - self._t0, 2), live)
            )

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)

    def mark(self, label):
        self.samples.append(
            (round(time.monotonic() - self._t0, 2), f"phase:{label}")
        )


class SpareWarmer:
    """Compiles each warm spare's decode path BEFORE it can be promoted:
    spares answer /generate on their own port while unregistered, so the
    jit cost is paid off the serving path and adoption really is
    instant."""

    def __init__(self, manager):
        self.manager = manager
        self.warmed = set()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def _warm_one(self, rid):
        addr = self.manager.addr(rid)
        if addr is None:
            return
        for plen in (4, 12):
            body = json.dumps({
                "prompt": list(range(1, plen + 1)), "max_new_tokens": 2,
            }).encode()
            req = urllib.request.Request(
                f"http://{addr[0]}:{addr[1]}/generate", data=body
            )
            with urllib.request.urlopen(req, timeout=300) as r:
                r.read()
        self.warmed.add(rid)

    def _loop(self):
        while not self._stop.wait(0.2):
            for rid in self.manager.spares():
                if rid in self.warmed:
                    continue
                try:
                    self._warm_one(rid)
                except (OSError, ValueError):
                    pass  # not ready yet; retry next tick

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5)


def decisions_by_action(plane):
    out = {}
    for d in list(plane.autoscaler.decisions):
        out.setdefault(d["action"], []).append(d)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--selftest", action="store_true",
                    help="tiny CI run: shorter phases, artifact under $TMPDIR")
    ap.add_argument("--base-clients", type=int, default=2,
                    help="baseline in-SLO client count (peak is 4x this)")
    ap.add_argument("--slo-p99-ms", type=float, default=2000.0)
    ap.add_argument("--slo-queue-depth", type=int, default=2)
    ap.add_argument("--deadline-ms", type=float, default=30000.0,
                    help="in-SLO client deadline (well above the SLO)")
    ap.add_argument("--max-replicas", type=int, default=4)
    ap.add_argument("--warm-spares", type=int, default=1)
    ap.add_argument("--cooldown", type=float, default=1.0)
    ap.add_argument("--spike-s", type=float, default=6.0)
    ap.add_argument("--step-s", type=float, default=20.0)
    ap.add_argument("--down-wait-s", type=float, default=60.0)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--max-new", type=int, default=4)
    args = ap.parse_args()

    out_path = _OUT
    if args.selftest:
        args.spike_s = min(args.spike_s, 4.0)
        args.step_s = min(args.step_s, 12.0)
        args.max_replicas = min(args.max_replicas, 3)
        out_path = os.path.join(
            os.environ.get("TMPDIR", "/tmp"), "AUTOSCALE_BENCH.selftest.json"
        )

    os.environ.setdefault("ODTP_OBS", "autoscale-bench")  # watchdogs armed
    # keep breach-exemplar traces resolvable: later traffic must not
    # evict them from the completed ring before the gates look them up
    os.environ.setdefault("ODTP_REQTRACE_CAP", "16384")
    import jax

    from opendiloco_tpu.utils.compile_cache import enable_compile_cache
    from opendiloco_tpu.utils.device import device_stamp

    # replica subprocesses resolve the same directory (fleet.replica.main
    # calls this too), so they share one jit cache: a cold boot is a
    # process start + cache hit, not a recompile (closer to an image pull)
    enable_compile_cache()

    from opendiloco_tpu import fleet, obs
    from opendiloco_tpu.config import FleetConfig
    from opendiloco_tpu.models.llama import LlamaConfig, init_params
    from opendiloco_tpu.obs import reqtrace

    obs.reset()
    model_cfg = LlamaConfig(
        vocab_size=256,
        hidden_size=args.hidden,
        intermediate_size=args.hidden * 2,
        num_hidden_layers=args.layers,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=256,
    )
    fleet_cfg = FleetConfig(
        enabled=True,
        replicas=1,
        inprocess=False,
        push_interval_s=0.1,
        max_batch=4,
        max_context=128,
        prefill_buckets=[16, 64],
        autoscale=True,
        slo_p99_ms=args.slo_p99_ms,
        slo_queue_depth=args.slo_queue_depth,
        min_replicas=1,
        max_replicas=args.max_replicas,
        warm_spares=args.warm_spares,
        scale_cooldown_s=args.cooldown,
        scale_eval_interval_s=0.25,
        scale_up_evals=2,
        scale_down_evals=8,
    )
    params = init_params(jax.random.PRNGKey(0), model_cfg)

    print("=== booting fleet (1 active + warm spares) ===")
    plane = fleet.build_fleet(fleet_cfg, model_cfg, params)
    warmer = SpareWarmer(plane.manager).start()
    sampler = FleetSampler(plane.router).start()
    phases = {}
    try:
        _wait(
            lambda: plane.autoscaler.ready_spares()
            and set(plane.autoscaler.ready_spares()) <= warmer.warmed,
            300,
            "warm spare keyframed + compiled",
        )
        # warm the initial active replica off the clock too
        addr = plane.manager.addr("r0")
        for plen in (4, 12):
            body = json.dumps({
                "prompt": list(range(1, plen + 1)), "max_new_tokens": 2,
            }).encode()
            req = urllib.request.Request(
                f"http://{addr[0]}:{addr[1]}/generate", data=body
            )
            with urllib.request.urlopen(req, timeout=300) as r:
                r.read()

        clients = InSloClients(
            plane.port, model_cfg, args.max_new, args.deadline_ms
        )
        doomed = OutOfSloClients(plane.port).start()

        print("=== phase: baseline ===")
        sampler.mark("baseline")
        t0 = time.perf_counter()
        clients.scale_to(args.base_clients)
        time.sleep(4.0)
        phases["baseline"] = {"active": len(plane.router.live_replicas())}

        print("=== phase: spike (4x clients) ===")
        sampler.mark("spike")
        clients.scale_to(4 * args.base_clients)
        _wait(
            lambda: decisions_by_action(plane).get("scale_up"),
            max(30.0, args.spike_s * 5),
            "a scale_up decision during the spike",
        )
        time.sleep(args.spike_s)
        first_up = decisions_by_action(plane)["scale_up"][0]
        phases["spike"] = {
            "first_scale_up": first_up,
            "active": len(plane.router.live_replicas()),
        }
        print(f"    scale_up via {first_up['mode']}")

        print("=== phase: step hold + SIGKILL chaos ===")
        sampler.mark("step")
        victims = [
            rid for rid in plane.router.live_replicas()
            if hasattr(plane.replicas.get(rid), "send_signal")
        ]
        victim = sorted(victims)[0]
        pre_live = len(plane.router.live_replicas())
        pre_replace = len(decisions_by_action(plane).get("replace", []))
        plane.replicas[victim].send_signal(signal.SIGKILL)
        plane.replicas[victim].wait(timeout=30)
        t_kill = time.perf_counter()
        _wait(
            lambda: len(decisions_by_action(plane).get("replace", []))
            > pre_replace,
            60,
            f"the autoscaler replacing SIGKILLed {victim}",
        )
        _wait(
            lambda: len(plane.router.live_replicas()) >= pre_live,
            120,
            "capacity restored after the kill",
        )
        t_restore = time.perf_counter() - t_kill
        replace = decisions_by_action(plane)["replace"][-1]
        phases["chaos"] = {
            "victim": victim,
            "replace_decision": replace,
            "restore_s": round(t_restore, 3),
            "active": len(plane.router.live_replicas()),
        }
        print(
            f"    {victim} replaced via {replace.get('mode')} "
            f"in {phases['chaos']['restore_s']}s"
        )
        time.sleep(args.step_s)
        phases["step"] = {"active": len(plane.router.live_replicas())}

        print("=== phase: step-down (back to baseline clients) ===")
        sampler.mark("step-down")
        clients.scale_to(args.base_clients)
        _wait(
            lambda: decisions_by_action(plane).get("scale_down"),
            args.down_wait_s,
            "a scale_down decision after load dropped",
        )
        time.sleep(2.0)
        phases["step_down"] = {"active": len(plane.router.live_replicas())}

        elapsed = time.perf_counter() - t0
        clients.stop()
        doomed.stop()
    finally:
        warmer.stop()
        sampler.stop()
        plane.stop()

    # -- artifact -------------------------------------------------------------
    tr = obs.tracer()
    counters: dict = {}
    if tr is not None:
        for (cname, _labels), v in tr.counters().items():
            counters[cname] = counters.get(cname, 0) + v
    by_action = {
        k: len(v)
        for k, v in decisions_by_action(plane).items()
    }
    decisions = list(plane.autoscaler.decisions)
    # every scale_up must name the requests that justified it, and the
    # ids must resolve to actual recorded traces (the router mints ids
    # in THIS process and replicas adopt them verbatim, so replica-
    # reported exemplars resolve in the local ring)
    rt = reqtrace.ring()
    scale_up_exemplars = [
        {
            "exemplars": d.get("exemplars") or [],
            "resolved": sum(
                1 for t in d.get("exemplars") or []
                if rt is not None and rt.has(t)
            ),
        }
        for d in decisions if d["action"] == "scale_up"
    ]
    lives = [s[1] for s in sampler.samples if isinstance(s[1], int)]
    in_slo = {
        "submitted": clients.submitted,
        "completed": clients.completed,
        "dropped": clients.submitted - clients.completed
        - len(clients.errors),
        "errors": clients.errors[:5],
        "latency_ms": {
            "p50": clients.percentile_ms(50),
            "p99": clients.percentile_ms(99),
        },
    }
    out_slo = {
        "submitted": doomed.submitted,
        "shed_503": doomed.shed_503,
        "retry_after_ok": doomed.retry_after_ok,
        "served_200": doomed.served_200,
        "queue_timeouts": doomed.timeouts,
        "other": doomed.other[:5],
    }
    doc = {
        "schema": 1,
        "selftest": bool(args.selftest),
        "host": {"node": os.uname().nodename, "cpus": os.cpu_count()},
        **device_stamp(),
        "updated": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "slo": {
            "p99_ms": args.slo_p99_ms,
            "queue_depth": args.slo_queue_depth,
            "min_replicas": 1,
            "max_replicas": args.max_replicas,
            "warm_spares": args.warm_spares,
            "cooldown_s": args.cooldown,
        },
        "load": {
            "base_clients": args.base_clients,
            "peak_clients": 4 * args.base_clients,
            "swing": "4x",
            "duration_s": round(elapsed, 3),
        },
        "phases": phases,
        "traffic": {"in_slo": in_slo, "out_of_slo": out_slo},
        "fleet_swing": {
            "min_active": min(lives) if lives else None,
            "max_active": max(lives) if lives else None,
            "samples": sampler.samples,
        },
        "decisions_by_action": by_action,
        "decision_log": decisions,
        "scale_up_exemplars": scale_up_exemplars,
        "counters": {
            k: v for k, v in sorted(counters.items())
            if k.startswith(("fleet_", "anomaly_"))
        },
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True, default=str)
    print(f"wrote {out_path}")
    print("decisions:", json.dumps(by_action))
    print(
        f"in-SLO: {in_slo['completed']}/{in_slo['submitted']} "
        f"p99 {in_slo['latency_ms']['p99']} ms; "
        f"out-of-SLO: {out_slo['shed_503']}/{out_slo['submitted']} shed 503"
    )

    # -- gates ----------------------------------------------------------------
    if in_slo["dropped"] != 0 or in_slo["errors"]:
        raise SystemExit(
            f"in-SLO traffic lost requests: dropped={in_slo['dropped']} "
            f"errors={in_slo['errors']} — acceptance is zero"
        )
    p99 = in_slo["latency_ms"]["p99"]
    if p99 is None or p99 > args.slo_p99_ms:
        raise SystemExit(
            f"in-SLO p99 {p99} ms violates the {args.slo_p99_ms} ms SLO"
        )
    if out_slo["queue_timeouts"] or out_slo["served_200"] or out_slo["other"]:
        raise SystemExit(
            "out-of-SLO traffic must be shed at the edge, not queued: "
            f"{out_slo}"
        )
    if out_slo["shed_503"] == 0 or out_slo["shed_503"] != out_slo[
        "retry_after_ok"
    ]:
        raise SystemExit(
            f"every out-of-SLO request needs a 503 with Retry-After: {out_slo}"
        )
    for action in ("scale_up", "scale_down", "replace", "boot_spare"):
        if not by_action.get(action):
            raise SystemExit(
                f"decision log has no '{action}' — got {by_action}"
            )
    promoted = [
        d for d in decisions
        if d["action"] in ("scale_up", "replace")
        and d.get("mode") == "spare_promotion"
    ]
    if not promoted:
        raise SystemExit(
            "no warm-spare adoption (spare_promotion) in the decision log"
        )
    for i, ex in enumerate(scale_up_exemplars):
        if not ex["exemplars"]:
            raise SystemExit(
                f"scale_up decision #{i} carries no breach exemplars — "
                "an alarm that names no offending request is unactionable"
            )
        if not ex["resolved"]:
            raise SystemExit(
                f"scale_up decision #{i} exemplars {ex['exemplars']} "
                "resolve to no recorded trace"
            )
    if not lives or max(lives) <= min(lives):
        raise SystemExit(
            f"fleet never swung: live-replica samples {lives[:20]}"
        )
    if not any(k.startswith("anomaly_dead_peer") for k in counters):
        raise SystemExit("dead-peer watchdog never named the SIGKILL victim")
    if not counters.get("fleet_autoscale_decisions"):
        raise SystemExit("fleet_autoscale_decisions counter never moved")
    print("all gates passed")


if __name__ == "__main__":
    main()
