#!/usr/bin/env python
"""Serving-plane benchmark: synthetic load against the continuous-batching
server while DiLoCo training runs in the SAME process.

The north star serves traffic off the live master weights; this bench
measures that leg end to end: a tiny Llama trains through
DiLoCoOptimizer (loopback backend, short inner phases so outer epochs
land quickly) while client threads drive the serve plane with random
prompts. Banks SERVE_BENCH.json at the repo root:

    python scripts/serve_bench.py                # full run, banks artifact
    python scripts/serve_bench.py --selftest     # tiny CI run, /tmp artifact

Recorded: sustained requests/s, p50/p99/mean latency, TTFT, tokens/s,
batch occupancy, the snapshot-staleness distribution, weight-swap count,
and the drop count (must be 0 — no request is dropped across a swap).
The acceptance line (full runs only): at least one hot-swap observed and
zero dropped/failed requests.

``--decode`` instead runs plain decode over static weights (no trainer in
the process), writing DECODE_BENCH.json with tokens/s and the per-stage
breakdown (prefill/decode/swap) sourced from obs spans. One gate rides it:
the arm must clear 2x the banked SERVE_BENCH.json tokens/s (full runs only).

    python scripts/serve_bench.py --decode            # writes DECODE_BENCH.json
    python scripts/serve_bench.py --decode --selftest # tiny CI run

``--longctx`` runs the KV-tiering A/B (PR 20): the same open-loop long-
context workload through two engines at EQUAL per-request context — an
all-resident arm with one device slot per request, and a tiered arm with
4x fewer slots plus the host cold tier (``ODTP_KV_TIER`` machinery)
paging paused sequences D2H/H2D between decode steps. Banks a
``longctx`` section into DECODE_BENCH.json (read-modify-write; the
decode arm is preserved). Gates: the tiered arm serves an aggregate
context >= 4x its device ring capacity, drops nothing, streams token-
bit-identical outputs (codec none), and its TTFT p50 stays within 1.5x
of the all-resident arm.

    python scripts/serve_bench.py --longctx            # banks the longctx section
    python scripts/serve_bench.py --longctx --selftest # tiny CI run
"""
import argparse
import json
import os
import socket
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_OUT = os.environ.get("ODTP_SERVE_BENCH_OUT") or os.path.join(
    REPO, "SERVE_BENCH.json"
)
_DECODE_OUT = os.environ.get("ODTP_DECODE_BENCH_OUT") or os.path.join(
    REPO, "DECODE_BENCH.json"
)


def build_world(args):
    """Tiny model + trainer + single-peer loopback DiLoCo + serving plane,
    all in this process (the train.py wiring, minus the data pipeline)."""
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.config import DilocoConfig, ServeConfig
    from opendiloco_tpu.diloco import DiLoCoOptimizer, LoopbackWorld
    from opendiloco_tpu.models.llama import LlamaConfig, init_params
    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.serve import build_serving
    from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

    model_cfg = LlamaConfig(
        vocab_size=512,
        hidden_size=args.hidden,
        intermediate_size=args.hidden * 2,
        num_hidden_layers=args.layers,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=512,
    )
    params = init_params(jax.random.PRNGKey(0), model_cfg)
    tc = TrainerConfig(
        lr=1e-3, warmup_steps=2, total_steps=100_000,
        precision="fp32", remat=False,
    )
    plan = build_mesh("NO_SHARD", devices=[jax.devices()[0]])
    trainer = InnerTrainer(model_cfg, tc, plan)
    state = trainer.init_state(jax.random.key(1), params)
    dcfg = DilocoConfig(local_steps=args.local_steps, backend="loopback")
    backend = LoopbackWorld(1).make_backends()[0]
    opt = DiLoCoOptimizer(trainer, backend, dcfg, state, batch_size=8)
    scfg = ServeConfig(
        enabled=True,
        max_batch=args.slots,
        max_context=args.max_context,
        prefill_buckets=[16, 64],
        swap_every_steps=args.swap_every,
        max_stale_rounds=0,
    )
    plane = build_serving(
        scfg, model_cfg, state["params"], opt, compute_dtype=jnp.float32
    )
    return model_cfg, trainer, state, opt, plane, scfg


def run_bench(args) -> dict:
    model_cfg, trainer, state, opt, plane, scfg = build_world(args)
    rng = np.random.default_rng(0)

    # -- training thread: inner steps -> outer epochs -> hot-swap source --
    stop_train = threading.Event()
    train_steps = [0]

    def train_loop():
        s = state
        while not stop_train.is_set():
            ids = rng.integers(0, model_cfg.vocab_size, (8, 32)).astype(np.int32)
            batch = trainer.shard_batch(ids, ids.copy(), 1)
            s, _ = opt.step(s, batch)
            train_steps[0] += 1

    # -- client threads: closed-loop synthetic load -----------------------
    stop_clients = threading.Event()
    client_rng = np.random.default_rng(7)
    lock = threading.Lock()
    submitted = [0]
    errors = []

    def client_loop(cid):
        r = np.random.default_rng(1000 + cid)
        while not stop_clients.is_set():
            n = int(r.integers(3, 15))
            prompt = r.integers(1, model_cfg.vocab_size, n).tolist()
            req = plane.batcher.submit(
                prompt, max_new_tokens=int(r.integers(4, args.max_new + 1))
            )
            with lock:
                submitted[0] += 1
            if not req.wait(120):
                errors.append("client request hung")
                return
            if req.error is not None:
                errors.append(req.error)

    # warm the compile caches before timing (prefill buckets + decode)
    warm = plane.batcher.submit([1, 2, 3], max_new_tokens=2)
    warm.wait(300)
    for b in scfg.prefill_buckets:
        w = plane.batcher.submit(list(range(1, b + 1))[: b], max_new_tokens=2)
        w.wait(300)

    trainer_thread = threading.Thread(target=train_loop, daemon=True)
    clients = [
        threading.Thread(target=client_loop, args=(i,), daemon=True)
        for i in range(args.clients)
    ]
    base_completed = plane.batcher.completed
    base_tokens = plane.batcher.total_new_tokens
    t0 = time.perf_counter()
    trainer_thread.start()
    for c in clients:
        c.start()
    time.sleep(args.duration)
    stop_clients.set()
    for c in clients:
        c.join(timeout=180)
    plane.batcher.drain(timeout=180)
    elapsed = time.perf_counter() - t0
    stop_train.set()
    trainer_thread.join(timeout=180)

    # -- one front-end round trip over the real socket --------------------
    http_ok = False
    try:
        conn = socket.create_connection(("127.0.0.1", plane.port), timeout=30)
        conn.sendall(
            (json.dumps({"prompt": [5, 6, 7], "max_new_tokens": 2}) + "\n").encode()
        )
        buf = b""
        while b"\n" not in buf:
            chunk = conn.recv(4096)
            if not chunk:
                break
            buf += chunk
        http_ok = b"tokens" in buf
        conn.close()
    except OSError as e:
        errors.append(f"frontend: {e}")

    stats = plane.batcher.stats()
    plane.stop()

    completed = stats["completed"] - base_completed
    new_tokens = stats["new_tokens"] - base_tokens
    return {
        "model": {
            "hidden": model_cfg.hidden_size,
            "layers": model_cfg.num_hidden_layers,
            "vocab": model_cfg.vocab_size,
            "params": int(model_cfg.num_params()),
        },
        "load": {
            "clients": args.clients,
            "duration_s": round(elapsed, 3),
            "slots": args.slots,
            "max_new_tokens": args.max_new,
            "local_steps": args.local_steps,
        },
        "throughput": {
            "requests_per_s": round(completed / elapsed, 3),
            "tokens_per_s": round(new_tokens / elapsed, 3),
            "completed": completed,
            "submitted": submitted[0],
            "decode_steps": stats["decode_steps"],
        },
        "latency_ms": stats["latency_ms"],
        "ttft_ms": stats["ttft_ms"],
        "staleness_hist": stats["staleness_hist"],
        "swaps": {
            "count": stats["weight_swaps"],
            "final_weights_epoch": stats["weights_epoch"],
            "trainer_epochs": opt.epoch,
        },
        "training": {"inner_steps": train_steps[0]},
        "dropped": stats["failed"],
        "rejected": stats["rejected"],
        "frontend_roundtrip_ok": http_ok,
        "client_errors": errors[:5],
        "loop_error": stats["loop_error"],
    }


# -- plain decode over static weights (--decode) -----------------------------


def _decode_model(args):
    import jax

    from opendiloco_tpu.models.llama import LlamaConfig, init_params

    model_cfg = LlamaConfig(
        vocab_size=512,
        hidden_size=args.hidden,
        intermediate_size=args.hidden * 2,
        num_hidden_layers=args.layers,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=512,
    )
    return model_cfg, init_params(jax.random.PRNGKey(0), model_cfg)


def _span_totals():
    from opendiloco_tpu import obs

    tr = obs.tracer()
    if tr is None:
        return None
    totals = {}
    for ev in list(tr.events):
        if ev.get("ph") == "X" and str(ev.get("name", "")).startswith("serve_"):
            totals[ev["name"]] = totals.get(ev["name"], 0.0) + ev["dur"] / 1e6
    return {k: round(v, 6) for k, v in sorted(totals.items())}


def run_decode_arm(args, name, model_cfg, params) -> dict:
    import jax.numpy as jnp

    from opendiloco_tpu import obs
    from opendiloco_tpu.config import ServeConfig
    from opendiloco_tpu.serve import build_serving

    scfg = ServeConfig(
        enabled=True,
        max_batch=args.slots,
        max_context=args.max_context,
        prefill_buckets=[16, 64],
    )
    plane = build_serving(
        scfg, model_cfg, params, None,
        compute_dtype=jnp.float32, start_server=False,
    )

    stop_clients = threading.Event()
    errors = []
    submitted = [0]
    lock = threading.Lock()

    def client_loop(cid):
        r = np.random.default_rng(1000 + cid)
        while not stop_clients.is_set():
            prompt = r.integers(1, model_cfg.vocab_size, int(r.integers(3, 15))).tolist()
            req = plane.batcher.submit(
                prompt, max_new_tokens=int(r.integers(4, args.max_new + 1))
            )
            with lock:
                submitted[0] += 1
            if not req.wait(120):
                errors.append("client request hung")
                return
            if req.error is not None:
                errors.append(req.error)

    # warm every compile (prefill buckets, decode) before timing
    for b in [3] + list(scfg.prefill_buckets):
        w = plane.batcher.submit(list(range(1, b + 1)), max_new_tokens=2)
        w.wait(300)
    obs.reset()  # span totals cover the timed window only

    base_completed = plane.batcher.completed
    base_tokens = plane.batcher.total_new_tokens
    base_stages = dict(plane.engine.stage_seconds)
    clients = [
        threading.Thread(target=client_loop, args=(i,), daemon=True)
        for i in range(args.clients)
    ]
    t0 = time.perf_counter()
    for c in clients:
        c.start()
    time.sleep(args.duration)
    stop_clients.set()
    for c in clients:
        c.join(timeout=180)
    plane.batcher.drain(timeout=180)
    elapsed = time.perf_counter() - t0

    stats = plane.batcher.stats()
    spans = _span_totals()
    plane.stop()
    completed = stats["completed"] - base_completed
    new_tokens = stats["new_tokens"] - base_tokens
    arm = {
        "tokens_per_s": round(new_tokens / elapsed, 3),
        "requests_per_s": round(completed / elapsed, 3),
        "completed": completed,
        "new_tokens": new_tokens,
        "decode_steps": stats["decode_steps"],
        "duration_s": round(elapsed, 3),
        "latency_ms": stats["latency_ms"],
        "ttft_ms": stats["ttft_ms"],
        "staleness_hist": stats["staleness_hist"],
        "stages_s": {
            k: round(v - base_stages.get(k, 0.0), 6)
            for k, v in stats["stages_s"].items()
        },
        "client_errors": errors[:5],
        "loop_error": stats["loop_error"],
        "dropped": stats["failed"],
    }
    if spans is not None:
        arm["stages_from_spans_s"] = spans
    print(
        f"[{name}] tokens/s={arm['tokens_per_s']} stages={arm['stages_s']}"
    )
    return arm


# -- long-context tiering A/B (--longctx) ------------------------------------


def _longctx_arm(args, name, model_cfg, params, *, num_slots, kv_tier,
                 prompts, max_new) -> dict:
    """One open-loop leg: submit every request up front, wait for all.
    Equal per-request context across arms — only slot count and the cold
    tier differ."""
    import jax.numpy as jnp

    from opendiloco_tpu.serve import HostKVTier, ServeEngine
    from opendiloco_tpu.serve.scheduler import ContinuousBatcher

    engine = ServeEngine(
        model_cfg,
        params,
        num_slots=num_slots,
        max_context=args.max_context,
        prefill_buckets=[args.max_context // 4, args.max_context],
        compute_dtype=jnp.float32,
    )
    tier = (
        HostKVTier(host_slots=len(prompts) + 4, codec=args.tier_codec)
        if kv_tier
        else None
    )
    batcher = ContinuousBatcher(engine, kv_tier=tier).start()
    # warm the compile family (prefill buckets, decode, page transfers)
    w = batcher.submit(prompts[0][: args.max_context // 4], max_new_tokens=2)
    w.wait(300)
    batcher.drain(timeout=60)
    t0 = time.perf_counter()
    reqs = [batcher.submit(p, max_new_tokens=max_new) for p in prompts]
    hung = [r for r in reqs if not r.wait(600)]
    elapsed = time.perf_counter() - t0
    stats = batcher.stats()
    batcher.stop()
    errors = [r.error for r in reqs if r.error is not None]
    ttfts = [r.ttft_s * 1e3 for r in reqs if r.ttft_s is not None]
    arm = {
        "slots": num_slots,
        "kv_tier": bool(kv_tier),
        "requests": len(prompts),
        "per_request_context": len(prompts[0]) + max_new,
        "device_ring_tokens": num_slots * args.max_context,
        "aggregate_context_tokens": sum(len(p) + max_new for p in prompts),
        "duration_s": round(elapsed, 3),
        "tokens_per_s": round(stats["new_tokens"] / elapsed, 3),
        "ttft_p50_ms": round(float(np.percentile(ttfts, 50)), 3) if ttfts else None,
        "ttft_p99_ms": round(float(np.percentile(ttfts, 99)), 3) if ttfts else None,
        "latency_ms": stats["latency_ms"],
        "dropped": stats["failed"] + len(hung),
        "errors": errors[:5],
        "loop_error": stats["loop_error"],
        "tier": stats["tier"],
    }
    tokens = [list(r.tokens) for r in reqs]
    print(
        f"[{name}] slots={num_slots} tier={bool(kv_tier)} "
        f"ttft_p50={arm['ttft_p50_ms']}ms tokens/s={arm['tokens_per_s']} "
        f"dropped={arm['dropped']}"
        + (
            f" evictions={stats['tier']['evictions']}"
            f" resumes={stats['tier']['resumes']}"
            if stats["tier"]
            else ""
        )
    )
    return arm, tokens


def run_longctx(args) -> dict:
    model_cfg, params = _decode_model(args)
    rng = np.random.default_rng(3)
    max_new = args.max_new
    prompt_len = args.max_context - max_new  # final context fills the ring
    tiered_slots = max(1, args.slots)
    # enough concurrent requests that their aggregate context is >= 4x the
    # tiered arm's device ring (the whole point of the cold tier), with
    # half a slot's worth of margin over the exact 4x line
    n_req = -(-9 * tiered_slots // 2)  # ceil(4.5 * slots)
    prompts = [
        rng.integers(1, model_cfg.vocab_size, prompt_len).tolist()
        for _ in range(n_req)
    ]
    resident, tok_resident = _longctx_arm(
        args, "all-resident", model_cfg, params,
        num_slots=n_req, kv_tier=False, prompts=prompts, max_new=max_new,
    )
    tiered, tok_tiered = _longctx_arm(
        args, "tiered", model_cfg, params,
        num_slots=tiered_slots, kv_tier=True, prompts=prompts, max_new=max_new,
    )
    bit_exact = tok_resident == tok_tiered
    overcommit = (
        tiered["aggregate_context_tokens"] / tiered["device_ring_tokens"]
    )
    ttft_ratio = (
        tiered["ttft_p50_ms"] / resident["ttft_p50_ms"]
        if tiered["ttft_p50_ms"] and resident["ttft_p50_ms"]
        else None
    )
    return {
        "model": {
            "hidden": model_cfg.hidden_size,
            "layers": model_cfg.num_hidden_layers,
            "vocab": model_cfg.vocab_size,
        },
        "load": {
            "requests": n_req,
            "prompt_tokens": prompt_len,
            "max_new_tokens": max_new,
            "max_context": args.max_context,
            "tier_codec": args.tier_codec,
        },
        "arms": {"all_resident": resident, "tiered": tiered},
        "overcommit_x": round(overcommit, 3),
        "ttft_p50_ratio": round(ttft_ratio, 3) if ttft_ratio else None,
        "token_bit_exact": bit_exact,
    }


def run_decode(args) -> dict:
    model_cfg, params = _decode_model(args)
    arms = {"plain": run_decode_arm(args, "plain", model_cfg, params)}
    baseline = None
    try:
        with open(_OUT) as f:
            baseline = json.load(f)["throughput"]["tokens_per_s"]
    except (OSError, KeyError, ValueError):
        pass
    best_name = max(arms, key=lambda a: arms[a]["tokens_per_s"])
    best = arms[best_name]["tokens_per_s"]
    return {
        "model": {
            "hidden": model_cfg.hidden_size,
            "layers": model_cfg.num_hidden_layers,
            "vocab": model_cfg.vocab_size,
            "params": int(model_cfg.num_params()),
        },
        "load": {
            "clients": args.clients,
            "slots": args.slots,
            "max_new_tokens": args.max_new,
            "duration_s_per_arm": args.duration,
        },
        "arms": arms,
        "baseline_tokens_per_s": baseline,
        "best_arm": best_name,
        "best_tokens_per_s": best,
        "speedup_vs_baseline": (
            round(best / baseline, 3) if baseline else None
        ),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--selftest", action="store_true",
                    help="tiny CI run; artifact under $TMPDIR, no acceptance line")
    ap.add_argument("--decode", action="store_true",
                    help="plain decode over static weights; writes "
                         "DECODE_BENCH.json")
    ap.add_argument("--longctx", action="store_true",
                    help="KV-tiering A/B: all-resident vs host-cold-tier arms "
                         "at equal per-request context; banks a `longctx` "
                         "section into DECODE_BENCH.json")
    ap.add_argument("--tier-codec", default="none",
                    choices=("none", "blockwise4bit"),
                    help="cold-page codec for the --longctx tiered arm "
                         "(bit-exactness is only gated with `none`)")
    ap.add_argument("--duration", type=float, default=45.0,
                    help="seconds of sustained load")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-context", type=int, default=128)
    ap.add_argument("--local-steps", type=int, default=10,
                    help="inner steps per outer epoch (small -> frequent swaps)")
    ap.add_argument("--swap-every", type=int, default=8)
    args = ap.parse_args()

    out_path = _DECODE_OUT if (args.decode or args.longctx) else _OUT
    if args.selftest:
        args.duration = min(args.duration, 8.0 if not args.decode else 6.0)
        args.clients = min(args.clients, 3)
        args.slots = min(args.slots, 4 if not args.longctx else 2)
        args.hidden = min(args.hidden, 64)
        args.layers = min(args.layers, 2)
        args.max_new = min(args.max_new, 8 if not args.longctx else 16)
        args.local_steps = min(args.local_steps, 5)
        if args.longctx:
            args.max_context = min(args.max_context, 64)
        name = "DECODE_BENCH" if (args.decode or args.longctx) else "SERVE_BENCH"
        out_path = os.path.join(
            os.environ.get("TMPDIR", "/tmp"), f"{name}.selftest.json"
        )

    from opendiloco_tpu.utils.device import device_stamp
    if args.longctx:
        if not args.selftest:
            args.slots = min(args.slots, 4)  # 4 device slots vs ~18 requests
        result = run_longctx(args)
        # read-modify-write: the longctx section rides DECODE_BENCH.json
        # next to the decode arm without clobbering it
        try:
            with open(out_path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = {"schema": 1}
        doc["longctx"] = {
            "selftest": bool(args.selftest),
            **device_stamp(),
            "updated": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            **result,
        }
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        print(f"wrote {out_path} (longctx section)")
        lx = doc["longctx"]
        print(
            f"overcommit={lx['overcommit_x']}x ttft_ratio={lx['ttft_p50_ratio']} "
            f"bit_exact={lx['token_bit_exact']}"
        )
        for name, arm in lx["arms"].items():
            if arm["dropped"] != 0 or arm["errors"] or arm["loop_error"]:
                raise SystemExit(
                    f"longctx arm {name}: dropped={arm['dropped']} "
                    f"errors={arm['errors']} loop={arm['loop_error']}"
                )
        if lx["overcommit_x"] < 4.0:
            raise SystemExit(
                f"tiered arm served only {lx['overcommit_x']}x its device "
                "ring — acceptance is >= 4x"
            )
        if args.tier_codec == "none" and not lx["token_bit_exact"]:
            raise SystemExit("tiered token streams diverged from all-resident")
        ratio = lx["ttft_p50_ratio"]
        if ratio is not None and ratio > 1.5:
            # CPU CI boxes jitter; absolute slack covers tiny-p50 noise
            p50s = (
                lx["arms"]["tiered"]["ttft_p50_ms"],
                lx["arms"]["all_resident"]["ttft_p50_ms"],
            )
            if not (args.selftest and p50s[0] - p50s[1] <= 200.0):
                raise SystemExit(
                    f"tiered TTFT p50 regression {ratio}x — acceptance is <= 1.5x"
                )
        return
    if args.decode:
        # per-stage breakdown rides obs spans: arm the tracer for the run
        os.environ.setdefault("ODTP_OBS", "1")
        result = run_decode(args)
        doc = {
            "schema": 1,
            "selftest": bool(args.selftest),
            "host": {"node": os.uname().nodename, "cpus": os.cpu_count()},
            **device_stamp(),
            "updated": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            **result,
        }
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        print(f"wrote {out_path}")
        print(
            "best:", doc["best_arm"], doc["best_tokens_per_s"], "tok/s;",
            "baseline:", doc["baseline_tokens_per_s"],
            "speedup:", doc["speedup_vs_baseline"],
        )
        for name, arm in doc["arms"].items():
            if arm["loop_error"] or arm["client_errors"]:
                raise SystemExit(
                    f"decode arm {name} errors: {arm['client_errors']} "
                    f"{arm['loop_error']}"
                )
            if arm["dropped"] != 0:
                raise SystemExit(f"decode arm {name} dropped requests")
        if not args.selftest:
            if doc["baseline_tokens_per_s"] is None:
                raise SystemExit("no banked SERVE_BENCH.json baseline to gate on")
            if doc["speedup_vs_baseline"] < 2.0:
                raise SystemExit(
                    f"plain decode {doc['best_tokens_per_s']} tok/s is "
                    f"{doc['speedup_vs_baseline']}x the banked baseline — "
                    "acceptance is >= 2x"
                )
        return
    result = run_bench(args)
    doc = {
        "schema": 1,
        "selftest": bool(args.selftest),
        "host": {
            "node": os.uname().nodename,
            "cpus": os.cpu_count(),
        },
        **device_stamp(),
        "updated": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        **result,
    }
    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    print(f"wrote {out_path}")
    print(json.dumps(doc["throughput"], indent=None))
    print(json.dumps(doc["latency_ms"], indent=None))
    print("swaps:", json.dumps(doc["swaps"]), "dropped:", doc["dropped"])

    if doc["loop_error"] or doc["client_errors"]:
        raise SystemExit(f"serve bench errors: {doc['client_errors']} "
                         f"{doc['loop_error']}")
    if doc["dropped"] != 0:
        raise SystemExit(f"{doc['dropped']} requests dropped — acceptance is 0")
    if not doc["frontend_roundtrip_ok"]:
        raise SystemExit("socket front-end round trip failed")
    if not args.selftest and doc["swaps"]["count"] < 1:
        raise SystemExit(
            "no weight hot-swap observed during the full run — "
            "training too slow relative to --duration?"
        )


if __name__ == "__main__":
    main()
