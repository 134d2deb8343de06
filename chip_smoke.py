#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py            # one TPU chip: train + serve + compare
    python3 chip_smoke.py --chips 4  # four chips: sharded worker + galaxy

One process (a chip belongs to one process at a time). It refuses to run
anywhere but on a TPU: there is no CPU mode, and without a TPU it exits
non-zero and prints no result. Everything it reports is a smoke reading,
never a benchmark: wall times here include compilation and a cold cache.

One chip, all at llama-150m published width (d 1024, 12 layers, 16 heads x
64, vocab 32000), seq 1024, bf16-mixed, fake data from a fixed seed:

- *train*: ``opendiloco_tpu.train.train(Config(...))`` -- what the CLI's
  ``main()`` calls -- with DiLoCo on, one worker, two outer boundaries, and
  ``serve.enabled`` so the engine is built off the live masters.
- *serve*: while that trainer is alive, ``POST /generate`` and
  ``GET /healthz`` against the in-process server; the weights hot-swap off
  the masters between the two waves of requests.
- *compare*: each kernel against what it replaces, on the chip, same
  weights and inputs.

``--chips 4`` runs only what exists across chips: a FULL_SHARD worker
against its one-chip twin, llama-1b FULL_SHARD over an outer boundary, and
four one-chip DiLoCo workers in this one process.

Lines before the last are one JSON object per phase. The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
The phases are plain functions of a model name, sizes and a device list, so
tests/test_chip_smoke.py rehearses them at ``2m`` on CPU devices.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))

# stated tolerances of the comparisons (bf16 unless noted)
TRAIN_LOSS_RTOL = 1e-2  # pallas vs xla attention, one train step: loss
TRAIN_GNORM_RTOL = 5e-2  # ... and global grad norm
# pallas vs xla decode logits, ||d|| / ||ref||. In bf16 two equivalent
# paths round apart layer by layer (measured 2.3e-2 at 150m on the v5e); in
# float32 at the highest matmul precision only the kernel itself is left
LOGITS_REL_L2 = {"bfloat16": 5e-2, "float32": 2e-3}
SHARDED_LOSS_RTOL = 2e-2  # FULL_SHARD vs NO_SHARD loss trajectory

# how long the trainer may wait at an outer boundary for the serving wave
# in front of it, and a client for the server or a weight swap
HOLD_TIMEOUT_S = 600.0


def emit(phase: str, **facts) -> None:
    print(json.dumps({"phase": phase, **facts}), flush=True)


def require(facts: dict, **expected) -> None:
    """The facts that only hold on the chip (kernel really in the program,
    state really on the device), checked where the chip is."""
    for key, want in expected.items():
        if facts[key] != want:
            raise AssertionError(
                f"{key} resolved to {facts[key]!r} on the chip, expected {want!r}"
            )


# ---------------------------------------------------------------------------
# set-up: native library, compile cache, versions
# ---------------------------------------------------------------------------


def build_native() -> dict:
    """Build ``native/`` from the tracked sources before anything imports
    it, so a stale git-ignored binary is never what ran."""
    subprocess.run(
        ["make", "-C", os.path.join(REPO, "native"), "-s", "clean", "all"],
        check=True,
    )
    from opendiloco_tpu import native

    lib = native.get_lib()
    if lib is None:
        raise RuntimeError("native/libodtp.so was just built but did not load")
    return {"native": "built", "lib": lib._name, "version": lib.odtp_version()}


class CacheCounter:
    """Persistent-compile-cache events, as JAX's own monitoring reports
    them: a hit loads an executable, a miss compiles and writes one."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self)

    def __call__(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"cache_hits": self.hits, "cache_misses": self.misses}


def versions() -> dict:
    from importlib import metadata

    import jax
    import jaxlib

    out = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        out["libtpu"] = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        out["libtpu"] = None
    return out


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------


def ramp_batch(rng, vocab: int, batch: int, seq: int):
    """The learnable deterministic stream (consecutive-token ramps)."""
    import numpy as np

    starts = rng.integers(0, vocab, (batch, 1))
    ids = ((starts + np.arange(seq)) % vocab).astype(np.int32)
    return ids, ids.copy()


def tree_bytes_per_device(tree) -> dict:
    """Bytes each device really holds of ``tree``, from addressable_shards."""
    import jax

    held: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in leaf.addressable_shards:
            held[shard.device.id] = held.get(shard.device.id, 0) + shard.data.nbytes
    return held


def memory_stats(devices) -> list:
    """Per-device allocator readings where the backend reports them."""
    out = []
    for d in devices:
        ms = d.memory_stats() or {}
        out.append(
            {
                "device": d.id,
                "bytes_in_use": ms.get("bytes_in_use"),
                "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
                "bytes_limit": ms.get("bytes_limit"),
            }
        )
    return out


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_json(port: int, path: str, body: dict | None = None, timeout=300.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read().decode())


def wait_until(what: str, fn, timeout: float = HOLD_TIMEOUT_S, every=0.25):
    deadline = time.monotonic() + timeout
    while True:
        try:
            got = fn()
        except OSError:
            got = None
        if got:
            return got
        if time.monotonic() > deadline:
            raise TimeoutError(f"timed out after {timeout:.0f}s waiting for {what}")
        time.sleep(every)


# ---------------------------------------------------------------------------
# phase: train + serve (one chip)
# ---------------------------------------------------------------------------


def smoke_config(model: str, seq: int, batch: int, **over):
    """The one training configuration the phases share, so that the same
    train step compiles once: auto defaults (pallas attention, unfused
    loss, full unroll on a TPU) without rematerialization -- the repo's
    headline configuration."""
    from opendiloco_tpu.config import Config

    base = dict(
        path_model=model,
        fake_data=True,
        fake_data_mode="ramp",
        seq_length=seq,
        per_device_train_batch_size=batch,
        total_batch_size=batch,
        warmup_steps=2,
        lr=4e-4,
        precision="bf16-mixed",
        remat=False,
        metric_logger_type="jsonl",
    )
    base.update(over)
    return Config(**base)


def make_trainer(model, seq, batch, devices, strategy="NO_SHARD", **over):
    """-> (model config, InnerTrainer) built the way ``train()`` builds them
    from ``smoke_config``, on a mesh over ``devices``."""
    from opendiloco_tpu.models import hf_io
    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.train import make_trainer_config
    from opendiloco_tpu.trainer import InnerTrainer

    model_cfg, _ = hf_io.get_model(model)
    config = smoke_config(model, seq, batch, sharding_strategy=strategy, **over)
    return model_cfg, InnerTrainer(
        model_cfg,
        make_trainer_config(config),
        build_mesh(strategy, devices=devices),
    )


def size_batch(model, seq, devices, batch_sizes, resident_bytes: int, **over):
    """Largest candidate batch whose compiled train step, per
    ``memory_analysis()``, fits the device beside ``resident_bytes`` of
    other state. Returns the compiled step's facts too. Given the same
    ``over`` as the run's config it is the very program ``train()`` compiles
    next (the schedule's constants are part of it), so that compile is a
    cache hit."""
    limit = (devices[0].memory_stats() or {}).get("bytes_limit")
    for batch in batch_sizes:
        _, trainer = make_trainer(model, seq, batch, devices, **over)
        t0 = time.perf_counter()
        compiled = trainer.lower_abstract(batch, seq, accum=1).compile()
        compile_s = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        step_bytes = (
            mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes
        )
        facts = {
            "batch": batch,
            "attn_impl": trainer.tc.attn_impl,
            "fused_loss": bool(trainer.tc.fused_loss),
            "scan_unroll": trainer.tc.scan_unroll,
            "compile_s": round(compile_s, 2),
            "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
            "step_bytes": int(step_bytes),
            "resident_bytes": int(resident_bytes),
            "bytes_limit": limit,
        }
        # 0.92: the allocator's own overhead and fragmentation
        if limit is None or step_bytes + resident_bytes <= 0.92 * limit:
            return facts
        emit("train.size", fits=False, **facts)
    raise RuntimeError(
        f"no batch of {batch_sizes} fits {limit} bytes beside the outer "
        "plane and the serving engine"
    )


def drive_serving(port: int, holds: list, prompts: list, new_tokens: int, out: dict):
    """The serving client: a wave of requests on the initial weights, let
    the trainer cross its first boundary, wait for the hot-swap, a second
    wave, let the trainer cross its second boundary and finish. The trainer
    cannot end while a request is in flight: it is held at each boundary
    until the wave in front of it has been answered."""

    def generate(prompt):
        return http_json(
            port, "/generate", {"prompt": prompt, "max_new_tokens": new_tokens}
        )

    try:
        out["healthz"] = wait_until(
            "the serving port", lambda: http_json(port, "/healthz", timeout=5.0)
        )
        with concurrent.futures.ThreadPoolExecutor(len(prompts)) as pool:
            out["wave1"] = list(pool.map(generate, prompts))
            holds[0].set()
            first = max(r["epoch"] for r in out["wave1"])
            wait_until(
                "the weights hot-swap",
                lambda: http_json(port, "/healthz", timeout=5.0)["weights_epoch"]
                > first,
            )
            out["wave2"] = list(pool.map(generate, prompts))
        out["healthz_end"] = http_json(port, "/healthz", timeout=5.0)
    except BaseException as e:  # re-raised by the phase, on the main thread
        out["error"] = e
    finally:
        for hold in holds:  # never leave the trainer waiting
            hold.set()


def phase_train_serve(
    model: str,
    seq: int,
    devices: list,
    *,
    seed: int,
    batch_sizes=(8, 4, 2, 1),
    local_steps: int = 5,
    slots: int = 8,
    buckets=(64, 256),
    prompt_lens=(40, 200, 48, 180),
    new_tokens: int = 32,
) -> dict:
    import numpy as np

    from opendiloco_tpu.config import DilocoConfig, ServeConfig
    from opendiloco_tpu.diloco import LoopbackWorld
    from opendiloco_tpu.models import hf_io
    from opendiloco_tpu.serve.kvcache import pick_bucket
    from opendiloco_tpu.train import train
    from opendiloco_tpu.utils.logger import read_jsonl

    model_cfg, _ = hf_io.get_model(model)
    # beside the train state: four f32 copies of the parameters (master and
    # momentum of the device outer plane, the engine's own weights, one more
    # while a hot-swap holds old and new) and the bf16 K and V rings
    kv_ring = (
        2 * 2 * model_cfg.num_hidden_layers * slots * seq
        * model_cfg.kv_heads * model_cfg.head_dim
    )
    total_steps = 2 * local_steps  # two outer boundaries
    step = size_batch(
        model,
        seq,
        devices,
        batch_sizes,
        4 * 4 * model_cfg.num_params() + kv_ring,
        total_steps=total_steps,
    )
    port = free_port()
    rows_path = os.path.join(tempfile.mkdtemp(prefix="chip_smoke_"), "train.jsonl")
    config = smoke_config(
        model,
        seq,
        step["batch"],
        total_steps=total_steps,
        project=rows_path,
        diloco=DilocoConfig(
            backend="loopback", local_steps=local_steps, skip_load_from_peers=True
        ),
        serve=ServeConfig(
            enabled=True,
            port=port,
            max_batch=slots,
            max_context=seq,
            prefill_buckets=list(buckets),
        ),
    )

    # one outer round per boundary (an all-reduce a piece, all under the
    # round's epoch); each waits for the serving wave before it
    (backend,) = LoopbackWorld(1).make_backends()
    holds = [threading.Event(), threading.Event()]
    all_reduce = backend.all_reduce

    def held_all_reduce(arrays, **kw):
        epoch = kw.get("epoch") or 0
        if epoch < len(holds) and not holds[epoch].wait(HOLD_TIMEOUT_S):
            raise TimeoutError("the serving wave before this boundary never ended")
        return all_reduce(arrays, **kw)

    backend.all_reduce = held_all_reduce

    rng = np.random.default_rng(seed)
    prompts = [
        rng.integers(3, model_cfg.vocab_size, n).tolist() for n in prompt_lens
    ]
    served: dict = {}
    client = threading.Thread(
        target=drive_serving,
        args=(port, holds, prompts, new_tokens, served),
        name="chip-smoke-client",
        daemon=True,
    )
    client.start()
    t0 = time.perf_counter()
    summary = train(config, backend, devices=devices)
    train_wall_s = time.perf_counter() - t0
    client.join(timeout=60.0)
    if client.is_alive():
        raise RuntimeError("the serving client did not finish")
    if "error" in served:
        raise served["error"]

    rows = read_jsonl(rows_path)
    losses = [r["Loss"] for r in rows]
    facts = {
        **step,
        "losses": [round(x, 4) for x in losses],
        "step_wall_s_smoke": [round(r["time_taken"], 3) for r in rows],
        "train_wall_s_smoke": round(train_wall_s, 2),
        "outer_epoch": summary["outer_epoch"],
        "outer_placement": summary["outer_placement"],
        "num_peers": sorted({int(r["num_peers"]) for r in rows}),
        "memory": memory_stats(devices),
    }
    assert len(losses) == total_steps, rows
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    assert summary["outer_epoch"] == 2, summary

    answers = served["wave1"] + served["wave2"]
    for prompt, ans in zip(prompts + prompts, answers):
        assert "error" not in ans and len(ans["tokens"]) == new_tokens, ans
    epochs = [a["epoch"] for a in answers]
    assert max(epochs) > epochs[0], f"no hot-swap seen: epochs {epochs}"
    facts["serve"] = {
        "platform": served["healthz"]["platform"],
        "device_kind": served["healthz"]["device_kind"],
        "decode_kernel": served["healthz"]["decode_kernel"],
        "healthz_ok": served["healthz"]["ok"] and served["healthz_end"]["ok"],
        "requests": [
            {
                "prompt_len": len(p),
                "bucket": pick_bucket(len(p), sorted(buckets)),
                "new_tokens": len(a["tokens"]),
                "epoch": a["epoch"],
                "latency_ms_smoke": a["latency_ms"],
            }
            for p, a in zip(prompts + prompts, answers)
        ],
        "weight_epochs": sorted(set(epochs)),
    }
    assert facts["serve"]["healthz_ok"], served
    assert len({r["bucket"] for r in facts["serve"]["requests"]}) >= 2
    return facts


# ---------------------------------------------------------------------------
# phase: compare (one chip)
# ---------------------------------------------------------------------------


def phase_compare_train_step(model, seq, devices, *, seed, batch=2):
    """(a) one train step, attention ``pallas`` vs ``xla``."""
    import jax
    import numpy as np

    out = {}
    for impl in ("pallas", "xla"):
        model_cfg, trainer = make_trainer(
            model, seq, batch, devices, attn_implementation=impl
        )
        ids, labels = ramp_batch(
            np.random.default_rng(seed), model_cfg.vocab_size, batch, seq
        )
        state = trainer.init_state(jax.random.key(seed))
        state, m = trainer.train_step(state, trainer.shard_batch(ids, labels, 1))
        out[impl] = {
            "loss": float(m["loss"]),
            "grad_norm": float(m["grad_norm"]),
        }
        del state, trainer
    rel = lambda k: abs(out["pallas"][k] - out["xla"][k]) / abs(out["xla"][k])
    facts = {
        "batch": batch,
        **{f"{impl}_{k}": round(v, 5) for impl, d in out.items() for k, v in d.items()},
        "loss_rel_diff": rel("loss"),
        "grad_norm_rel_diff": rel("grad_norm"),
        "tolerance": {"loss_rtol": TRAIN_LOSS_RTOL, "grad_norm_rtol": TRAIN_GNORM_RTOL},
    }
    assert np.isfinite(out["pallas"]["loss"]) and np.isfinite(out["xla"]["loss"])
    assert facts["loss_rel_diff"] <= TRAIN_LOSS_RTOL, facts
    assert facts["grad_norm_rel_diff"] <= TRAIN_GNORM_RTOL, facts
    return facts


def phase_compare_decode_kernels(model, seq, devices, *, seed, slots=8):
    """(b) one ``decode_forward`` over a half-full ring, decode kernel
    ``pallas`` vs ``xla``: in bfloat16, the
    engine's dtype, and in float32 at the highest matmul precision, where
    what is left of the difference is the kernel's own."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from opendiloco_tpu.models import hf_io
    from opendiloco_tpu.models.llama import decode_forward, init_params
    from opendiloco_tpu.models.ring_cache import cache_shape

    cfg, _ = hf_io.get_model(model)
    facts: dict = {"tolerance": {"logits_rel_l2": LOGITS_REL_L2}}
    with jax.default_device(devices[0]):
        kp, kk, kv, kt = jax.random.split(jax.random.key(seed), 4)
        params = init_params(kp, cfg)
        shape = cache_shape(
            cfg.num_hidden_layers, slots, seq, cfg.kv_heads, cfg.head_dim
        )
        # ragged around half full, one empty slot
        lens = jnp.asarray(
            [0] + [seq // 2 + 7 * i for i in range(1, slots)], jnp.int32
        )
        tokens = jax.random.randint(kt, (slots,), 3, cfg.vocab_size, jnp.int32)

        def run(dt, kernel, cache_k, cache_v):
            """-> (logits, Pallas kernels in the compiled program)"""
            args = (params, tokens, lens, cache_k, cache_v)
            compiled = (
                jax.jit(
                    lambda p, t, l, ck, cv: decode_forward(
                        p, t, l, ck, cv, cfg, compute_dtype=dt, decode_kernel=kernel
                    )[0]
                )
                .lower(*args)
                .compile()
            )
            return (
                np.asarray(compiled(*args)),
                compiled.as_text().count("tpu_custom_call"),
            )

        for dt in (jnp.bfloat16, jnp.float32):
            name = jnp.dtype(dt).name
            cache_k = (0.5 * jax.random.normal(kk, shape, jnp.float32)).astype(dt)
            cache_v = (0.5 * jax.random.normal(kv, shape, jnp.float32)).astype(dt)
            facts[name] = {}
            with jax.default_matmul_precision(
                "highest" if dt == jnp.float32 else "default"
            ):
                g, calls = run(dt, "pallas", cache_k, cache_v)
                r, _ = run(dt, "xla", cache_k, cache_v)
                assert np.all(np.isfinite(g)) and np.all(np.isfinite(r))
                rel = float(np.linalg.norm(g - r) / np.linalg.norm(r))
                facts[name]["decode"] = {
                    "tpu_custom_calls": calls,
                    "logits_shape": list(g.shape),
                    "rel_l2": rel,
                    "max_abs_diff": float(np.max(np.abs(g - r))),
                    "ref_abs_max": float(np.max(np.abs(r))),
                    # reported, not asserted: random weights give
                    # near-uniform logits
                    "greedy_tokens_agree": bool(
                        np.array_equal(g.argmax(-1), r.argmax(-1))
                    ),
                }
                assert rel <= LOGITS_REL_L2[name], (name, facts[name])
    return facts


def phase_compare_engines(
    model, seq, devices, *, seed, new_tokens=32, buckets=(64, 256)
):
    """(c) the prefix-cache path end to end, under the continuous batcher,
    against the plain engine: the same requests must give the same tokens.

    Random weights give near-uniform logits, where one argmax flipped by
    rounding forks a stream for good, and the paths do round differently:
    other kernels, other matmul shapes. So token identity is asserted where
    rounding is out of the picture -- float32 at the highest matmul
    precision, where the paths differ by accumulation order only -- and in
    bfloat16 (the in-process engine's dtype) the agreement is reported. On
    either path and in both dtypes every request must complete and the
    prefix cache must really have hit."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from opendiloco_tpu.models import hf_io
    from opendiloco_tpu.models.llama import init_params
    from opendiloco_tpu.serve import ContinuousBatcher, ServeEngine

    cfg, _ = hf_io.get_model(model)
    rng = np.random.default_rng(seed)
    shared = rng.integers(3, cfg.vocab_size, buckets[0] // 2).tolist()
    # two prompts that share a prefix (the second reuses it while the first
    # still decodes), and one in the larger prefill bucket
    prompts = [
        shared + rng.integers(3, cfg.vocab_size, 5).tolist(),
        shared + rng.integers(3, cfg.vocab_size, 9).tolist(),
        rng.integers(
            3, cfg.vocab_size, buckets[0] + (buckets[1] - buckets[0]) // 4
        ).tolist(),
    ]
    def serve(dtype, prefix_cache=False):
        """-> (token stream per prompt, batcher stats, resolved kernel)"""
        engine = ServeEngine(
            cfg,
            params,
            num_slots=4,
            max_context=seq,
            prefill_buckets=buckets,
            compute_dtype=dtype,
        )
        batcher = ContinuousBatcher(engine, prefix_cache=prefix_cache).start()
        try:
            reqs = [batcher.submit(p, max_new_tokens=new_tokens) for p in prompts]
            for r in reqs:
                assert r.wait(HOLD_TIMEOUT_S), "request timed out"
                assert r.error is None, r.error
                assert len(r.tokens) == new_tokens, r.tokens
            return [list(r.tokens) for r in reqs], batcher.stats(), engine.decode_kernel
        finally:
            batcher.stop()

    def agreeing(got, ref):
        return sum(a == b for sa, sb in zip(got, ref) for a, b in zip(sa, sb))

    facts: dict = {}
    with jax.default_device(devices[0]):
        params = init_params(jax.random.key(seed), cfg)
        try:
            for dtype in (jnp.bfloat16, jnp.float32):
                # global, not the thread-local context manager: the engine's
                # jits trace on the batcher's own thread
                jax.config.update(
                    "jax_default_matmul_precision",
                    "highest" if dtype == jnp.float32 else None,
                )
                plain, _, kernel = serve(dtype)
                prefix, prefix_stats, _ = serve(dtype, prefix_cache=True)
                prefix_stats = prefix_stats["prefix"]
                assert prefix_stats["hits"] > 0, prefix_stats
                facts[jnp.dtype(dtype).name] = {
                    "decode_kernel": kernel,
                    "tokens_per_path": new_tokens * len(prompts),
                    "prefix_hits": prefix_stats["hits"],
                    "prefix_tokens_saved": prefix_stats["tokens_saved"],
                    "prefix_identical_to_plain": prefix == plain,
                    "prefix_tokens_agreeing": agreeing(prefix, plain),
                }
        finally:
            jax.config.update("jax_default_matmul_precision", None)
    assert facts["float32"]["prefix_identical_to_plain"], facts
    return facts


# ---------------------------------------------------------------------------
# phases: four chips
# ---------------------------------------------------------------------------


def _trajectory(model, seq, devices, strategy, *, seed, batch, steps):
    import jax
    import numpy as np

    model_cfg, trainer = make_trainer(model, seq, batch, devices, strategy)
    state = trainer.init_state(jax.random.key(seed))
    held = tree_bytes_per_device(
        {"params": state["params"], "opt_state": state["opt_state"]}
    )
    rng = np.random.default_rng(seed)
    losses = []
    for _ in range(steps):
        ids, labels = ramp_batch(rng, model_cfg.vocab_size, batch, seq)
        state, m = trainer.train_step(state, trainer.shard_batch(ids, labels, 1))
        losses.append(float(m["loss"]))
    return losses, held, trainer.tc


def assert_spread(held: dict, devices: list, what: str) -> None:
    """State is spread over all of ``devices``, not parked on the first."""
    assert sorted(held) == sorted(d.id for d in devices), (what, held)
    total = sum(held.values())
    for dev, nbytes in held.items():
        share = nbytes / total
        assert share <= 1.2 / len(devices), (
            f"{what}: device {dev} holds {share:.0%} of the state "
            f"over {len(devices)} devices"
        )


def phase_sharded(
    model: str,
    big_model: str,
    seq: int,
    devices: list,
    *,
    seed: int,
    batch: int = 8,
    steps: int = 6,
    big_batch: int = 16,
    big_accum: int = 4,
    big_local_steps: int = 3,
    big_steps: int = 5,
) -> dict:
    """A FULL_SHARD worker over ``devices`` against its NO_SHARD twin on the
    first of them, then ``big_model`` FULL_SHARD across an outer boundary."""
    import jax
    import numpy as np

    from opendiloco_tpu.config import DilocoConfig
    from opendiloco_tpu.diloco import DiLoCoOptimizer, LoopbackWorld

    kw = dict(seed=seed, batch=batch, steps=steps)
    sharded, held, tc = _trajectory(model, seq, devices, "FULL_SHARD", **kw)
    assert_spread(held, devices, f"{model} FULL_SHARD")
    single, _, _ = _trajectory(model, seq, devices[:1], "NO_SHARD", **kw)
    rel = float(np.max(np.abs(np.array(sharded) - single) / np.abs(single)))
    facts = {
        "twin": {
            "model": model,
            "attn_impl": tc.attn_impl,
            "global_batch": batch,
            "full_shard_losses": [round(x, 4) for x in sharded],
            "no_shard_losses": [round(x, 4) for x in single],
            "max_rel_diff": rel,
            "tolerance": {"loss_rtol": SHARDED_LOSS_RTOL},
            "state_bytes_per_device": held,
        }
    }
    assert np.all(np.isfinite(sharded)) and np.all(np.isfinite(single))
    assert rel <= SHARDED_LOSS_RTOL, facts["twin"]
    jax.clear_caches()

    # the model that does not fit one chip: sharded from its first draw
    big_cfg, trainer = make_trainer(
        big_model, seq, big_batch, devices, "FULL_SHARD", remat=True
    )
    state = trainer.init_state(jax.random.key(seed))
    after_init = memory_stats(devices)
    (backend,) = LoopbackWorld(1).make_backends()
    opt = DiLoCoOptimizer(
        trainer,
        backend,
        DilocoConfig(
            backend="loopback", local_steps=big_local_steps, skip_load_from_peers=True
        ),
        state,
        batch_size=big_batch,
    )
    rng = np.random.default_rng(seed)
    losses = []
    t0 = time.perf_counter()
    for _ in range(big_steps):
        ids, labels = ramp_batch(rng, big_cfg.vocab_size, big_batch, seq)
        state, m = opt.step(state, trainer.shard_batch(ids, labels, big_accum))
        losses.append(float(m["loss"]))
    state = opt.flush(state)
    held = tree_bytes_per_device(
        {"params": state["params"], "opt_state": state["opt_state"]}
    )
    facts["big"] = {
        "model": big_model,
        "params": big_cfg.num_params(),
        "attn_impl": trainer.tc.attn_impl,
        "fused_loss": bool(trainer.tc.fused_loss),
        "scan_unroll": trainer.tc.scan_unroll,
        "global_batch": big_batch,
        "accum": big_accum,
        "losses": [round(x, 4) for x in losses],
        "wall_s_smoke": round(time.perf_counter() - t0, 2),
        "outer_epoch": opt.epoch,
        "outer_placement": opt.placement,
        "state_bytes_per_device": held,
        "memory_after_init": after_init,
        "memory": memory_stats(devices),
    }
    assert np.all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    assert opt.epoch == big_steps // big_local_steps >= 1, opt.epoch
    assert_spread(held, devices, f"{big_model} FULL_SHARD")
    in_use = [m["bytes_in_use"] for m in facts["big"]["memory"]]
    if all(b is not None for b in in_use):  # parked on one of n: a ratio of n
        assert max(in_use) <= 2 * min(in_use), f"lopsided devices: {in_use}"
    opt.drop_pending()
    return facts


def phase_galaxy(
    model: str,
    seq: int,
    devices: list,
    *,
    seed: int,
    batch: int = 8,
    local_steps: int = 2,
    rounds: int = 2,
) -> dict:
    """One one-chip DiLoCo worker per device, all in this process: threads,
    each trainer on its own single-device mesh, one ``LoopbackWorld``."""
    import jax
    import numpy as np

    from opendiloco_tpu.config import DilocoConfig
    from opendiloco_tpu.diloco import DiLoCoOptimizer, LoopbackWorld
    from opendiloco_tpu.utils.debug import hash_pytree

    n = len(devices)
    backends = LoopbackWorld(n).make_backends()
    results: list = [None] * n
    errors: list = []

    def worker(rank: int) -> None:
        try:
            model_cfg, trainer = make_trainer(model, seq, batch, [devices[rank]])
            state = trainer.init_state(jax.random.key(seed))  # same everywhere
            opt = DiLoCoOptimizer(
                trainer,
                backends[rank],
                DilocoConfig(
                    backend="loopback",
                    local_steps=local_steps,
                    skip_load_from_peers=True,
                    timeout_waiting_for_peers=HOLD_TIMEOUT_S,
                    averaging_timeout=HOLD_TIMEOUT_S,
                ),
                state,
                batch_size=batch,
            )
            rng = np.random.default_rng((seed, rank))  # disjoint shards
            losses, masters, peers = [], [], []
            for step in range(1, rounds * local_steps + 1):
                ids, labels = ramp_batch(rng, model_cfg.vocab_size, batch, seq)
                state, m = opt.step(state, trainer.shard_batch(ids, labels, 1))
                losses.append(float(m["loss"]))
                if step % local_steps == 0:
                    # the boundary step leaves params == the new master
                    masters.append(hash_pytree(state["params"]))
                    peers.append(int(m["num_peers"]))
            on = {d.id for leaf in jax.tree.leaves(state) for d in leaf.devices()}
            results[rank] = {
                "device": devices[rank].id,
                "state_on_devices": sorted(on),
                "losses": [round(x, 4) for x in losses],
                "master_hashes": masters,
                "num_peers": peers,
                "outer_epoch": opt.epoch,
                "outer_placement": opt.placement,
            }
            opt.drop_pending()
        except BaseException as e:  # re-raised by the phase, on the main thread
            errors.append((rank, e))

    threads = [
        threading.Thread(target=worker, args=(r,), name=f"galaxy-{r}")
        for r in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=2 * HOLD_TIMEOUT_S)
    if errors:
        raise errors[0][1]
    assert not any(t.is_alive() for t in threads), "a worker never finished"

    for rank, r in enumerate(results):
        assert r["state_on_devices"] == [devices[rank].id], r
        assert r["num_peers"] == [n] * rounds, r
        assert r["outer_epoch"] == rounds, r
        assert np.all(np.isfinite(r["losses"])), r
        # bit-equal masters after every boundary
        assert r["master_hashes"] == results[0]["master_hashes"], (
            rank, r["master_hashes"], results[0]["master_hashes"]
        )
    return {"workers": results, "memory": memory_stats(devices)}


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1: train + serve + compare on one chip (default). 4: only the "
        "sharded-worker and galaxy phases, on four",
    )
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    found = jax.devices()
    if found[0].platform != "tpu":
        print(
            f"chip_smoke: JAX found platform {found[0].platform!r}, not a TPU; "
            "there is no CPU mode and nothing was run",
            file=sys.stderr,
        )
        return 2
    if len(found) < args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but JAX found {len(found)}",
            file=sys.stderr,
        )
        return 2
    devices = found[: args.chips]
    kind = devices[0].device_kind

    native = build_native()
    from opendiloco_tpu.utils.compile_cache import enable_compile_cache

    cache = CacheCounter()
    emit(
        "setup",
        **versions(),
        device_kind=kind,
        devices_found=len(found),
        devices_used=len(devices),
        compile_cache_dir=enable_compile_cache(),
        **native,
    )

    if args.chips == 1:
        facts = phase_train_serve("150m", 1024, devices, seed=args.seed)
        require(facts, attn_impl="pallas", outer_placement="device")
        require(facts["serve"], platform="tpu", decode_kernel="pallas")
        assert facts["tpu_custom_calls"] > 0, "no Pallas kernel in the train step"
        emit("train+serve", **facts, **cache.snapshot())
        jax.clear_caches()  # drop the phase's executables from the device

        facts = phase_compare_train_step("150m", 1024, devices, seed=args.seed)
        emit("compare.train_step", **facts, **cache.snapshot())
        jax.clear_caches()

        facts = phase_compare_decode_kernels("150m", 1024, devices, seed=args.seed)
        for dtype in LOGITS_REL_L2:
            assert facts[dtype]["decode"]["tpu_custom_calls"] > 0, dtype
        emit("compare.decode_kernels", **facts, **cache.snapshot())

        facts = phase_compare_engines("150m", 1024, devices, seed=args.seed)
        for eng in facts.values():
            require(eng, decode_kernel="pallas")
        emit("compare.engines", **facts, **cache.snapshot())
    else:
        facts = phase_sharded("150m", "1b", 1024, devices, seed=args.seed)
        require(facts["twin"], attn_impl="pallas")
        require(facts["big"], attn_impl="pallas", outer_placement="device")
        emit("sharded", **facts, **cache.snapshot())
        jax.clear_caches()

        facts = phase_galaxy("150m", 1024, devices, seed=args.seed)
        for w in facts["workers"]:
            require(w, outer_placement="device")
        emit("galaxy", **facts, **cache.snapshot())

    print(
        json.dumps(
            {
                "ok": True,
                "device": {"platform": "tpu", "kind": kind, "count": len(devices)},
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
