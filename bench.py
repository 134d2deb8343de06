"""Headline benchmark: inner-loop training throughput on llama-150m.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

The reference publishes no in-tree numbers (BASELINE.md); the driver-specified
north-star is >=40% inner-loop MFU on llama-150m (BASELINE.json). We report
tokens/sec/chip and vs_baseline = achieved_MFU / 0.40.

Sweeps perf variants -- the measured-best first: pallas attention, UNFUSED
loss, remat=False (no recompute -- it fits at small batch), per-chip
bs13 under the full layer-scan unroll -- the config that beat the 40%
MFU north-star by 6.6 points in round 5's live fine sweep (best
end-to-end emission 78,541 tok/s, 46.60% MFU; the full unroll lets XLA
fuse the lm-head itself, beating the manual fused kernel's slower
backward), then the runner-up configs and the XLA baseline
comparison row -- and reports the fastest. A variant that fails to
compile loses that variant, not the whole bench. It measures on a TPU or
not at all: with no TPU, or when no variant completes, it prints an error
and exits non-zero -- never a number it did not measure. Pin a single
variant with OPENDILOCO_TPU_BENCH_ATTN /
OPENDILOCO_TPU_BENCH_FUSED / OPENDILOCO_TPU_BENCH_REMAT (true|false|dots|dots_all)
/ OPENDILOCO_TPU_BENCH_BS (global batch); unset pin knobs default to
the headline pallas+fused config.
"""

import json
import os
import sys
import time

import numpy as np

_METRIC = "llama-150m inner-loop throughput (seq 1024, bf16)"
_RESULTS: dict[str, float] = {}  # variant -> tokens/sec/chip (best-so-far store)
_CTX: dict = {}

# Measurement log: every successful variant measurement is appended here
# (JSONL) the moment it exists. A record of what was measured, never a
# source for what this run reports.
_BANK_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "BENCH_LIVE.json")


def _bank(model: str, variant: str, tps: float) -> None:
    mfu = tps * _CTX["flops_per_token"] / _CTX["peak"]
    row = {
        "ts": time.time(),
        "iso": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "model": model,
        "variant": variant,
        "tokens_per_sec_per_chip": round(tps, 1),
        "mfu": round(mfu, 4),
        "device": _CTX["device"],
        "chips": _CTX["chips"],
    }
    try:
        with open(_BANK_PATH, "a") as f:
            f.write(json.dumps(row) + "\n")
    except OSError as e:
        print(f"# bank write failed: {e}", flush=True)


def model_flops_per_token(cfg, seq: int) -> float:
    """fwd+bwd matmul FLOPs per token: 6*N_matmul + causal attention term."""
    n_matmul = cfg.num_params() - cfg.vocab_size * cfg.hidden_size  # drop embed
    attn = 6 * cfg.num_hidden_layers * cfg.hidden_size * seq  # causal: 12*L*D*T/2
    return 6 * n_matmul + attn


_XLA_ATTN_MFU_REF = 0.289  # PARITY.md: "xla attention, remat=full" same-chip MFU


def _vs_xla_attention(tps: float, mfu: float) -> float:
    """Side-by-side same-chip ratio vs the XLA-attention baseline — the
    honest companion to ``vs_baseline`` (which divides by the 0.40-MFU
    north star and reads like an absolute claim). Prefers an xla variant
    measured in THIS run; otherwise scales by the committed PARITY.md
    xla-attention MFU (28.9%), which is a same-chip tokens/sec ratio."""
    xla = [v for k, v in _RESULTS.items() if k.startswith("xla") and v > 0]
    if xla:
        return round(tps / max(xla), 4)
    return round(mfu / _XLA_ATTN_MFU_REF, 4)


def _emit() -> None:
    """Print the one JSON line for the fastest variant measured in this run."""
    best = max(_RESULTS, key=_RESULTS.get)
    tps = _RESULTS[best]
    mfu = tps * _CTX["flops_per_token"] / _CTX["peak"]
    print(
        json.dumps(
            {
                "metric": _METRIC,
                "value": round(tps, 1),
                "unit": "tokens/sec/chip",
                "vs_baseline": round(mfu / 0.40, 4),
                "vs_xla_attention": _vs_xla_attention(tps, mfu),
                "extra": {
                    "mfu": round(mfu, 4),
                    "chips": _CTX["chips"],
                    "device": _CTX["device"],
                    "platform": _CTX["platform"],
                    "best_variant": best,
                    "variants": {k: round(v, 1) for k, v in _RESULTS.items()},
                },
            }
        ),
        flush=True,
    )


def _run_variant(
    cfg, attn: str, fused: bool, seq: int, bs: int, accum: int, remat=True,
    n_steps: int = 15,
):
    """One timed variant; bs is the GLOBAL batch (per-chip x chips)."""
    import jax

    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

    tc = TrainerConfig(
        lr=4e-4, warmup_steps=10, total_steps=1000, precision="bf16-mixed",
        attn_impl=attn, remat=remat, fused_loss=fused,
    )
    trainer = InnerTrainer(cfg, tc, build_mesh("NO_SHARD"))
    state = trainer.init_state(jax.random.key(0))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (bs, seq)).astype(np.int32)
    batch = trainer.shard_batch(ids, ids.copy(), accum=accum)

    for _ in range(3):  # warmup/compile
        state, m = trainer.train_step(state, batch)
    float(m["loss"])  # scalar fetch: waits for the device

    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, m = trainer.train_step(state, batch)
    loss = float(m["loss"])
    dt = time.perf_counter() - t0
    assert np.isfinite(loss), loss
    return n_steps * bs * seq / dt / _CTX["chips"]


def main():
    import jax

    from opendiloco_tpu.models.hf_io import get_model
    from opendiloco_tpu.obs.mfu import peak_flops
    from opendiloco_tpu.utils.compile_cache import enable_compile_cache

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(
            f"bench.py measures on a TPU; JAX found platform "
            f"{device.platform!r} ({device.device_kind}). Nothing was measured."
        )
    enable_compile_cache()

    model = os.environ.get("OPENDILOCO_TPU_BENCH_MODEL", "150m")
    cfg, _ = get_model(model)
    seq, per_dev_bs, accum = 1024, 16, 1
    if model == "1b":
        # fp32 params + adam ~= 12GB on a 16GB chip: small micro-batch,
        # accumulate to keep the MXU fed
        per_dev_bs, accum = 4, 4
    elif model != "150m":  # smoke/debug runs on small models
        seq, per_dev_bs = 256, 8
    n_chips = len(jax.devices())
    bs = per_dev_bs * n_chips

    global _METRIC
    if model != "150m":
        _METRIC = f"llama-{model} inner-loop throughput (seq {seq}, bf16)"
    _CTX.update(
        model=model,
        chips=n_chips,
        device=device.device_kind,
        platform=device.platform,
        peak=peak_flops(device.device_kind),  # per-chip MFU accounting
        flops_per_token=model_flops_per_token(cfg, seq),
    )

    env_attn = os.environ.get("OPENDILOCO_TPU_BENCH_ATTN")
    env_fused = os.environ.get("OPENDILOCO_TPU_BENCH_FUSED")
    env_remat = os.environ.get("OPENDILOCO_TPU_BENCH_REMAT")
    if env_remat and env_remat.lower() not in ("true", "false", "dots", "dots_all"):
        # fail loudly up front: a typo'd value would otherwise surface only
        # as a swallowed per-variant compile error and a silently-missing pin
        raise SystemExit(
            f"OPENDILOCO_TPU_BENCH_REMAT={env_remat!r}: must be true|false|dots|dots_all"
        )
    env_bs = os.environ.get("OPENDILOCO_TPU_BENCH_BS")
    if env_bs:
        try:
            pin_bs = int(env_bs)  # env pins the GLOBAL batch
        except ValueError:
            raise SystemExit(
                f"OPENDILOCO_TPU_BENCH_BS={env_bs!r}: must be a global "
                "batch size (integer)"
            )
        if pin_bs <= 0 or pin_bs % (accum * n_chips):
            raise SystemExit(
                f"OPENDILOCO_TPU_BENCH_BS={env_bs!r}: global batch {pin_bs} "
                f"must be positive and divisible by accum*chips = "
                f"{accum * n_chips} (each microbatch shards over the "
                "batch axis of the mesh)"
            )
    if env_attn or env_fused or env_remat or env_bs:
        # pinned single variant. Unset knobs default to the HEADLINE config
        # (pallas attention + fused loss) so pinning one lever, e.g. BS=32,
        # measures the configuration the roofline actually models; pass
        # FUSED=0 explicitly for an unfused pin
        remat = {"false": False, "true": True, "dots": "dots", "dots_all": "dots_all"}[
            (env_remat or "true").lower()
        ]
        variants = [
            (
                env_attn or "pallas",
                (env_fused or "1") in ("1", "true"),
                remat,
                pin_bs if env_bs else bs,
            )
        ]
    elif model == "150m":
        # Measured-best first. Round 5's
        # live fine sweep (PUSH40.json) crossed the north-star and kept
        # climbing: the winner is NO remat at all + UNFUSED loss at small
        # per-chip batch under the full layer-scan unroll -- the bs8-15
        # region is one plateau (77-78k, run jitter ~1.5%): bs13 best
        # single row 78,317 tok/s (46.47% MFU), bs8 77,175 (45.79%). The
        # old
        # "remat=False exceeds HBM" AOT verdict was the bs16+fused shape;
        # at bs6-8 unfused the whole step is 6.9-8.3G of 15.75G. Unfused
        # because under the unroll XLA fuses the lm-head matmul itself and
        # the manual fused kernel's slower backward loses
        # (KERNEL_EVIDENCE.json chained timings).
        variants = [
            ("pallas", False, False, 13 * n_chips),
            ("pallas", False, False, 8 * n_chips),
            ("pallas", False, "dots_all", 6 * n_chips),
            ("xla", False, True, bs),
        ]
    else:
        # non-headline models: best-known generic ordering. Round the 1.5x
        # batch to a multiple of accum * n_chips: shard_batch asserts accum
        # divisibility (1b runs accum=4) and each microbatch must shard
        # evenly over the batch axis of a multi-chip mesh
        base = accum * n_chips
        bs_best = max(bs * 3 // 2 // base, 1) * base
        variants = [
            ("pallas", True, "dots", bs_best),
            ("pallas", True, "dots", bs),
            ("pallas", True, True, bs),
            ("xla", False, True, bs),
        ]
        variants = list(dict.fromkeys(variants))  # bs_best may equal bs (1b)

    def _vname(attn, fused, remat, vbs):
        name = f"{attn}{'+fused' if fused else ''}+remat={remat}"
        # PER-CHIP batch in the label (mfu_sweep.py's convention, so
        # BENCH_LIVE.json rows for one physical config carry one number)
        return name if vbs == bs else f"{name}+bs{vbs // n_chips}"

    for attn, fused, remat, vbs in variants:
        name = _vname(attn, fused, remat, vbs)
        try:
            tps = _run_variant(cfg, attn, fused, seq, vbs, accum, remat=remat)
            _RESULTS[name] = tps
            _bank(model, name, tps)
        except Exception as e:  # compile flake / OOM: lose the variant only
            print(f"# variant {name} failed: {e}", flush=True)

    if not _RESULTS:
        sys.exit("bench.py: no variant completed; nothing was measured.")
    _emit()


if __name__ == "__main__":
    main()
