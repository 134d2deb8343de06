"""MFU sweep on the real chip: batch scaling x remat x model configs.

Writes MFU_SWEEP.json at the repo root incrementally (a run cut short
keeps whatever finished) and logs every measurement into BENCH_LIVE.json
via bench._bank.

Also records the compiled step's cost analysis (FLOPs, HBM bytes) for the
best 150m config, giving a roofline attribution of where non-MXU time goes
(the VERDICT r3 ask: a table with >=1 config at >=40% MFU, or a measured
explanation of the ceiling).

North-star: BASELINE.md >=40% inner-loop MFU on llama-150m.
"""

import json
import os
import sys
import threading
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import bench  # noqa: E402  (repo-root headline bench; reuses its helpers)

_OUT = os.path.join(_ROOT, "MFU_SWEEP.json")
_DOC: dict = {"rows": [], "started": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}


def _flush():
    _DOC["updated"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    with open(_OUT, "w") as f:
        json.dump(_DOC, f, indent=1, sort_keys=True)
        f.write("\n")


def _watchdog(seconds: float):
    def fire():
        _DOC["aborted"] = f"watchdog after {seconds}s (accelerator unresponsive)"
        _flush()
        os._exit(0 if _DOC["rows"] else 4)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t


def main():
    import jax

    from opendiloco_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    wd = _watchdog(float(os.environ.get("MFU_SWEEP_TIMEOUT", "1700")))

    from opendiloco_tpu.models.hf_io import get_model

    from opendiloco_tpu.obs.mfu import peak_flops
    from opendiloco_tpu.utils.device import device_stamp

    _DOC["device"] = jax.devices()[0].device_kind
    _DOC.update(device_stamp())
    peak = peak_flops(jax.devices()[0].device_kind)
    n_chips = len(jax.devices())
    _flush()

    # (model, seq, per-chip bs, accum, remat, fused) -- measured-best first
    # so a short window still refreshes the headline; then the levers.
    # Round 5's fine sweeps (PUSH40.json) moved the winner twice: the
    # headline is now NO remat + UNFUSED loss at small per-chip batch
    # under the full layer-scan unroll (bs8 77.2k tok/s, 45.8% MFU; the
    # old remat=False OOM verdict was the bs16+fused shape). The 1b
    # single-chip configs still exceed HBM at every remat (AOT-proved) --
    # a live window must not re-discover those OOMs.
    plan = [
        ("150m", 1024, 8, 1, False, False),
        ("150m", 1024, 10, 1, False, False),
        ("150m", 1024, 6, 1, "dots_all", False),
        ("150m", 1024, 24, 1, "dots", True),
        ("150m", 1024, 16, 1, True, True),
        ("150m", 2048, 8, 1, True, True),
        ("150m", 2048, 16, 1, True, True),
    ]
    cfgs = {}
    for model, seq, bs, accum, remat, fused in plan:
        if model not in cfgs:
            cfgs[model] = get_model(model)[0]
        cfg = cfgs[model]
        bench._CTX.update(
            model=model,
            chips=n_chips,
            device=_DOC["device"],
            peak=peak,
            flops_per_token=bench.model_flops_per_token(cfg, seq),
        )
        name = f"{model} seq{seq} bs{bs} accum{accum} remat={remat}"
        try:
            tps = bench._run_variant(
                cfg, "pallas", fused, seq, bs * n_chips, accum, remat=remat
            )
            mfu = tps * bench._CTX["flops_per_token"] / peak
            attn_label = "pallas+fused" if fused else "pallas"
            row = {
                "model": model, "seq": seq, "per_chip_bs": bs, "accum": accum,
                "remat": str(remat), "attn": attn_label,
                "tokens_per_sec_per_chip": round(tps, 1),
                "mfu": round(mfu, 4),
            }
            _DOC["rows"].append(row)
            bench._bank(model, f"{attn_label}+remat={remat}+bs{bs}+seq{seq}", tps)
            print(f"# {name}: {tps:.0f} tok/s/chip, {mfu:.1%} MFU", flush=True)
        except Exception as e:
            _DOC["rows"].append({"config": name, "error": f"{type(e).__name__}: {e}"})
            print(f"# {name} failed: {e}", flush=True)
        _flush()

    # roofline attribution for the measured-best 150m row: compiled-step
    # cost analysis says whether the ceiling is FLOPs or HBM bytes
    try:
        best = max(
            (r for r in _DOC["rows"] if r.get("model") == "150m" and "mfu" in r),
            key=lambda r: r["mfu"],
            default=None,
        )
        if best is not None:

            from opendiloco_tpu.parallel.mesh import build_mesh
            from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

            cfg = cfgs["150m"]
            remat = {"True": True, "False": False, "dots": "dots", "dots_all": "dots_all"}[best["remat"]]
            tc = TrainerConfig(
                lr=4e-4, warmup_steps=10, total_steps=1000,
                precision="bf16-mixed", attn_impl="pallas", remat=remat,
                fused_loss="fused" in best.get("attn", "pallas+fused"),
            )
            # unroll the layer scan for the cost compile: cost_analysis
            # counts a scan body ONCE, so the looped build under-reports
            # FLOPs/bytes ~n_layers-fold (round 5's first live window banked
            # a roofline with a phantom 10x measured-vs-bound gap this way;
            # same fix as scripts/aot_roofline.py). Save/restore rather than
            # pop: an operator-set ODTP_SCAN_UNROLL must survive for the
            # block-sweep runs below.
            prev_unroll = os.environ.get("ODTP_SCAN_UNROLL")
            os.environ["ODTP_SCAN_UNROLL"] = "64"
            try:
                trainer = InnerTrainer(cfg, tc, build_mesh("NO_SHARD"))
                lowered = trainer.lower_abstract(
                    best["per_chip_bs"] * n_chips, best["seq"], accum=best["accum"]
                )
            finally:
                if prev_unroll is None:
                    os.environ.pop("ODTP_SCAN_UNROLL", None)
                else:
                    os.environ["ODTP_SCAN_UNROLL"] = prev_unroll
            ca = lowered.compile().cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
            flops = float(ca.get("flops", 0.0))
            bytes_hbm = float(ca.get("bytes accessed", 0.0))
            step_s = (
                best["per_chip_bs"] * n_chips * best["seq"]
                / (best["tokens_per_sec_per_chip"] * n_chips)
            )
            _DOC["roofline"] = {
                "config": f"150m bs{best['per_chip_bs']} seq{best['seq']} remat={best['remat']}",
                "xla_flops_per_step": flops,
                "xla_hbm_bytes_per_step": bytes_hbm,
                "measured_step_s": round(step_s, 5),
                "flops_bound_step_s": round(flops / peak, 5),
                # v5e HBM ~819 GB/s
                "hbm_bound_step_s": round(bytes_hbm / 819e9, 5),
                "note": (
                    "step time vs max(flops_bound, hbm_bound) attributes the "
                    "gap; if hbm_bound > flops_bound the kernel mix is "
                    "bandwidth-limited and more MFU needs bigger batch/seq "
                    "or fewer remat passes, not faster matmuls"
                ),
            }
            _flush()
    except Exception as e:
        _DOC["roofline_error"] = f"{type(e).__name__}: {e}"
        _flush()

    # flash block-size sweep on the best 150m row (the 1024x1024 defaults
    # were chosen by a round-2 live sweep; this records the neighborhood so
    # the defaults are evidence-backed, VERDICT r2 "What's weak" #1)
    try:
        best = max(
            (r for r in _DOC["rows"] if r.get("model") == "150m" and "mfu" in r),
            key=lambda r: r["mfu"],
            default=None,
        )
        if best is not None:
            # _CTX["flops_per_token"] is whatever the LAST plan row set (the
            # seq-2048 value in round 5's first live window, which inflated
            # these rows' MFU by seq2048/seq1024 ~ 6.6%) -- recompute for the
            # best row's seq AND push it back into _CTX so bench._bank writes
            # the same corrected MFU into BENCH_LIVE.json rows
            fpt = bench.model_flops_per_token(cfgs["150m"], best["seq"])
            bench._CTX["flops_per_token"] = fpt
            best_fused = "fused" in best.get("attn", "pallas+fused")
            best_attn = "pallas+fused" if best_fused else "pallas"
            for bq, bk in [(512, 512), (512, 1024), (1024, 512)]:
                os.environ["OPENDILOCO_TPU_FLASH_BLOCKS"] = f"{bq},{bk}"
                name = f"150m blocks={bq}x{bk}"
                try:
                    tps = bench._run_variant(
                        cfgs["150m"], "pallas", best_fused, best["seq"],
                        best["per_chip_bs"] * n_chips, best["accum"],
                        remat={"True": True, "False": False, "dots": "dots",
                               "dots_all": "dots_all"}[best["remat"]],
                    )
                    mfu = tps * fpt / peak
                    _DOC["rows"].append({
                        "model": "150m", "seq": best["seq"],
                        "per_chip_bs": best["per_chip_bs"],
                        "accum": best["accum"], "remat": best["remat"],
                        "attn": f"{best_attn} blocks={bq}x{bk}",
                        "tokens_per_sec_per_chip": round(tps, 1),
                        "mfu": round(mfu, 4),
                    })
                    bench._bank("150m", f"{best_attn}+blocks={bq}x{bk}", tps)
                    print(f"# {name}: {tps:.0f} tok/s/chip, {mfu:.1%}", flush=True)
                except Exception as e:
                    _DOC["rows"].append(
                        {"config": name, "error": f"{type(e).__name__}: {e}"}
                    )
                _flush()
            os.environ.pop("OPENDILOCO_TPU_FLASH_BLOCKS", None)
    except Exception as e:
        _DOC["block_sweep_error"] = f"{type(e).__name__}: {e}"
        _flush()

    wd.cancel()
    _DOC["complete"] = True
    _flush()
    print(json.dumps(_DOC, indent=1, sort_keys=True))


if __name__ == "__main__":
    main()
