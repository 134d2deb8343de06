"""Offline AOT roofline: bound the remat/batch perf levers without a TPU.

Chip time is budgeted, so the remat=true|dots|false and batch-size levers
coded into bench.py are bounded before any of it is spent. This script
compiles the REAL training step — the same ``InnerTrainer._train_step`` bench.py times —
deviceless for a v5e target via ``jax.experimental.topologies`` (PJRT
topology AOT), and reads the compiled executable's own cost model:

  - executed FLOPs (includes remat recompute) and HBM bytes accessed
    from ``compiled.cost_analysis()``
  - peak memory footprint from ``compiled.memory_analysis()`` (does the
    variant even fit a 16 GiB chip?)
  - a roofline step-time bound  t >= max(flops/peak_mxu, bytes/peak_bw)
    and the predicted-MFU ceiling  model_flops / (t * peak_mxu)

These are CEILINGS from XLA's cost model at nominal peak rates (197 bf16
TFLOP/s, 819 GB/s HBM for v5e-1), not measurements — but they are
machine-generated from the compiled HLO for the exact bench shapes, which
turns "levers coded" into "levers bounded": they rank the variants and say
which are compute- vs bandwidth-limited and which OOM, so chip minutes
go to the predicted winner first. Nothing here runs on a device: the
artifact says so (``"platform": "deviceless"``).

Writes AOT_ROOFLINE.json (incrementally — a crash keeps finished rows).
"""

from __future__ import annotations

import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "AOT_ROOFLINE.json")

V5E_HBM_BW = 819e9  # bytes/s
V5E_HBM_BYTES = 16 * 1024**3


def build_rows():
    rows = []
    # (model, seq, per-chip bs, accum, remat, fused) — bench.py's exact
    # shapes (150m: seq 1024 bs 16; 1b: bs 4 x accum 4) plus the batch
    # levers the sweep would try on hardware
    for model, seq, shapes in (
        ("150m", 1024, [(16, 1), (32, 1)]),
        ("1b", 1024, [(4, 4), (8, 2)]),
    ):
        for bs, accum in shapes:
            for remat in (True, "dots", False):
                rows.append((model, seq, bs, accum, remat, True))
    # round 5's live fine sweep moved the winning regime to small batch
    # with the loss UNFUSED; the original fused bs16/bs32 OOM verdicts for
    # remat=False do NOT transfer there (measured live: bs8 unfused
    # no-remat is the 45.8%-MFU headline). Bound those shapes too.
    for bs in (6, 8, 10):
        for remat in (False, "dots_all"):
            rows.append(("150m", 1024, bs, 1, remat, False))
    return rows


def flush(doc):
    tmp = OUT + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, OUT)


def main():
    # deviceless by design: the compiler targets a described v5e, and no
    # attached accelerator is wanted even where one exists
    os.environ["JAX_PLATFORMS"] = "cpu"
    # unroll the layer scan so the compiled HLO exposes EVERY layer's
    # FLOPs/bytes to cost_analysis (a while-loop body is counted once;
    # with the scan in place the 150m step reported 12x fewer FLOPs than
    # the analytic count). 64 covers every zoo config's depth.
    os.environ["ODTP_SCAN_UNROLL"] = "64"
    from jax.experimental import topologies

    from bench import model_flops_per_token  # the one MFU accounting
    from opendiloco_tpu.obs.mfu import peak_flops
    from opendiloco_tpu.models.hf_io import get_model
    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

    # resume: completed rows survive re-runs (each compile costs minutes on
    # this box; a re-run only fills what's missing, e.g. the multichip
    # section added after the single-chip sweep was banked)
    existing = None
    if os.path.exists(OUT):
        try:
            with open(OUT) as f:
                existing = json.load(f)
        except ValueError:
            existing = None
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        raise SystemExit(f"topology unavailable: {type(e).__name__}: {e}")
    target_kind = topo.devices[0].device_kind
    V5E_PEAK_FLOPS = peak_flops(target_kind)
    doc = existing or {
        "device": "v5e (deviceless PJRT topology AOT)",
        # nothing ran: every row is a compile for the described target
        "platform": "deviceless",
        "device_kind": target_kind,
        "device_count": 0,
        "peak_flops": V5E_PEAK_FLOPS,
        "hbm_bw": V5E_HBM_BW,
        "hbm_bytes": V5E_HBM_BYTES,
        "generated": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "note": (
            "roofline CEILINGS from the compiled HLO's cost model at nominal "
            "peak rates, not measurements; ranks the bench.py variants and "
            "flags OOM so chip minutes go to the predicted winner. "
            "Caveat: the unrolled build used for cost_analysis lets XLA CSE "
            "part of the remat recompute (recompute_factor < 1 means the "
            "counted FLOPs approximate the no-remat ideal); the memory "
            "verdicts compile the program the trainer actually runs "
            "(full unroll for dense <=16-layer stacks, looped otherwise), "
            "so fits_hbm/oom are faithful to the runtime default"
        ),
        "rows": [],
    }
    doc.pop("error", None)  # marker of an aborted run under older code
    devices = list(topo.devices)[:1]  # single-chip bench shape

    cfg_cache = {}
    # errored rows retry (and are dropped so a re-run can't leave a stale
    # FAILED row next to its success); OOM verdicts are results and stay
    doc["rows"] = [r for r in doc.get("rows", []) if "error" not in r]
    have = {
        (
            r["model"], r["per_chip_batch"], r["accum"], r["remat"],
            "fused" in r.get("attn", "pallas+fused"),
        )
        for r in doc["rows"]
    }
    for model, seq, bs, accum, remat, fused in build_rows():
        if (model, bs, accum, str(remat), fused) in have:
            continue
        name = f"{model} seq{seq} bs{bs} accum{accum} remat={remat}"
        t0 = time.time()
        row = {
            "model": model,
            "seq": seq,
            "per_chip_batch": bs,
            "accum": accum,
            "remat": str(remat),
            "attn": "pallas+fused" if fused else "pallas",
        }
        try:
            if model not in cfg_cache:
                cfg_cache[model] = get_model(model)[0]
            cfg = cfg_cache[model]
            tc = TrainerConfig(
                lr=4e-4, warmup_steps=10, total_steps=1000,
                precision="bf16-mixed", attn_impl="pallas", remat=remat,
                fused_loss=fused,
            )
            assert bs % accum == 0, (bs, accum)

            def compile_step():
                # fresh trainer per compile: jit caches lowerings, and the
                # two compiles here must see different ODTP_SCAN_UNROLL
                trainer = InnerTrainer(
                    cfg, tc, build_mesh("NO_SHARD", devices=devices)
                )
                return trainer.lower_abstract(bs, seq, accum=accum).compile()

            # memory footprint from the program that actually runs: the
            # trainer's auto default FULLY unrolls dense stacks <= 16
            # layers on TPU (looped otherwise) -- round 5 found the looped
            # build can mis-verdict the unrolled runtime in both
            # directions (bs10 no-remat "doesn't fit" looped yet runs
            # live). FLOPs/bytes always come from the unrolled build,
            # where cost_analysis sees every layer instead of one loop
            # body
            runtime_unroll = (
                cfg.num_hidden_layers
                if (not cfg.num_experts and cfg.num_hidden_layers <= 16)
                else 1
            )
            os.environ["ODTP_SCAN_UNROLL"] = str(runtime_unroll)
            mem = compile_step().memory_analysis()
            os.environ["ODTP_SCAN_UNROLL"] = "64"
            ca = compile_step().cost_analysis()

            flops = float(ca.get("flops", 0.0))
            byts = float(ca.get("bytes accessed", 0.0))
            tokens = bs * seq
            model_flops = model_flops_per_token(cfg, seq) * tokens
            t_compute = flops / V5E_PEAK_FLOPS
            t_mem = byts / V5E_HBM_BW
            t_pred = max(t_compute, t_mem)
            peak_bytes = (
                mem.argument_size_in_bytes
                + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
                - mem.alias_size_in_bytes
            )
            row.update(
                tokens_per_step=tokens,
                executed_flops=flops,
                model_flops=model_flops,
                recompute_factor=round(flops / model_flops, 3) if model_flops else None,
                bytes_accessed=byts,
                t_compute_s=round(t_compute, 6),
                t_mem_s=round(t_mem, 6),
                bound="compute" if t_compute >= t_mem else "memory",
                predicted_tokens_per_s=round(tokens / t_pred, 1),
                predicted_mfu_ceiling=round(
                    model_flops / (t_pred * V5E_PEAK_FLOPS), 4
                ),
                peak_memory_bytes=int(peak_bytes),
                fits_hbm=bool(peak_bytes < 0.95 * V5E_HBM_BYTES),
                temp_bytes=int(mem.temp_size_in_bytes),
                compile_s=round(time.time() - t0, 1),
            )
            print(
                f"{name}: mfu_ceiling={row['predicted_mfu_ceiling']} "
                f"bound={row['bound']} fits={row['fits_hbm']} "
                f"recompute={row['recompute_factor']}",
                flush=True,
            )
        except Exception as e:
            msg = f"{type(e).__name__}: {str(e)[:400]}"
            if "RESOURCE_EXHAUSTED" in msg:
                # a first-class result, not a failure: this variant cannot
                # run on a 16 GiB chip -- don't spend chip minutes on it
                row["fits_hbm"] = False
                row["oom"] = msg
                print(f"{name}: does NOT fit HBM", flush=True)
            else:
                row["error"] = msg
                print(f"{name}: FAILED {msg}", flush=True)
                traceback.print_exc()
        doc["rows"].append(row)
        flush(doc)

    ok = [r for r in doc["rows"] if r.get("fits_hbm")]
    if ok:
        best = max(ok, key=lambda r: r["predicted_mfu_ceiling"])
        doc["predicted_best"] = {
            k: best[k]
            for k in (
                "model", "per_chip_batch", "accum", "remat",
                "predicted_mfu_ceiling", "bound",
            )
        }
    flush(doc)

    # ---- multichip: the 1b deployment shape ---------------------------
    # single-chip 1b is infeasible (rows above); prove the OTHER half of
    # that story deviceless: FULL_SHARD over 4 virtual v5e chips — does
    # the per-chip footprint fit, and what does the cost model predict?
    # (The reference's 1b recipe is likewise a sharded multi-accelerator
    # worker.) Collective ICI traffic is not modeled by the HBM roofline;
    # these rows bound memory + per-chip math only.
    doc["multichip_rows"] = [
        r for r in doc.get("multichip_rows", []) if "error" not in r
    ]
    have_mc = {
        # fused isn't a row field: rows record it only through the attn
        # label, so derive it the same way the writer encodes it
        (r["model"], r["per_chip_batch"], r["accum"], r["remat"],
         r.get("attn") == "pallas+fused")
        for r in doc["multichip_rows"]
    }
    for model, seq, bs_chip, accum, remat, fused in (
        ("1b", 1024, 4, 4, True, True),
        ("1b", 1024, 8, 2, True, True),
        ("150m", 1024, 16, 1, True, False),
    ):
        if (model, bs_chip, accum, str(remat), fused) in have_mc:
            continue
        name = f"mc4 {model} seq{seq} bs{bs_chip}/chip accum{accum} remat={remat}"
        t0 = time.time()
        row = {
            "model": model, "seq": seq, "chips": 4,
            "strategy": "FULL_SHARD", "per_chip_batch": bs_chip,
            "accum": accum, "remat": str(remat),
            "attn": "pallas+fused" if fused else "pallas",
        }
        try:
            if model not in cfg_cache:
                cfg_cache[model] = get_model(model)[0]
            cfg = cfg_cache[model]
            tc = TrainerConfig(
                lr=4e-4, warmup_steps=10, total_steps=1000,
                precision="bf16-mixed", attn_impl="pallas", remat=remat,
                fused_loss=fused,
            )
            mc_devices = list(topo.devices)[:4]
            bs = bs_chip * 4

            def compile_mc():
                trainer = InnerTrainer(
                    cfg, tc, build_mesh("FULL_SHARD", devices=mc_devices)
                )
                return trainer.lower_abstract(bs, seq, accum=accum).compile()

            # same runtime-unroll memory basis as the single-chip rows
            runtime_unroll = (
                cfg.num_hidden_layers
                if (not cfg.num_experts and cfg.num_hidden_layers <= 16)
                else 1
            )
            os.environ["ODTP_SCAN_UNROLL"] = str(runtime_unroll)
            mem = compile_mc().memory_analysis()
            os.environ["ODTP_SCAN_UNROLL"] = "64"
            ca = compile_mc().cost_analysis()
            peak_bytes = (
                mem.argument_size_in_bytes
                + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
                - mem.alias_size_in_bytes
            )
            tokens = bs * seq
            flops = float(ca.get("flops", 0.0))
            byts = float(ca.get("bytes accessed", 0.0))
            row.update(
                tokens_per_step=tokens,
                # per-DEVICE program numbers (SPMD cost analysis scopes one
                # module): useful relatively, NOT an MFU claim -- the
                # headline of these rows is the memory verdict
                executed_flops_per_device=flops,
                bytes_accessed_per_device=byts,
                peak_memory_bytes_per_chip=int(peak_bytes),
                fits_hbm=bool(peak_bytes < 0.95 * V5E_HBM_BYTES),
                compile_s=round(time.time() - t0, 1),
            )
            print(
                f"{name}: fits={row['fits_hbm']} "
                f"peak/chip={peak_bytes / 2**30:.2f}G",
                flush=True,
            )
        except Exception as e:
            msg = f"{type(e).__name__}: {str(e)[:400]}"
            if "RESOURCE_EXHAUSTED" in msg:
                row["fits_hbm"] = False
                row["oom"] = msg
                print(f"{name}: does NOT fit HBM", flush=True)
            else:
                row["error"] = msg
                print(f"{name}: FAILED {msg}", flush=True)
        doc["multichip_rows"].append(row)
        flush(doc)
    print("wrote", OUT, flush=True)


if __name__ == "__main__":
    main()
