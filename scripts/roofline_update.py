"""Refresh MFU_SWEEP.json's roofline + rows from the PUSH40.json sweep.

The committed roofline section must describe the CURRENT measured-best
config (the push40 fine sweeps move it); this recomputes the compiled-step
cost analysis at that config and folds the push40 rows into MFU_SWEEP.json
so the one artifact stays the authoritative sweep record.
"""

import json
import os
import re
import sys
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

import bench  # noqa: E402
from opendiloco_tpu.obs.mfu import peak_flops  # noqa: E402


def main():
    import jax

    from opendiloco_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    with open(os.path.join(_ROOT, "PUSH40.json")) as f:
        push = json.load(f)
    with open(os.path.join(_ROOT, "MFU_SWEEP.json")) as f:
        sweep = json.load(f)

    rows = [r for r in push["rows"] if "mfu" in r]
    if not rows:
        raise SystemExit("no measured push40 rows")

    # fold push40 rows into the sweep artifact (its row schema), keeping
    # the BEST measurement per config (repeat reps jitter ~±2%; first-wins
    # dedupe was dropping a better later rep and mis-picking the roofline
    # config)
    def _key(bs, remat, seq, blocks, fused):
        return (bs, remat, seq, blocks or "1024,1024", fused)

    index = {}
    for r in sweep["rows"]:
        k = _key(
            r.get("per_chip_bs"),
            str(r.get("remat")),
            r.get("seq"),
            r.get("flash_blocks"),
            "fused" in r.get("attn", "pallas+fused"),
        )
        index[k] = r
    for r in rows:
        m = re.search(r"remat=([a-zA-Z_]+)", r["variant"])
        remat = m.group(1) if m else "dots"
        fused = "+fused" in r["variant"]
        k = _key(r["per_chip_bs"], remat, 1024, r.get("blocks"), fused)
        old = index.get(k)
        if old is not None and old.get("mfu", 0) >= r["mfu"]:
            continue
        row = {
            "accum": 1,
            "attn": "pallas+fused" if fused else "pallas",
            "mfu": r["mfu"],
            "model": "150m",
            "per_chip_bs": r["per_chip_bs"],
            "remat": remat,
            "seq": 1024,
            "tokens_per_sec_per_chip": r["tokens_per_sec_per_chip"],
        }
        if r.get("blocks") and r["blocks"] != "1024,1024":
            row["flash_blocks"] = r["blocks"]
        if old is not None:
            sweep["rows"][sweep["rows"].index(old)] = row
        else:
            sweep["rows"].append(row)
        index[k] = row

    best = max(
        (r for r in sweep["rows"] if r.get("model") == "150m" and "mfu" in r),
        key=lambda r: r["mfu"],
    )
    from opendiloco_tpu.models.hf_io import get_model
    from opendiloco_tpu.parallel.mesh import build_mesh
    from opendiloco_tpu.trainer import InnerTrainer, TrainerConfig

    cfg, _ = get_model("150m")
    n_chips = len(jax.devices())
    remat = {"True": True, "False": False, "dots": "dots", "dots_all": "dots_all"}[
        str(best["remat"])
    ]
    tc = TrainerConfig(
        lr=4e-4, warmup_steps=10, total_steps=1000, precision="bf16-mixed",
        attn_impl="pallas", remat=remat,
        fused_loss="fused" in best.get("attn", "pallas+fused"),
    )
    # cost_analysis counts a scan body once; unroll so FLOPs/bytes are real
    prev = os.environ.get("ODTP_SCAN_UNROLL")
    os.environ["ODTP_SCAN_UNROLL"] = "64"
    try:
        trainer = InnerTrainer(cfg, tc, build_mesh("NO_SHARD"))
        lowered = trainer.lower_abstract(
            best["per_chip_bs"] * n_chips, best["seq"], accum=best.get("accum", 1)
        )
    finally:
        if prev is None:
            os.environ.pop("ODTP_SCAN_UNROLL", None)
        else:
            os.environ["ODTP_SCAN_UNROLL"] = prev
    ca = lowered.compile().cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    flops = float(ca.get("flops", 0.0))
    bytes_hbm = float(ca.get("bytes accessed", 0.0))
    step_s = (
        best["per_chip_bs"] * n_chips * best["seq"]
        / (best["tokens_per_sec_per_chip"] * n_chips)
    )
    sweep["roofline"] = {
        "config": (
            f"150m bs{best['per_chip_bs']} seq{best['seq']} "
            f"remat={best['remat']} attn={best.get('attn', 'pallas+fused')}"
        ),
        "xla_flops_per_step": flops,
        "xla_hbm_bytes_per_step": bytes_hbm,
        "measured_step_s": round(step_s, 5),
        # against the peak of the device the sweep was MEASURED on
        "flops_bound_step_s": round(flops / peak_flops(sweep["device"]), 5),
        "hbm_bound_step_s": round(bytes_hbm / 819e9, 5),
        "note": (
            "step time vs max(flops_bound, hbm_bound) attributes the gap; "
            "if hbm_bound > flops_bound the kernel mix is bandwidth-limited "
            "and more MFU needs bigger batch/seq or fewer remat passes, not "
            "faster matmuls"
        ),
    }
    sweep["updated"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    with open(os.path.join(_ROOT, "MFU_SWEEP.json"), "w") as f:
        json.dump(sweep, f, indent=1, sort_keys=True)
        f.write("\n")
    print(json.dumps(sweep["roofline"], indent=1))


if __name__ == "__main__":
    main()
