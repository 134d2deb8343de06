#!/usr/bin/env python3
"""The readings that place the Solar-Open2 cell's limit, on the chip:

    python3 benchmark/tools/solar2_check_readings.py --seed 2964000017 --faults

For one seed (a process holds one engine), at the cell's published widths, on
the check's own prompts (``--prompts 1``: the first alone): the engine's logits
against the float32 reference, row by row (row 0 is the prompt's last token,
from its last chunk; the others are decode steps); then, each against the sound
float32 reference, the reference with the operands of every matrix
multiplication rounded to bfloat16 (the precision the configuration states) and
to float8_e4m3fn (the nearest precision below it), and with ``--faults`` the
reference with one assumed equation broken (``reference_solar2``'s faults).
Every reading goes through the check's own comparison
(``closed_loop_solar2.verdict``) and carries its ``ok``. Prints one JSON line
and leaves it under ``chiprun_out/``; needs the TPU (``--rehearse``: the cell's
small preset, on the CPU).
"""

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.dirname(BENCH_DIR))

FAULTS = (
    "bf16_state", "beta_one", "scalar_decay", "decay_after", "no_delta", "zero_state_chunks",
    "zero_tail_chunks", "no_l2", "no_q_scale", "no_silu", "norm_all", "no_kda_gate",
    "no_gqa_gate", "headwise_gate", "gqa_rope", "softmax_router", "topk_among_held",
    "bias_weighed", "no_shared",
)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="serve-solar2-reason")
    ap.add_argument("--seed", type=int, default=2964000017)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--prompts", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax.numpy as jnp
    import numpy as np

    from odbench import device, manifest, reference_solar2
    from odbench.logits_check import rel_l2, served_rows

    root = os.path.dirname(BENCH_DIR)
    man = manifest.Manifest(root, BENCH_DIR)
    cell = man.cell(args.workload, rehearse=args.rehearse)
    cell.options["check"]["prompt_tokens"] = cell.options["check"]["prompt_tokens"][: args.prompts]
    driver = man.driver(cell.traffic["kind"])
    devices, facts, _ = device.require(cell.chips, allow_cpu=args.rehearse)
    device.enable_compile_cache(root)
    report = device.Reporter(facts, cell.name, args.seed)
    _, engine = driver.build(cell, devices, args.seed, report, time.perf_counter())
    # the chunk the two ``zero_*_chunks`` faults forget at is the engine's
    reference_solar2.CHUNK = engine.cfg.q_chunk_size
    out = {"seed": args.seed}
    prompts, seqs, got = served_rows(cell, engine, args.seed)
    t = time.perf_counter()

    def reading(rows, against):
        rel = rel_l2(rows, against)[0]
        ok, _, failed = driver.verdict(rel)
        return {"ok": ok, "limits_not_met": failed, "logits_rel_l2": rel}

    want = driver.reference_rows(cell, engine.params, prompts, seqs)
    by_row = [
        [float(np.linalg.norm(g - w) / np.linalg.norm(w)) for g, w in zip(have, ref)]
        for have, ref in zip(got, want)
    ]
    out.update(prompts=[len(p) for p in prompts], reference_s=time.perf_counter() - t,
               engine={**reading(got, want), "rel_l2_by_row": by_row})
    print(json.dumps({"what": "progress", "engine": out["engine"]}), file=sys.stderr, flush=True)
    variants = [("reference_bfloat16", jnp.bfloat16, ()), ("reference_float8_e4m3fn", jnp.float8_e4m3fn, ())]
    if args.faults:
        variants += [(f"fault_{name}", None, (name,)) for name in FAULTS]
    for name, dtype, faults in variants:
        rows = driver.reference_rows(cell, engine.params, prompts, seqs, dtype, faults)
        out[name] = reading(rows, want)
        print(json.dumps({"what": "progress", "done": name, "s": time.perf_counter() - t,
                          "reading": out[name]}), file=sys.stderr, flush=True)
    os.makedirs(os.path.join(root, "chiprun_out"), exist_ok=True)
    with open(os.path.join(root, "chiprun_out", f"solar2_check_readings.{args.seed}.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
