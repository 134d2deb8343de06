#!/usr/bin/env python3
"""The readings that place the dots3 cell's limits, on the chip:

    python3 benchmark/tools/dots3_check_readings.py --seed 2900000017 --faults

For one seed (a process holds one engine), at the cell's published widths, on
the check's own prompts (``--prompts 1``: the first alone): the engine's logits
against the float32 reference along the engine's chosen rows, row by row (row
0 is the prompt's last token, from its last chunk; the others are decode
steps), and how far apart in the reference's scores the exchanged rows lie;
then, each against the sound float32 reference along *its own* chosen rows, the
reference with the operands of every matrix multiplication rounded to bfloat16
(the precision the configuration states) and to float8_e4m3fn (the nearest
precision below it), and with ``--faults`` the reference with one assumed
equation broken (``reference_dots3``'s faults). Every reading goes through the
check's own comparison (``closed_loop_dots3.verdict``) and carries its ``ok``
and the limits it did not meet. Prints one JSON line and leaves it under
``chiprun_out/``; needs the TPU (``--rehearse``: the cell's small preset, on
the CPU).
"""

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.dirname(BENCH_DIR))

FAULTS = ("no_gate", "no_rescale", "window_minus", "index_rotate_whole", "no_relu")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="serve-dots3-notes")
    ap.add_argument("--seed", type=int, default=2900000017)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--prompts", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax.numpy as jnp
    import numpy as np

    from odbench import device, manifest
    from odbench.logits_check import rel_l2

    root = os.path.dirname(BENCH_DIR)
    man = manifest.Manifest(root, BENCH_DIR)
    cell = man.cell(args.workload, rehearse=args.rehearse)
    cell.options["check"]["prompt_tokens"] = cell.options["check"]["prompt_tokens"][: args.prompts]
    driver = man.driver(cell.traffic["kind"])
    devices, facts, _ = device.require(cell.chips, allow_cpu=args.rehearse)
    device.enable_compile_cache(root)
    report = device.Reporter(facts, cell.name, args.seed)
    _, engine = driver.build(cell, devices, args.seed, report, time.perf_counter())
    prompts, seqs, got, choices = driver.served_rows_and_choices(cell, engine, args.seed)
    t = time.perf_counter()
    topk = cell.config["index_topk"]

    def reading(rel, differing, distance):
        chose = driver.exchanged(cell, differing, distance)
        ok, _, failed = driver.verdict(rel, chose)
        return {"ok": ok, "limits_not_met": failed, "logits_rel_l2": rel, **chose}

    want, differing, distance, _ = driver.reference_rows(cell, engine.params, prompts, seqs, choices)
    by_row = [
        [float(np.linalg.norm(g - w) / np.linalg.norm(w)) for g, w in zip(have, ref)]
        for have, ref in zip(got, want)
    ]
    out = {"seed": args.seed, "prompts": [len(p) for p in prompts],
           "reference_s": time.perf_counter() - t,
           "engine": {**reading(rel_l2(got, want)[0], differing, distance), "rel_l2_by_row": by_row}}
    print(json.dumps({"what": "progress", "engine": out["engine"]}), file=sys.stderr, flush=True)

    variants = [("reference_bfloat16", jnp.bfloat16, ()), ("reference_float8_e4m3fn", jnp.float8_e4m3fn, ())]
    if args.faults:
        variants += [(f"fault_{name}", None, (name,)) for name in FAULTS]
    for name, dtype, faults in variants:
        # the variant walks alone and chooses for itself; then the sound reference
        # follows the variant's sets: its logits against the variant's, and how far
        # apart the exchanged rows lie in the sound scores
        rows, _, _, own = driver.reference_rows(cell, engine.params, prompts, seqs, None, dtype, faults)
        sets = [driver.sets_as_rows(chosen, topk) for chosen in own]
        along, differing, distance, _ = driver.reference_rows(cell, engine.params, prompts, seqs, sets)
        out[name] = reading(rel_l2(rows, along)[0], differing, distance)
        print(json.dumps({"what": "progress", "done": name, "s": time.perf_counter() - t,
                          "reading": out[name]}), file=sys.stderr, flush=True)
    os.makedirs(os.path.join(root, "chiprun_out"), exist_ok=True)
    with open(os.path.join(root, "chiprun_out", f"dots3_check_readings.{args.seed}.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
