#!/usr/bin/env python3
"""The readings that place the ZAYA1 cell's two limits, on the chip:

    python3 benchmark/tools/zaya_check_readings.py --seed 2147483659 [--faults]

For one seed (a process holds one engine), at the cell's published widths, on
the check's own prompts: the engine's logits against the float32 reference
along the engine's expert choices, and how the two chose (what the cell's
``check`` line reports); then the reference itself with the operands of every
matrix multiplication rounded to bfloat16 (the precision the configuration
states) and to float8_e4m3fn (the nearest precision below it), each taken as
the program would be: its logits against the float32 reference along *its*
choices, and its differing choices' largest float32 margin. The float8 reading
has to come out above one of the driver's limits and the others below both.
``--faults`` adds the reference with one equation broken and bfloat16
operands, read the same way. Prints one JSON line; needs the TPU.
"""

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.dirname(BENCH_DIR))

FAULTS = ("no_conv0", "no_conv1_back", "no_mean", "no_temp", "own_values_only", "full_rotary",
          "no_carry", "bias_weighed", "no_residual_scaling")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="serve-zaya1-reason")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax.numpy as jnp

    from odbench import device, manifest

    root = os.path.dirname(BENCH_DIR)
    man = manifest.Manifest(root, BENCH_DIR)
    cell = man.cell(args.workload, rehearse=args.rehearse)
    driver = man.driver(cell.traffic["kind"])
    devices, facts, _ = device.require(cell.chips, allow_cpu=args.rehearse)
    device.enable_compile_cache(root)
    report = device.Reporter(facts, cell.name, args.seed)
    _, engine = driver.build(cell, devices, args.seed, report, time.perf_counter())
    prompts, seqs, got, choices = driver.served_rows_and_choices(cell, engine, args.seed)
    t = time.perf_counter()

    def reading(rows, chosen):
        """``rows`` and ``chosen`` as a program's: against float32 along them."""
        want, own, margins = driver.reference_rows(cell, engine.params, prompts, seqs, chosen)
        return {"logits_rel_l2": driver.rel_l2(rows, want)[0], **driver.differing(chosen, own, margins)}

    out = {"seed": args.seed, "tolerance": {"logits_rel_l2": driver.LOGITS_REL_L2,
                                           "largest_differing_margin": driver.CHOICE_MARGIN},
           "engine": reading(got, choices)}
    out["reference_s"] = time.perf_counter() - t
    low = [("reference_bfloat16", jnp.bfloat16, ()), ("reference_float8_e4m3fn", jnp.float8_e4m3fn, ())]
    low += [("fault_" + f, jnp.bfloat16, (f,)) for f in FAULTS] if args.faults else []
    for name, dtype, faults in low:
        rows, own, _ = driver.reference_rows(cell, engine.params, prompts, seqs, None, dtype, faults)
        out[name] = reading(rows, own)
        print(json.dumps({"what": "progress", "done": name, "s": time.perf_counter() - t}),
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
