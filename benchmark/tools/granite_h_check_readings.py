#!/usr/bin/env python3
"""The readings that place the granite-4.0-h cell's tolerance, on the chip:

    python3 benchmark/tools/granite_h_check_readings.py --seed 2147483659

For one seed (a process holds one engine: the chip has no room for a second),
at the cell's published widths, on the check's own prompts: the engine's
logits against the float32 reference (what the cell's ``check`` line
reports), and the reference itself with the operands of every matrix
multiplication rounded to bfloat16 (the precision the configuration states)
and to float8_e4m3fn (the nearest precision below it), each against float32.
The float8 reading has to come out above the driver's ``LOGITS_REL_L2`` and
the others below it. Prints one JSON line; needs the TPU.
"""

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.dirname(BENCH_DIR))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="serve-granite-h-docqa")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax.numpy as jnp

    from odbench import device, manifest, serve_cell

    root = os.path.dirname(BENCH_DIR)
    man = manifest.Manifest(root, BENCH_DIR)
    cell = man.cell(args.workload, rehearse=args.rehearse)
    driver = man.driver(cell.traffic["kind"])
    devices, facts, _ = device.require(cell.chips, allow_cpu=args.rehearse)
    device.enable_compile_cache(root)
    report = device.Reporter(facts, cell.name, args.seed)
    _, engine = serve_cell.build(cell, devices, args.seed, report, time.perf_counter())
    prompts, seqs, got = driver.served_rows(cell, engine, args.seed)
    t = time.perf_counter()
    want = driver.reference_rows(cell, engine.params, prompts, seqs)
    out = {"seed": args.seed, "tolerance": driver.LOGITS_REL_L2,
           "engine": driver.rel_l2(got, want)[0], "reference_s": time.perf_counter() - t}
    for name, dtype in (("reference_bfloat16", jnp.bfloat16),
                        ("reference_float8_e4m3fn", jnp.float8_e4m3fn)):
        low = driver.reference_rows(cell, engine.params, prompts, seqs, operands=dtype)
        out[name] = driver.rel_l2(low, want)[0]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
