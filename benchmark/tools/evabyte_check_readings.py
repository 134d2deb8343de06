#!/usr/bin/env python3
"""The readings that place the EvaByte cell's limit, on the chip:

    python3 benchmark/tools/evabyte_check_readings.py --seed 2147483659

For one seed (a process holds one engine), at the cell's published widths, on
the check's own prompts: the engine's logits against the float32 reference
(what the cell's ``check`` line reports); the reference itself with the
operands of every matrix multiplication rounded to bfloat16 (the precision the
configuration states) and to float8_e4m3fn (the nearest precision below it),
each against the float32 reference; and the reference with every pooled row
readable from its chunk's end on (``visible="chunk"``) against the engine and
against the sound reference. The float8 reading and both chunk-visible
readings have to come out above the driver's limit, the others below it.
Prints one JSON line; needs the TPU.
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
    ap.add_argument("--workload", default="serve-evabyte-complete")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax.numpy as jnp

    from odbench import device, manifest
    from odbench.logits_check import rel_l2, served_rows

    root = os.path.dirname(BENCH_DIR)
    man = manifest.Manifest(root, BENCH_DIR)
    cell = man.cell(args.workload, rehearse=args.rehearse)
    driver = man.driver(cell.traffic["kind"])
    devices, facts, _ = device.require(cell.chips, allow_cpu=args.rehearse)
    device.enable_compile_cache(root)
    report = device.Reporter(facts, cell.name, args.seed)
    _, engine = driver.build(cell, devices, args.seed, report, time.perf_counter())
    prompts, seqs, got = served_rows(cell, engine, args.seed)
    t = time.perf_counter()
    want = driver.reference_rows(cell, engine.params, prompts, seqs)
    out = {"seed": args.seed, "tolerance": {"logits_rel_l2": driver.LOGITS_REL_L2},
           "engine": rel_l2(got, want)[0], "reference_s": time.perf_counter() - t}
    for name, dtype in (("reference_bfloat16", jnp.bfloat16),
                        ("reference_float8_e4m3fn", jnp.float8_e4m3fn)):
        out[name] = rel_l2(driver.reference_rows(cell, engine.params, prompts, seqs, dtype), want)[0]
        print(json.dumps({"what": "progress", "done": name, "s": time.perf_counter() - t}),
              file=sys.stderr, flush=True)
    early = driver.reference_rows(cell, engine.params, prompts, seqs, None, "chunk")
    out["engine_against_chunk_visible_reference"] = rel_l2(got, early)[0]
    out["chunk_visible_reference"] = rel_l2(early, want)[0]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
