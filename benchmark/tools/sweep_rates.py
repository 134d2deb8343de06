#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: ``python3
benchmark/tools/sweep_rates.py --workload <cell> --rates 1,2,3 --seconds 30
--out <file.json>``.

Builds the cell's engine once and offers each rate for ``--seconds`` through
the cell's own driver code (``drivers/open_loop.py:window``), the same traffic
mix at another ``rate_per_s``. One row per rate: offered and completed rate,
backlog at the window's middle and end, both tails. The knee is the rate the
system sustains: the highest completed rate of the sweep. (The backlog at one
instant follows the clumps of a few dozen arrivals; ``growing`` keeps that
reading beside each row.)
"""

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    from odbench import device, manifest, serve_cell, traffic

    man = manifest.Manifest(ROOT, BENCH_DIR)
    cell = man.cell(args.workload, rehearse=args.rehearse)
    devices, facts, _ = device.require(cell.chips, allow_cpu=args.rehearse)
    device.enable_compile_cache(ROOT)
    report = device.Reporter(facts, cell.name, args.seed)
    driver = man.driver("open_loop")

    from opendiloco_tpu.serve import ContinuousBatcher

    t_process = time.perf_counter()
    cfg, engine = serve_cell.build(cell, devices, args.seed, report, t_process)
    batcher = ContinuousBatcher(engine).start()
    rows = []
    try:
        serve_cell.warm_up(engine, batcher, cfg.vocab_size, args.seed)
        for rate in (float(r) for r in args.rates.split(",")):
            mix = dict(cell.traffic, rate_per_s=rate)
            arrivals = traffic.open_loop(mix, args.seconds, cfg.vocab_size, args.seed)
            before = serve_cell.snapshot(engine, batcher)
            reqs_due, backlog = driver.window(batcher, arrivals, args.seconds)
            after = serve_cell.snapshot(engine, batcher)
            tails = serve_cell.tails(reqs_due, mix, report)
            steps = after["decode_steps"] - before["decode_steps"]
            rows.append({
                "rate_per_s": rate, "seconds": args.seconds, **backlog, **tails,
                "decode_step_ms": (after["decode_s"] - before["decode_s"]) / max(1, steps) * 1e3,
                "growing": backlog["backlog_end"] > (backlog["backlog_mid"] or 0),
            })
            report.line("sweep_row", **rows[-1])
    finally:
        batcher.stop()
    out = {
        "cell": cell.name, "device": facts, "seed": args.seed,
        "slots": engine.num_slots, "max_context": engine.max_context,
        "traffic": {k: v for k, v in cell.traffic.items() if k != "rate_per_s"},
        "rule": "knee = the highest completed rate of the sweep (what the system "
                "sustains); the cell runs at about 0.8 x knee",
        "knee_per_s": max(r["completed_rate_per_s"] for r in rows),
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
        f.write("\n")
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
