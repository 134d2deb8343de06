#!/usr/bin/env python3
"""The readings that place the MiniCPM-SALA cell's limits, on the chip:

    python3 benchmark/tools/sala_check_readings.py --seed 2461000007 --faults

For one seed (a process holds one engine), at the cell's published widths, on
the check's own prompts (``--prompts 1``: the first alone): the engine's logits
against the float32 reference along the engine's chosen blocks, row by row (row
0 is the prompt's last token, from its last chunk; the others are decode
steps), and the exchange distances of its choices by prompt, by part and by
layer; then, each against the sound float32 reference, the reference with the
operands of every matrix multiplication rounded to bfloat16 (the precision the
configuration states) and to float8_e4m3fn (the nearest precision below it),
and with ``--faults`` the reference with one assumed equation broken
(``reference_sala``'s faults): a variant walks its own chosen blocks, and the
sound reference is walked along them, so that both the logits and the
exchange distances of a wrong selection are read as the check would read them
of an engine that had the fault. Every reading goes through the check's own
comparison (``closed_loop_sala.verdict``) and carries its ``ok``. Prints one
JSON line and leaves it under ``chiprun_out/``; needs the TPU (``--rehearse``:
the cell's small preset, on the CPU).
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
    "bf16_state", "decay_next_layer", "zero_state_chunks", "no_scale", "no_lightning_rope",
    "sparse_rope", "norm_after_rope", "norm_per_head", "gate_before_norm", "no_out_gate",
    "no_attn_gate", "depth_cut", "no_head_scale", "no_emb_scale", "block_means",
    "topk_beside_forced", "first_blocks", "early_windows",
)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="serve-sala-longdoc")
    ap.add_argument("--seed", type=int, default=2461000007)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--only", default="", help="comma-separated fault names (default: all)")
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
    engine.keep_row_choices()
    out = {"seed": args.seed}
    prompts, seqs, got, choices = driver.served_rows_and_choices(cell, engine, args.seed)
    t = time.perf_counter()

    def reading(rows, against, differing, distance):
        rel = rel_l2(rows, against)[0]
        chose = driver.exchanged(cell, differing, distance)
        ok, _, failed = driver.verdict(rel, chose)
        return {"ok": ok, "limits_not_met": failed, "logits_rel_l2": rel, **chose}

    want, differing, distance = driver.reference_rows(cell, engine.params, prompts, seqs, choices)
    by_row = [
        [float(np.linalg.norm(g - w) / np.linalg.norm(w)) for g, w in zip(have, ref)]
        for have, ref in zip(got, want)
    ]
    out.update(prompts=[len(p) for p in prompts], reference_s=time.perf_counter() - t,
               engine={**reading(got, want, differing, distance), "rel_l2_by_row": by_row})
    print(json.dumps({"what": "progress", "engine": out["engine"]}), file=sys.stderr, flush=True)
    variants = [("reference_bfloat16", jnp.bfloat16, ()), ("reference_float8_e4m3fn", jnp.float8_e4m3fn, ())]
    if args.faults:
        names = [n for n in args.only.split(",") if n] or FAULTS
        variants += [(f"fault_{name}", None, (name,)) for name in names]
    for name, dtype, faults in variants:
        # the variant by itself: its rows and, from its own walk, the blocks it chose
        rows, own = variant_rows(cell, engine.params, prompts, seqs, dtype, faults)
        # the sound reference along the variant's blocks: what the check compares
        sound, d, dist = driver.reference_rows(cell, engine.params, prompts, seqs, own)
        out[name] = reading(rows, sound, d, dist)
        print(json.dumps({"what": "progress", "done": name, "s": time.perf_counter() - t,
                          "reading": out[name]}), file=sys.stderr, flush=True)
    os.makedirs(os.path.join(root, "chiprun_out"), exist_ok=True)
    with open(os.path.join(root, "chiprun_out", f"sala_check_readings.{args.seed}.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


def variant_rows(cell, params, prompts, seqs, operands, faults):
    """A variant of the reference by itself -> (its logits rows, and the blocks
    it chose at the rows compared, per prompt [R, Ls, Kh, blocks]: what an
    engine with that fault would hand the check)."""
    import numpy as np

    from odbench import reference_sala

    spec = cell.options["check"]
    steps, pad = int(spec["decode_steps"]), int(spec["pad_to"])
    rows, chosen = [], []
    for prompt, seq in zip(prompts, seqs):
        ids = np.zeros((1, pad), np.int32)
        ids[0, : len(seq)] = seq
        logits, own, _ = reference_sala.forward(
            params, ids, cell.config, operands, faults, (len(prompt) - 1, steps + 1),
            prompt_len=len(prompt), with_choices=True,
        )
        rows.append(np.asarray(logits)[0])
        chosen.append(np.asarray(own))
    return rows, chosen


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
