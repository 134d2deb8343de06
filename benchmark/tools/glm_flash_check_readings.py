#!/usr/bin/env python3
"""The readings that place the GLM-4.7-Flash cell's tolerance, on the chip:

    python3 benchmark/tools/glm_flash_check_readings.py --seed 2147483659 [--faults both]

For one seed (a process holds one engine: the chip has no room for a second),
at the cell's published widths, on the check's own prompts: the engine's
logits against the float32 reference (what the cell's ``check`` line
reports), and the reference itself with the operands of every matrix
multiplication rounded to bfloat16 (the precision the configuration states)
and to float8_e4m3fn (the nearest precision below it), each against float32.
The float8 reading has to come out above the driver's ``LOGITS_REL_L2`` and
the others below it.

``--faults`` adds what the limit has to catch at these widths: the reference
with one equation broken (``reference_glm_flash``'s ``faults``) and bfloat16
operands, which is what an engine with that fault would read (``both``: with
float32 operands too, the fault's own size), and the engine itself with a
stale row planted in its ring (the previous tenant's row where each prompt's
last row belongs; ``stale_row``: that one alone), against the reference on
the tokens that engine then sampled. Each equation's fault is one more
compile of the 24-layer reference.
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

EQUATION_FAULTS = ("no_kv_norm", "rope_on_nope", "bias_weighed", "softmax_scores", "no_scale",
                   "values_from_tail")


def stale_row_reading(driver, cell, engine, seed, sound_seqs, want):
    """The engine's rows with, in every layer of each check slot, the row of
    a previous tenant (another prompt of the same length) left where the
    prompt's last row belongs: what a reader sees whose mask is one row too
    wide, or a writer that skipped a row."""
    import jax.numpy as jnp

    from odbench import traffic

    rng, old = traffic.rng_for(seed, 4), {}
    for slot, n in enumerate(cell.options["check"]["prompt_tokens"]):
        engine.admit(slot, rng.integers(traffic.FIRST_TOKEN, cell.config["vocab_size"], n).tolist())
        old[slot] = jnp.copy(engine.cache_k[:, slot, 0, :, n - 1])

    def plant(engine, prompts):
        for slot, prompt in enumerate(prompts):
            engine.cache_k = engine.cache_k.at[:, slot, 0, :, len(prompt) - 1].set(old[slot])

    prompts, seqs, got = driver.served_rows(cell, engine, seed, after_admit=plant)
    if seqs != sound_seqs:  # the planted row turned a sampled token: the reference follows it
        want = driver.reference_rows(cell, engine.params, prompts, seqs)
    return driver.rel_l2(got, want)[0], seqs == sound_seqs


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="serve-glm-flash-agent")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--faults", choices=("none", "stale_row", "bfloat16", "both"), default="none")
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
    engine_rel, per_prompt = driver.rel_l2(got, want)
    out = {"seed": args.seed, "tolerance": driver.LOGITS_REL_L2, "engine": engine_rel,
           "engine_per_prompt": per_prompt, "reference_s": time.perf_counter() - t}
    for name, dtype in (("reference_bfloat16", jnp.bfloat16),
                        ("reference_float8_e4m3fn", jnp.float8_e4m3fn)):
        low = driver.reference_rows(cell, engine.params, prompts, seqs, operands=dtype)
        out[name] = driver.rel_l2(low, want)[0]
    operands = {"bfloat16": {"bfloat16": jnp.bfloat16},
                "both": {"bfloat16": jnp.bfloat16, "float32": None}}.get(args.faults, {})
    for fault in EQUATION_FAULTS if operands else ():
        for name, dtype in operands.items():
            rows = driver.reference_rows(cell, engine.params, prompts, seqs, dtype, (fault,))
            out.setdefault("fault_" + name, {})[fault] = driver.rel_l2(rows, want)[0]
        print(json.dumps({"what": "progress", "fault": fault, "s": time.perf_counter() - t}),
              file=sys.stderr, flush=True)
    if args.faults != "none":
        rel, same = stale_row_reading(driver, cell, engine, args.seed, seqs, want)
        out["fault_engine"] = {"stale_row": rel, "same_tokens": same}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
