#!/usr/bin/env python3
"""The readings that place the Laguna cell's limit, on the chip:

    python3 benchmark/tools/laguna_check_readings.py --seed 2900000017 --faults --forms

For one seed (a process holds one engine), at the cell's published widths, on
the check's own prompts (``--prompts 1``: the first alone): the engine's logits
against the float32 reference, row by row (row 0 is the prompt's last token,
from its last chunk; the others are decode steps); then, each against the sound
float32 reference, the reference with the operands of every matrix
multiplication rounded to bfloat16 (the precision the configuration states) and
to float8_e4m3fn (the nearest precision below it), and with ``--faults`` the
reference with one assumed equation broken (``reference_laguna``'s faults).
Every reading goes through the check's own comparison
(``closed_loop_laguna.verdict``) and carries its ``ok``. With ``--forms`` also
the two forms a chunk's sliding layers can take, timed alone at the cell's
shapes: the band alone (``banded_chunk_attention``) and every tile of the ring
under the window's mask (``tiled_sparse_attention``). Prints one JSON line and
leaves it under ``chiprun_out/``; needs the TPU (``--rehearse``: the cell's
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

FAULTS = ("heads_48", "swap_rope", "no_factor", "rotate_whole", "no_ramp", "no_gate", "no_scaling",
          "sigmoid_scores", "window_minus", "window_plus")


def chunk_forms(engine, iters: int = 10) -> dict:
    """us a call of one sliding layer's chunk attention in each form, at the
    engine's shapes, a chunk behind two whole chunks: ``iters`` calls in one
    program less one call, over ``iters`` - 1."""
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.ops.attention import (
        band_block, banded_chunk_attention, ring_window_rows, tiled_sparse_attention,
    )

    cfg = engine.cfg
    c, h, d, w = cfg.q_chunk_size, cfg.swa_num_attention_heads, cfg.head_dim, cfg.sliding_window_size
    ring = engine.cache_v.shape[-1]
    key = jax.random.key(0)
    q = jax.random.normal(key, (c, h, d), engine.compute_dtype)
    pages = jax.random.normal(key, (cfg.kv_heads, d, ring), engine.compute_dtype)
    plen = jnp.int32(2 * c)
    block = band_block(c, ring, w)
    forms = {
        "banded": lambda q, plen: banded_chunk_attention(q, pages, pages, plen, w, block),
        "tiled_under_the_mask": lambda q, plen: tiled_sparse_attention(
            q, pages, pages, ring_window_rows(plen + jnp.arange(c), ring, w), ring, min(512, ring)),
    }
    out = {"band_block": block, "ring": ring, "queries": c, "heads": h}
    for name, fn in forms.items():
        def many(q, plen, n, fn=fn):
            return jax.lax.fori_loop(0, n, lambda i, x: fn(x, plen).astype(x.dtype), q)

        seconds = {}
        for n in (1, iters):
            run = jax.jit(lambda q, plen, n=n: many(q, plen, n))
            jax.block_until_ready(run(q, plen))
            t = time.perf_counter()
            jax.block_until_ready(run(q, plen))
            seconds[n] = time.perf_counter() - t
        out[f"{name}_us"] = (seconds[iters] - seconds[1]) / (iters - 1) * 1e6
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="serve-laguna-repoedit")
    ap.add_argument("--seed", type=int, default=2900000017)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--forms", action="store_true")
    ap.add_argument("--prompts", type=int, default=2)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax.numpy as jnp
    import numpy as np

    from odbench import device, manifest
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
    out = {"seed": args.seed}
    if args.forms:
        out["chunk_forms"] = chunk_forms(engine)
        print(json.dumps({"what": "progress", "chunk_forms": out["chunk_forms"]}), file=sys.stderr, flush=True)
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
    with open(os.path.join(root, "chiprun_out", f"laguna_check_readings.{args.seed}.json"), "w") as f:
        json.dump(out, f)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
