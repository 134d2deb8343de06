#!/usr/bin/env python3
"""The readings that place the Keye cell's two limits, on the chip:

    python3 benchmark/tools/keye_check_readings.py --seed 2147483659

For one seed (a process holds one engine), at the cell's published widths, on
the check's own prompts: the engine's logits against the float32 reference
along the engine's chosen rows, and how far apart in the reference's scores
the exchanged rows lie, set by set (what the cell's ``check`` line reports:
the median over the sets is what the limit holds); then, each
against the sound float32 reference along *its own* chosen rows, the reference
with the operands of every matrix multiplication rounded to bfloat16 (the
precision the configuration states) and to float8_e4m3fn (the nearest
precision below it), and the reference with one equation broken (``--faults``:
an indexer without its ReLU, the first 2,048 rows in place of the largest, a
selection that drops one chosen row in a hundred, chunks that do not see the
rows before them; and that last-but-two selection confined to one prompt of the
two, to the prompts' last tokens, to the last quarter of the layers: a part of
the sets that a median over all of them cannot see). Every reading goes
through the check's own comparison (``closed_loop_keye.verdict``) and carries
its ``ok`` and the limits it did not meet: every one but the engine's and
bfloat16's has to come out ``ok: false``. Among them stands one witness that
is no fault: the bfloat16 walk handed the float32 walk's experts, token by
token, which shows how much of a rounded walk's exchanged rows come from
tokens whose experts flipped at a near-tie (``--only-witness``: that walk and
the plain bfloat16 one alone). ``--only-check`` stops after the engine's. Prints one JSON line and leaves it under ``chiprun_out/``; needs the
TPU (``--rehearse``: the cell's small preset, on the CPU).
"""

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.dirname(BENCH_DIR))

FAULTS = ("no_relu", "first_rows", "drop_rows", "chunk_blind")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="serve-keye-videoqa")
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--only-check", action="store_true")
    ap.add_argument("--only-witness", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import jax.numpy as jnp
    import numpy as np

    from odbench import device, manifest
    from odbench.logits_check import rel_l2

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
    topk = cell.config["sa_config"]["topk"]

    def reading(rel, differing, distance, worst=None):
        """One control through the check's own comparison (``driver.verdict``)."""
        chose = driver.exchanged(cell, differing, distance, worst)
        ok, _, failed = driver.verdict(rel, chose)
        return {"ok": ok, "limits_not_met": failed, "logits_rel_l2": rel, **chose}

    want, differing, distance, gap, worst = driver.reference_rows(
        cell, engine.params, prompts, seqs, choices)
    engine_rel = rel_l2(got, want)[0]
    out = {"seed": args.seed, "reference_s": time.perf_counter() - t,
           "tolerance": driver.verdict(engine_rel, driver.exchanged(cell, differing, distance))[1],
           "engine": reading(engine_rel, differing, distance, worst)}
    args.faults = args.faults or args.only_witness
    if not args.only_check:
        import jax

        spec = cell.options["check"]
        steps, pad = int(spec["decode_steps"]), int(spec["pad_to"])
        def walk_alone(dtype, faults, given=None):
            """A walk that chooses for itself (``given``: but takes the experts
            it is handed, a prompt's [L, T, k]) -> per prompt its rows compared,
            their sets as rows to follow, and the experts every token took."""
            walk = jax.jit(lambda p, ids, first, taken: driver.reference_keye.forward(
                p, ids, cell.config, dtype, faults, None, (first, steps + 1), with_choices=True,
                experts=taken, with_experts=True))
            rows, sets, took = [], [], []
            for i, (prompt, seq) in enumerate(zip(prompts, seqs)):
                ids = np.zeros((1, pad), np.int32)
                ids[0, : len(seq)] = seq
                res = walk(engine.params, ids, np.int32(len(prompt) - 1),
                           None if given is None else given[i])
                rows.append(np.asarray(res[0])[0])
                sets.append(driver.sets_as_rows(np.asarray(res[1]), topk))
                took.append(res[-1])
            return rows, sets, took

        # the sound reference on its own rows, and each variant's rows as sets to follow
        sound, _, sound_experts = walk_alone(None, ())
        variants = [("reference_bfloat16", jnp.bfloat16, (), None),
                    ("reference_float8_e4m3fn", jnp.float8_e4m3fn, (), None)]
        if args.faults:
            # no fault but a witness: the rounded walk handed the float32 walk's
            # experts, so that no token's experts flip at a near-tie
            variants += [("reference_bfloat16_given_the_sound_experts", jnp.bfloat16, (), sound_experts)]
            variants += [(f"fault_{name}", jnp.bfloat16, (name,), None) for name in FAULTS]
        if args.only_witness:
            variants = [variants[0], variants[2]]
        kept = {}
        for name, dtype, faults, given in variants:
            # the variant walks alone and chooses for itself; then the sound
            # reference follows the variant's sets: its logits against the
            # variant's, and how far apart the exchanged rows lie in the sound scores
            rows, sets, _ = walk_alone(dtype, faults, given)
            kept[name] = sets
            along, differing, distance, _, _ = driver.reference_rows(
                cell, engine.params, prompts, seqs, sets)
            out[name] = reading(rel_l2(rows, along)[0], differing, distance)
            out[name]["against_the_sound_reference_alone"] = rel_l2(rows, sound)[0]
            print(json.dumps({"what": "progress", "done": name, "s": time.perf_counter() - t}),
                  file=sys.stderr, flush=True)
        if args.faults and not args.only_witness:
            # a fault confined to a part of the sets, which a median over all of
            # them cannot see: the engine's own sets, but for that part the sets
            # of the selection that takes the first rows. Such an engine's logits
            # go with its sets, so the logits' reading is the engine's own; what
            # has to catch it is the limit that holds the part by itself.
            layers = cell.config["num_hidden_layers"]
            late = np.arange(layers) >= layers - max(1, layers // 4)  # 4 layers of 16
            parts = {
                "one_prompt": [np.zeros_like(choices[0][..., 0], bool), np.ones_like(choices[1][..., 0], bool)],
                "last_tokens": [np.arange(c.shape[0])[:, None] * np.ones(layers, int) == 0 for c in choices],
                "late_layers": [np.broadcast_to(late, c.shape[:2]) for c in choices],
            }
            for part, where in parts.items():
                mixed = [np.where(w[..., None], bad, own)
                         for w, bad, own in zip(where, kept["fault_first_rows"], choices)]
                _, differing, distance, _, _ = driver.reference_rows(
                    cell, engine.params, prompts, seqs, mixed)
                out[f"fault_first_rows_in_{part}"] = reading(engine_rel, differing, distance)
                print(json.dumps({"what": "progress", "done": part, "s": time.perf_counter() - t}),
                      file=sys.stderr, flush=True)
    os.makedirs(os.path.join(root, "chiprun_out"), exist_ok=True)
    with open(os.path.join(root, "chiprun_out", f"keye_check_readings.{args.seed}.json"), "w") as f:
        json.dump(out, f)
    short = lambda v: {k: x for k, x in v.items() if k != "largest_at"} if isinstance(v, dict) else v
    print(json.dumps({k: short(v) for k, v in out.items()}), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
