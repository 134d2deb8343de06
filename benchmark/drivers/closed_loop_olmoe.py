"""Serving, callers that wait, an OLMoE configuration: ``closed_loop.py``'s
window to the letter, with a check and counters of its own.

``closed_loop.run`` reaches the shared serving code through its module's one
name ``serve_cell``. This driver loads a private copy of that module and
gives it a view of ``serve_cell`` in which four functions are its own:

``start``           the check is against ``reference_olmoe`` (the routed FFN
                    and QK-norm written from their equations), at the cell's
                    published widths, on prompts of its traffic's lengths;
``snapshot``        also carries the engine's routed-FFN counters;
``traced_stretch``  also reads, from the program's ``serve_prefill`` and
                    ``serve_decode`` spans, what each traced call routed;
``finish``          hands the window's counter differences to the readers.

Everything else (``build``, ``warm_up``, ``tails``, the window, the traced
stretch itself) is the code the other closed-loop cell runs.
"""

from __future__ import annotations

import math
import os
import time
import types

import numpy as np

from odbench import costs_olmoe, manifest, program_obs, reference_olmoe, serve_cell, traffic

# Engine logits (bf16 activations over float32 weights, the grouped matmuls,
# the decode kernel, the ring cache) against the float32 reference's full
# forward, relative L2 over the rows compared: the last prompt position and
# each decode step of each prompt, as ``serve_cell.check_logits`` compares.
#
# It starts from ``serve_cell.LOGITS_REL_L2`` (6e-2, twice what bf16 against
# float32 measured on the dense blocks at 24-32 layers) and from this cell's
# own readings on the chip, 4 layers at the published widths (PR 26,
# ``tools/olmoe_check_readings.py`` on three seeds, the check lines of ten):
#
#   the engine                                   5.3e-3 to 9.7e-3
#   the reference with bfloat16 operands         4.3e-3 to 6.0e-3
#   the reference with float8_e4m3fn operands    2.6e-1 to 2.9e-1   (has to fail)
#
# A routed block adds a discontinuity to the rounding: a token whose 8th and
# 9th router probabilities lie closer than bf16 activations resolve takes
# another expert than in float32, and r_8 y_8 becomes r_9 y_9 in its FFN
# output. Where that happened in a compared row, one prompt's figure read
# 1.2e-2 against the 5.3e-3 to 5.8e-3 of prompts without a flip: a flip costs
# about 1e-2 of a prompt's nine rows. The limit is three times the largest
# reading over the seeds and a tenth of float8's; renormalised top-8 weights,
# a missing expert, a missing or per-head QK-norm, or a router or experts
# computed below bf16 each move the logits by 1e-1 and more.
LOGITS_REL_L2 = 3e-2
MOE_COUNTERS = ("moe_pairs", "moe_experts_hit", "moe_max_pairs")


def served_rows(cell, engine, seed):
    """The check's prompts through the engine: prefill, then decoding through
    the cache -> (prompts, the token sequence each was fed, the logits rows of
    its last prompt position and of each decode step)."""
    spec = cell.options["check"]
    rng = traffic.rng_for(seed, 3)
    vocab = cell.config["vocab_size"]
    prompts = [rng.integers(traffic.FIRST_TOKEN, vocab, n).tolist() for n in spec["prompt_tokens"]]
    steps = int(spec["decode_steps"])
    tokens = np.zeros(engine.num_slots, np.int32)
    cache_lens = np.zeros(engine.num_slots, np.int32)
    seqs, got = [], []
    for slot, prompt in enumerate(prompts):
        tok, logits = engine.admit(slot, prompt)
        tokens[slot], cache_lens[slot] = tok, len(prompt)
        seqs.append(list(prompt) + [tok])
        got.append([np.asarray(logits, np.float32)])
    for step in range(steps):
        nxt, logits = engine.decode_step(tokens, cache_lens)
        logits = np.asarray(logits, np.float32)
        for slot in range(len(prompts)):
            got[slot].append(logits[slot])
            tokens[slot] = nxt[slot]
            cache_lens[slot] += 1
            if step < steps - 1:
                seqs[slot].append(int(nxt[slot]))
    return prompts, seqs, [np.stack(rows) for rows in got]


def reference_rows(cell, params, prompts, seqs, operands=None):
    """The same rows from the reference's full forward over each sequence
    (padded to the check's ``pad_to``; causal, so the padding changes nothing)."""
    import jax

    spec = cell.options["check"]
    steps, pad = int(spec["decode_steps"]), int(spec["pad_to"])
    ref_fn = jax.jit(lambda p, ids: reference_olmoe.forward(p, ids, cell.config, operands))
    rows = []
    for prompt, seq in zip(prompts, seqs):
        ids = np.zeros((1, pad), np.int32)
        ids[0, : len(seq)] = seq
        first = len(prompt) - 1
        rows.append(np.asarray(ref_fn(params, ids))[0, first : first + steps + 1])
    return rows


def rel_l2(have: list, want: list):
    """-> (relative L2 over all rows, the same per prompt)."""
    num = [float(np.sum((h - w) ** 2)) for h, w in zip(have, want)]
    den = [float(np.sum(w**2)) for w in want]
    return math.sqrt(sum(num) / sum(den)), [math.sqrt(n / d) for n, d in zip(num, den)]


def check_logits(cell, engine, seed, report, t_process) -> bool:
    """Prefill, then decoding through the cache, against the reference's full
    forward on the same tokens: logits, never tokens. Outside the window."""
    prompts, seqs, got = served_rows(cell, engine, seed)
    rel, per_prompt = rel_l2(got, reference_rows(cell, engine.params, prompts, seqs))
    ok = math.isfinite(rel) and rel <= LOGITS_REL_L2
    report.line(
        "check", ok=ok, logits_rel_l2=rel, tolerance={"logits_rel_l2": LOGITS_REL_L2},
        reference="reference_olmoe", per_prompt_rel_l2=per_prompt,
        prompts=[len(p) for p in prompts], decode_steps=len(got[0]) - 1,
        rows_compared=sum(len(rows) for rows in got),
        setup_so_far_s=time.perf_counter() - t_process,
    )
    return ok


def start(cell, devices, seed, trace, report, t_process):
    """``serve_cell.start`` with this configuration's reference in the check."""
    from opendiloco_tpu.serve import ContinuousBatcher

    cfg, engine = serve_cell.build(cell, devices, seed, report, t_process)
    report.line(
        "routed", params=costs_olmoe.param_count(cell.config),
        active_matmul_params=costs_olmoe.active_matmul_param_count(cell.config),
        experts=cell.config["num_experts"], per_token=cell.config["num_experts_per_tok"],
    )
    check_ok = check_logits(cell, engine, seed, report, t_process)
    instrument = serve_cell.Instrument(engine) if trace == 1 else None
    return cfg, engine, check_ok, instrument, ContinuousBatcher(engine).start()


def snapshot(engine, batcher) -> dict:
    """``serve_cell.snapshot`` and the routed FFN's counters (0 where the
    program has none)."""
    return {
        **serve_cell.snapshot(engine, batcher),
        **{name: getattr(engine, name, 0) for name in MOE_COUNTERS},
    }


def traced_stretch(cell, engine, batcher, compiles, report, keep_sending, meanwhile=None) -> dict:
    """``serve_cell.traced_stretch`` and, per traced prefill and decode step,
    the pairs it routed and the experts they reached (summed over layers), as
    the program's spans carry them; nothing where they carry none."""
    traced = serve_cell.traced_stretch(
        cell, engine, batcher, compiles, report, keep_sending, meanwhile
    )
    stretch = traced["stretch"]
    calls = [
        [args["moe_pairs"], args["moe_experts_hit"]]
        for name in ("serve_prefill", "serve_decode")
        for args in program_obs.span_args(stretch.capture, name, stretch.t0, stretch.t1)
        if "moe_pairs" in args
    ]
    traced["counters"]["traced_moe_calls"] = calls
    report.line("traced_routed", calls=len(calls), pairs=sum(c[0] for c in calls),
                experts_hit=sum(c[1] for c in calls))
    return traced


def finish(*, before, after, extra_counters=None, **rest):
    moe = {name: after[name] - before[name] for name in MOE_COUNTERS}
    return serve_cell.finish(
        before=before, after=after, extra_counters={**(extra_counters or {}), **moe}, **rest
    )


def run(**kwargs):
    loop = manifest.load_module(
        os.path.join(os.path.dirname(os.path.abspath(__file__)), "closed_loop.py")
    )
    view = dict(vars(serve_cell))
    view.update(start=start, snapshot=snapshot, traced_stretch=traced_stretch, finish=finish)
    loop.serve_cell = types.SimpleNamespace(**view)
    return loop.run(**kwargs)
