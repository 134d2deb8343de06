"""Serving, callers that wait, a GLM-4.7-Flash configuration (latent attention,
a sigmoid router with a selection bias beside a shared expert, a leading dense
layer): ``closed_loop.py``'s window to the letter, with a check and counters
of its own.

As ``closed_loop_olmoe.py`` and ``closed_loop_granite_h.py`` do (PERF.md
section 7(f) stays the benchmark's debt), this driver loads a private copy of
``closed_loop.py`` and gives it a view of ``serve_cell`` in which four
functions are its own:

``start``           refuses, at once and before anything is built, a program
                    that knows no latent attention; the check is against
                    ``reference_glm_flash`` (attention in the rebuilt form
                    only, the router written from its equations, the held
                    experts), at the cell's published widths;
``snapshot``        also carries the engine's latent-ring and routed-FFN
                    counters;
``traced_stretch``  also reads, from the program's ``serve_prefill`` and
                    ``serve_decode`` spans, the live latent rows and slots of
                    each traced decode step and what each traced call routed
                    to the held experts;
``finish``          hands the window's counter differences to the readers, and
                    prints them (``window_counters``).

Everything else, the clients' requests (``traffic.requests``) among it, is the
code the other closed-loop cells run.
"""

from __future__ import annotations

import math
import os
import time
import types

import numpy as np

from odbench import costs_glm_flash, manifest, program_obs, reference_glm_flash, serve_cell
from odbench.logits_check import rel_l2, served_rows

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Engine logits (bf16 weights and activations; prefill in the rebuilt form,
# decode in the absorbed form through the latent ring and its kernel; the
# grouped matmuls) against the float32 reference's full forward (rebuilt form
# only), relative L2 over the rows compared: the last prompt position and each
# of 8 decode steps of two prompts (900 and 1,700 tokens, padded into buckets
# 1,280 and 1,792).
#
# Readings on the chip, 24 layers at the published widths (PR 32,
# ``tools/glm_flash_check_readings.py`` on six seeds, the check lines of
# twenty-eight more):
#
#   the engine                                   2.1e-2 to 8.0e-2  (a prompt: 1.4e-2 to 9.0e-2)
#   the reference with bfloat16 operands         1.6e-2 to 3.6e-2
#   the reference with float8_e4m3fn operands    9.75e-1 to 9.97e-1   (has to fail)
#
# and what an engine with one equation broken would read (the reference with
# that fault and bfloat16 operands against the sound float32 reference, two
# seeds; with float32 operands the same to two digits):
#
#   the values taken from another 512 of the row 1.39
#   the rotation put on the unrotated part       3.9e-1, 4.2e-1
#   softmax in place of sigmoid                  3.4e-1, 3.7e-1
#   no norm on c_kv                              2.7e-1, 2.8e-1
#   the 1.8 left out                             1.71e-1, 1.83e-1
#   b weighed as well as chosen by               5.2e-2, 6.6e-2   (not caught here)
#   a stale row of the previous tenant (engine)  9.2e-2, 1.02e-1  (not caught here; sound on
#                                                those seeds: 6.8e-2, 5.8e-2)
#
# This model reads higher than the other cells' (granite 2e-2, OLMoE 1e-2)
# already in the bfloat16 reference: 48 residual branches, the routed sum
# scaled by 1.8, and 23 routers whose scores are sigmoids of logits of
# about unit spread, so that the 4th and 5th biased scores of a token lie
# close more often than softmax probabilities do. The sound readings of 34
# seeds spread like a log-normal of median 4.3e-2 and sigma 0.35. The limit,
# 1.4e-1, is 1.75 times the largest of them (3.4 sigma), 0.82 of the
# smallest reading of the smallest fault it can catch (the scale, which
# varies little from seed to seed), a seventh of float8's. It stood at 2e-1
# until the faults were read at these widths: the scale left out passed
# under that. Two faults pass under any limit that the sound engine meets:
# a bias drawn N(0, 0.1^2) beside scores near 0.5, weighed and renormalised,
# moves the logits by less than bf16 does through 24 layers; and one stale
# row among the 900 or 1,700 a slot attends to moves them by about as much
# again as bf16 does. Those two are the CPU tests' (``tests/test_glm_flash.py``:
# float32, prompts of 13 tokens, a limit of 2e-3), not this check's.
#
# What the engine rounds that the bfloat16 reference does not: the residual
# stream between the 48 branches, and the absorbed form's own intermediates.
# The reference multiplies c_kv W_UK into a key and rounds the key; the
# decode step multiplies q_nope W_UK^T into a 512-wide query and rounds that,
# then rounds the 512-wide weighted sum of latents before W_UV where the
# reference rounds each value. Each is one more bf16 rounding (2^-9 relative)
# of a vector that enters a 512- or 576-long dot product, the same size as
# the roundings the rebuilt form makes, at other places: the two forms differ
# from each other by about what either differs from float32. As in the OLMoE
# cell, a token whose 4th and 5th biased scores lie closer than bf16
# activations resolve takes another expert than in float32, and g_4 y_4
# becomes g_5 y_5 in its FFN output.
LOGITS_REL_L2 = 1.4e-1
COUNTERS = (
    "moe_pairs", "moe_experts_hit", "moe_max_pairs", "moe_pairs_all",
    "latent_rows_read", "latent_bytes_moved",
)
RESIDENT = "latent_cache_resident_bytes"


def reference_rows(cell, params, prompts, seqs, operands=None, faults=()):
    """The same rows from the reference's full forward over each sequence
    (padded to the check's ``pad_to``; every layer is causal, so the padding
    changes nothing before it). ``operands`` and ``faults`` are the readings
    tool's: a lower precision, one equation broken."""
    import jax

    spec = cell.options["check"]
    steps, pad = int(spec["decode_steps"]), int(spec["pad_to"])
    ref_fn = jax.jit(lambda p, ids: reference_glm_flash.forward(p, ids, cell.config, operands, faults))
    rows = []
    for prompt, seq in zip(prompts, seqs):
        ids = np.zeros((1, pad), np.int32)
        ids[0, : len(seq)] = seq
        first = len(prompt) - 1
        rows.append(np.asarray(ref_fn(params, ids))[0, first : first + steps + 1])
    return rows


def check_logits(cell, engine, seed, report, t_process) -> bool:
    """Prefill (rebuilt), then decoding through the latent ring (absorbed),
    against the reference's full forward on the same tokens: logits, never
    tokens. Outside the window."""
    prompts, seqs, got = served_rows(cell, engine, seed)
    rel, per_prompt = rel_l2(got, reference_rows(cell, engine.params, prompts, seqs))
    ok = math.isfinite(rel) and rel <= LOGITS_REL_L2
    report.line(
        "check", ok=ok, logits_rel_l2=rel, tolerance={"logits_rel_l2": LOGITS_REL_L2},
        reference="reference_glm_flash", per_prompt_rel_l2=per_prompt,
        prompts=[len(p) for p in prompts], decode_steps=len(got[0]) - 1,
        rows_compared=sum(len(rows) for rows in got),
        setup_so_far_s=time.perf_counter() - t_process,
    )
    return ok


def start(cell, devices, seed, trace, report, t_process):
    """``serve_cell.start`` with this configuration's reference in the check,
    after asking the program whether it runs the configuration at all: one
    that reads no ``kv_lora_rank`` would build 24 layers of ordinary attention
    with a dense FFN of 10,240 under this model's name."""
    from opendiloco_tpu.models.llama import LlamaConfig
    from opendiloco_tpu.serve import ContinuousBatcher

    if not getattr(LlamaConfig.from_dict(cell.config), "latent", False):
        raise RuntimeError(
            f"the program under test cannot run {cell.config_name}: its LlamaConfig "
            "reads no kv_lora_rank and has no latent attention"
        )
    cfg, engine = serve_cell.build(cell, devices, seed, report, t_process)
    report.line(
        "latent", params=costs_glm_flash.param_count(cell.config),
        row_values=costs_glm_flash.latent_row_dim(cell.config),
        latent_bytes_per_token=costs_glm_flash.latent_bytes_per_token(cell.config),
        latent_cache_resident_bytes=engine.latent_cache_resident_bytes,
        weights_resident_bytes=engine.weights_resident_bytes,
        experts_held=cell.config["n_routed_experts"], experts=cell.config["num_experts"],
        per_token=cell.config["num_experts_per_tok"],
        dense_layers=cell.config["first_k_dense_replace"],
    )
    check_ok = check_logits(cell, engine, seed, report, t_process)
    instrument = serve_cell.Instrument(engine) if trace == 1 else None
    return cfg, engine, check_ok, instrument, ContinuousBatcher(engine).start()


def snapshot(engine, batcher) -> dict:
    """``serve_cell.snapshot``, the latent ring's and the routed FFN's
    counters, and what the ring holds."""
    return {
        **serve_cell.snapshot(engine, batcher),
        **{name: getattr(engine, name, 0) for name in (*COUNTERS, RESIDENT)},
    }


def traced_stretch(cell, engine, batcher, compiles, report, keep_sending, meanwhile=None) -> dict:
    """``serve_cell.traced_stretch`` and, per traced decode step, the live
    latent rows it read (over the layers) and its live slots, and per traced
    prefill and decode step the pairs it routed to the held experts (with the
    experts they reached), as the program's spans carry them; nothing where
    they carry none."""
    traced = serve_cell.traced_stretch(
        cell, engine, batcher, compiles, report, keep_sending, meanwhile
    )
    stretch = traced["stretch"]
    steps, routed = [], []
    for name in ("serve_prefill", "serve_decode"):
        for args in program_obs.span_args(stretch.capture, name, stretch.t0, stretch.t1):
            if name == "serve_decode" and "latent_rows" in args:
                steps.append([args["latent_rows"], args["slots"]])
            if "moe_pairs" in args:
                routed.append([args["moe_pairs"], args["moe_experts_hit"]])
    traced["counters"].update(traced_mla_calls=steps, traced_moe_calls=routed)
    report.line("traced_latent", decode_steps=len(steps), latent_rows=sum(c[0] for c in steps),
                live_slots=sum(c[1] for c in steps))
    report.line("traced_routed", calls=len(routed), held_pairs=sum(c[0] for c in routed),
                held_experts_hit=sum(c[1] for c in routed))
    return traced


def run(**kwargs):
    report = kwargs["report"]

    def finish(*, before, after, extra_counters=None, **rest):
        """The window's counter differences, to the readers and onto a line."""
        moved = {name: after[name] - before[name] for name in COUNTERS}
        steps = after["decode_steps"] - before["decode_steps"]
        report.line(
            "window_counters", **moved, **{RESIDENT: after[RESIDENT]}, decode_steps=steps,
            decode_step_ms=(after["decode_s"] - before["decode_s"]) / max(1, steps) * 1e3,
            prefill_s=after["prefill_s"] - before["prefill_s"],
            latent_bytes_per_decode_step=moved["latent_bytes_moved"] / max(1, steps),
            held_share_of_pairs=moved["moe_pairs"] / max(1, moved["moe_pairs_all"]),
        )
        return serve_cell.finish(
            before=before, after=after, extra_counters={**(extra_counters or {}), **moved}, **rest
        )

    loop = manifest.load_module(os.path.join(_BENCH, "drivers", "closed_loop.py"))
    view = dict(vars(serve_cell))
    view.update(start=start, snapshot=snapshot, traced_stretch=traced_stretch, finish=finish)
    loop.serve_cell = types.SimpleNamespace(**view)
    return loop.run(**kwargs)
