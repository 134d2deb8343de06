"""Serving, callers that wait, a granite-4.0-h hybrid configuration:
``closed_loop.py``'s window to the letter, with a check and counters of its own.

As ``closed_loop_olmoe.py`` does (PERF.md section 7(f) stays the benchmark's
debt), this driver loads a private copy of ``closed_loop.py`` and gives it a
view of ``serve_cell`` in which four functions are its own:

``start``           refuses, at once and before anything is built, a program
                    that cannot run Mamba-2 layers; the check is against
                    ``reference_granite_h`` (the sequential recurrence, the
                    held experts, the shared MLP and the multipliers written
                    from their equations), at the cell's published widths;
``snapshot``        also carries the engine's Mamba-2 and routed-FFN counters;
``traced_stretch``  also reads, from the program's ``serve_prefill`` and
                    ``serve_decode`` spans, what each traced call put through
                    the mixers and what it routed to the held experts, and
                    from the compiled programs' text which of their
                    instructions are the mixers';
``finish``          hands the window's counter differences to the readers, and
                    prints them (``window_counters``).

Everything else, the clients' requests (``traffic.requests``) among it, is the
code the other closed-loop cells run.
"""

from __future__ import annotations

import math
import os
import re
import time
import types

import numpy as np

from odbench import costs_granite_h, manifest, program_obs, reference_granite_h, serve_cell
from odbench.xplane import short_name

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the check's prompts through the engine, and the relative L2 over its rows,
# are the OLMoE driver's to the letter; an instruction's result shape is read
# as the reader reads an event's
_olmoe = manifest.load_module(os.path.join(_BENCH, "drivers", "closed_loop_olmoe.py"))
served_rows, rel_l2 = _olmoe.served_rows, _olmoe.rel_l2
result_shape = manifest.load_module(
    os.path.join(_BENCH, "readers", "ssm_mixer_roofline.py")
).result_shape

# Engine logits (bf16 activations over float32 weights, the chunked scan in
# prefill, the one-step recurrence over the float32 state in decode, the
# grouped matmuls, the decode kernel, the ring cache) against the float32
# reference's full forward (sequential recurrence), relative L2 over the rows
# compared: the last prompt position and each of 8 decode steps of two prompts
# (700 and 1,800 tokens, padded into buckets 1,024 and 2,048).
#
# Readings on the chip, 10 layers at the published widths (PR 30,
# ``tools/granite_h_check_readings.py``, and the check lines of 23 seeds):
#
#   the engine                                   1.80e-2 to 2.10e-2
#   the reference with bfloat16 operands         9.0e-3
#   the reference with float8_e4m3fn operands    2.32e-1   (has to fail)
#
# The engine reads twice the bfloat16 reference because it rounds more than
# the operands of its matmuls: the residual stream between the twenty
# branches, and in the chunked scan ``dt u``, the decay matrix and the state
# entering a chunk, are bf16. As in the OLMoE cell, a token whose 10th and
# 11th router logits lie closer than bf16 activations resolve takes another
# expert than in float32. The limit is 2.9 times the largest reading over the
# seeds and a quarter of float8's: room on both sides. A stale state (the
# slot's previous tenant's), a state taken at the bucket's end and not at the
# prompt's, a missing ``D`` or a missing ``z`` gate each move the logits by
# far more (tests/test_granite_hybrid.py holds that at a small size).
LOGITS_REL_L2 = 6e-2
COUNTERS = (
    "moe_pairs", "moe_experts_hit", "moe_max_pairs", "moe_pairs_all",
    "ssm_tokens", "ssm_state_bytes_moved",
)


def reference_rows(cell, params, prompts, seqs, operands=None):
    """The same rows from the reference's full forward over each sequence
    (padded to the check's ``pad_to``; every layer is causal, so the padding
    changes nothing before it)."""
    import jax

    spec = cell.options["check"]
    steps, pad = int(spec["decode_steps"]), int(spec["pad_to"])
    ref_fn = jax.jit(lambda p, ids: reference_granite_h.forward(p, ids, cell.config, operands))
    rows = []
    for prompt, seq in zip(prompts, seqs):
        ids = np.zeros((1, pad), np.int32)
        ids[0, : len(seq)] = seq
        first = len(prompt) - 1
        rows.append(np.asarray(ref_fn(params, ids))[0, first : first + steps + 1])
    return rows


def check_logits(cell, engine, seed, report, t_process) -> bool:
    """Prefill, then decoding through state and cache, against the
    reference's full forward on the same tokens: logits, never tokens.
    Outside the window."""
    prompts, seqs, got = served_rows(cell, engine, seed)
    rel, per_prompt = rel_l2(got, reference_rows(cell, engine.params, prompts, seqs))
    ok = math.isfinite(rel) and rel <= LOGITS_REL_L2
    report.line(
        "check", ok=ok, logits_rel_l2=rel, tolerance={"logits_rel_l2": LOGITS_REL_L2},
        reference="reference_granite_h", per_prompt_rel_l2=per_prompt,
        prompts=[len(p) for p in prompts], decode_steps=len(got[0]) - 1,
        rows_compared=sum(len(rows) for rows in got),
        setup_so_far_s=time.perf_counter() - t_process,
    )
    return ok


def start(cell, devices, seed, trace, report, t_process):
    """``serve_cell.start`` with this configuration's reference in the check,
    after asking the program whether it runs the configuration at all: one
    that knows no ``layer_types`` would build ten attention layers under this
    model's name (and 72 experts a layer, which no chip holds)."""
    from opendiloco_tpu.models.llama import LlamaConfig
    from opendiloco_tpu.serve import ContinuousBatcher

    if not getattr(LlamaConfig.from_dict(cell.config), "hybrid", False):
        raise RuntimeError(
            f"the program under test cannot run {cell.config_name}: its LlamaConfig "
            "reads no layer_types and has no Mamba-2 mixer"
        )
    cfg, engine = serve_cell.build(cell, devices, seed, report, t_process)
    report.line(
        "hybrid", params=costs_granite_h.param_count(cell.config),
        layers=costs_granite_h.layer_kinds(cell.config),
        experts_held=cell.config["num_local_experts"], experts=cell.config["num_experts"],
        per_token=cell.config["num_experts_per_tok"],
        ssm_state_resident_bytes=engine.ssm_state_resident_bytes,
        ssm_state_bytes_per_slot=costs_granite_h.ssm_state_bytes_per_slot(cell.config),
    )
    check_ok = check_logits(cell, engine, seed, report, t_process)
    instrument = serve_cell.Instrument(engine) if trace == 1 else None
    return cfg, engine, check_ok, instrument, ContinuousBatcher(engine).start()


def snapshot(engine, batcher) -> dict:
    """``serve_cell.snapshot`` and the mixers' and the routed FFN's counters."""
    return {
        **serve_cell.snapshot(engine, batcher),
        **{name: getattr(engine, name, 0) for name in COUNTERS},
    }


_SCOPE = "odtp_ssm"
_COMPUTATION = re.compile(r"^(ENTRY )?%?[\w.\-]+ \(.*\) -> .* \{$")


def top_level_instructions(text: str, scope: str = _SCOPE) -> tuple:
    """(result name, result shape) of each instruction of a compiled
    program's text outside fused computations (a fusion runs as one
    operation, under its own name) -> (those whose ``op_name`` lies under
    ``scope``, the others)."""
    inside, outside, fused = set(), set(), False
    for line in text.splitlines():
        line = line.strip()
        if _COMPUTATION.match(line):
            fused = "fused_computation" in line.split(" (")[0]
            continue
        if fused or " = " not in line:
            continue
        name, detail = short_name(line.removeprefix("ROOT "))
        found = inside if f"/{scope}/" in line else outside
        found.add((name.split(" ")[0], result_shape(detail)))
    return inside, outside


def mixer_instructions(engine) -> tuple:
    """The Mamba-2 mixers' instructions in the engine's prefill programs (one
    per bucket) and its decode program, as the chip's compiler named them:
    the programs are lowered and compiled again, which the persistent cache
    answers. After the traced stretch, so that neither the window nor the
    stretch sees it. -> (the instructions, those of them that another of the
    programs has under the same name and shape outside the mixers: a trace's
    events carry no program, so such an event is counted as a mixer's
    wherever it ran)."""
    import jax
    import jax.numpy as jnp

    shaped = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    vec = jax.ShapeDtypeStruct((engine.num_slots,), jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    params = shaped(engine.params)
    texts = [
        engine._decode.lower(
            params, vec, vec, *shaped((engine.cache_k, engine.cache_v, *engine._ssm))
        ).compile().as_text()
    ]
    for bucket in engine.prefill_buckets:
        ids = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
        texts.append(engine._prefill.lower(params, ids, scalar).compile().as_text())
    inside, outside = set(), set()
    for text in texts:
        mixers, others = top_level_instructions(text)
        inside |= mixers
        outside |= others
    return sorted(inside), sorted(inside & outside)


def traced_stretch(cell, engine, batcher, compiles, report, keep_sending, meanwhile=None) -> dict:
    """``serve_cell.traced_stretch`` and, per traced prefill and decode step,
    the tokens and sequences it put through the mixers and the pairs it
    routed to the held experts (with the experts they reached), as the
    program's spans carry them; then the mixers' instruction names."""
    traced = serve_cell.traced_stretch(
        cell, engine, batcher, compiles, report, keep_sending, meanwhile
    )
    stretch = traced["stretch"]
    calls, routed = [], []
    for name, decode in (("serve_prefill", 0), ("serve_decode", 1)):
        for args in program_obs.span_args(stretch.capture, name, stretch.t0, stretch.t1):
            if "ssm_tokens" in args:
                calls.append([args["ssm_tokens"], args["ssm_tokens"] if decode else 1, decode])
            if "moe_pairs" in args:
                routed.append([args["moe_pairs"], args["moe_experts_hit"]])
    t = time.perf_counter()
    ops, ambiguous = mixer_instructions(engine)
    traced["counters"].update(traced_ssm_calls=calls, ssm_ops=ops, traced_moe_calls=routed)
    report.line(
        "traced_mixers", calls=len(calls), prefills=sum(1 for c in calls if not c[2]),
        tokens=sum(c[0] for c in calls), instructions_named=len(ops),
        named_elsewhere_too=ambiguous, naming_s=time.perf_counter() - t,
    )
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
            "window_counters", **moved, decode_steps=steps,
            decode_step_ms=(after["decode_s"] - before["decode_s"]) / max(1, steps) * 1e3,
            prefill_s=after["prefill_s"] - before["prefill_s"],
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
