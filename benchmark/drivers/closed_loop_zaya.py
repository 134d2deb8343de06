"""Serving, callers that wait, a ZAYA1 configuration (CCA attention with a
per-slot state beside the ring, a router MLP fed by the layer before, top-1 of
16 experts): ``closed_loop.py``'s window to the letter, with a check and
counters of its own.

As the other routed cells' drivers do (PERF.md section 7(f) stays the
benchmark's debt), this driver loads a private copy of ``closed_loop.py`` and
gives it a view of ``serve_cell`` in which four functions are its own:

``start``           refuses, at once and before anything is built, a program
                    that knows no CCA; asks the engine to keep each call's
                    expert choices on the device; the check is against
                    ``reference_zaya`` at the cell's published widths, **along
                    the program's choices**, with every differing choice held
                    to a small reference margin;
``snapshot``        also carries the engine's CCA-state and routed-FFN counters;
``traced_stretch``  also reads, from the program's ``serve_prefill`` and
                    ``serve_decode`` spans, what each traced call put through
                    CCA's projections and routed to the experts, and from the
                    compiled programs' text which of their instructions lie
                    under the ``odtp_cca`` scope;
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

from odbench import costs_zaya, manifest, program_obs, reference_zaya, serve_cell
from odbench.logits_check import rel_l2, served_rows

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# which instructions of a compiled program lie under a named scope: the
# granite driver's, told this configuration's scope
top_level_instructions = manifest.load_module(
    os.path.join(_BENCH, "drivers", "closed_loop_granite_h.py")
).top_level_instructions
_SCOPE = "odtp_cca"

# Two limits, and a run is ``correct`` only inside both.
#
# LOGITS_REL_L2: engine logits (bf16 weights and activations; prefill with the
# convolutions as shifts over the bucket, decode through the ring, the decode
# kernel and the per-slot state; the grouped matmuls) against the float32
# reference's full forward *following the engine's expert choices*, relative
# L2 over the rows compared: the last prompt position and each of 8 decode
# steps of two prompts (400 and 900 tokens, padded into buckets 512 and 1,024).
# Top-1 makes a flipped choice a whole FFN of one token and layer, so a
# reference that chose for itself would read a token's flip as an error of the
# arithmetic; along the engine's choices the number is the arithmetic's alone.
#
# CHOICE_MARGIN: every (token, layer) of those sequences where the reference,
# on its own state along the engine's choices, would have chosen another
# expert than the engine did has to be a near tie *in the reference*: its
# largest biased score p + b_sel less its second at most this. A router that
# is wrong (a layer before not fed in, a bias weighed or left out, a missing
# GELU) differs where the reference is sure of itself; rounding differs only
# where it is not. ``choices_differing_share`` reports how many did.
#
# Readings on the chip, 10 layers at the published widths (PR 37: the check
# lines of fifteen seeds, ``tools/zaya_check_readings.py`` on two):
#
#                                      logits_rel_l2        largest differing margin  share differing
#   the engine                         1.13e-2 to 1.26e-2   9.1e-3 to 2.0e-2          0.84% to 1.14%
#   the reference, bfloat16 operands   8.2e-3, 8.5e-3       1.1e-2, 1.5e-2            0.59%, 0.74%
#   the reference, float8_e4m3fn       2.5e-1, 2.7e-1       3.5e-1, 3.8e-1            15.4%, 16.2%   (has to fail)
#
# and what an engine with one equation broken would read (the reference with
# that fault and bfloat16 operands against the sound float32 reference along
# the faulty one's choices, ``--faults``, one seed):
#
#   values that do not look back       1.28                 0.91
#   the q-k means left out             1.20                 0.91
#   the first convolution left out     1.02                 0.86
#   no residual scaling                6.0e-1               0.55
#   the second convolution's tap back  4.9e-1               0.55
#   a router not fed by the layer before  2.3e-1            0.66
#   no temperature on k                1.8e-1               0.24
#   the whole head rotated             1.6e-1               0.21
#   b_sel weighed as well as chosen by 3.7e-2               5.0e-2   (not caught here)
#
# The engine reads 1.4 times the bfloat16 reference because it rounds more
# than its matmuls' operands: the residual stream between the twenty branches,
# the convolutions' and the normalisation's intermediates, the per-slot state.
# Each limit lies between its two readings with room on both sides:
# LOGITS_REL_L2 is 3.2 times the engine's largest and a sixth of float8's;
# CHOICE_MARGIN is 4 times the engine's largest (the largest of 130 near ties
# among 13,160 choices is an extreme value and moves from seed to seed) and
# under a quarter of float8's. Every fault but one fails both. A selection
# bias drawn N(0, (0.2/16)^2) and weighed beside probabilities near 0.3 moves
# a token's FFN by 4% and passes under both, as the like fault does in the
# GLM cell: that one is the CPU tests' (``tests/test_zaya.py``: float32, a
# limit of 1e-4), not this check's. On the CPU at a small size a router not
# fed by the layer before moves no logit along its own choices at all and is
# caught by the margin alone (0.38): why there are two limits.
LOGITS_REL_L2 = 4e-2
CHOICE_MARGIN = 8e-2
COUNTERS = (
    "moe_pairs", "moe_experts_hit", "moe_max_pairs", "moe_pairs_all",
    "cca_tokens", "cca_state_bytes_moved",
)
RESIDENT = "cca_state_resident_bytes"


def served_rows_and_choices(cell, engine, seed):
    """``logits_check.served_rows`` and, beside each prompt's rows, the expert
    the engine's programs chose for every token it was fed in every layer
    [tokens, L]: the prompt's from its prefill, then one a decode step."""
    chosen = {"prefill": [], "decode": []}
    admit, decode_step = engine.admit, engine.decode_step

    def kept_admit(slot, prompt, **kw):
        out = admit(slot, prompt, **kw)
        chosen["prefill"].append(np.asarray(engine.expert_choices)[:, : len(prompt), 0].T)
        return out

    def kept_decode_step(tokens, lens):
        out = decode_step(tokens, lens)
        chosen["decode"].append(np.asarray(engine.expert_choices)[:, :, 0])  # [L, S]
        return out

    engine.admit, engine.decode_step = kept_admit, kept_decode_step
    try:
        prompts, seqs, got = served_rows(cell, engine, seed)
    finally:
        del engine.admit, engine.decode_step  # the instance's; the class's stay
    choices = [
        np.concatenate([chosen["prefill"][slot], *(step[None, :, slot] for step in chosen["decode"])])
        for slot in range(len(prompts))
    ]
    return prompts, seqs, got, choices


def reference_rows(cell, params, prompts, seqs, choices=None, operands=None, faults=()):
    """The same rows from the reference's full forward over each sequence
    (padded to the check's ``pad_to``; every layer is causal, so the padding
    changes nothing before it), following ``choices`` (None: its own) ->
    (rows, the reference's own choices [tokens, L] per prompt, their margins).
    ``operands`` and ``faults`` are the readings tool's: a lower precision,
    one equation broken."""
    import jax

    spec = cell.options["check"]
    steps, pad = int(spec["decode_steps"]), int(spec["pad_to"])
    layers = cell.config["num_hidden_layers"]
    ref_fn = jax.jit(  # ``follow`` None: the walk's own choices (a trace of its own)
        lambda p, ids, follow, first: reference_zaya.forward(
            p, ids, cell.config, operands, faults, follow, (first, steps + 1), with_choices=True
        )
    )
    rows, own, margins = [], [], []
    for i, (prompt, seq) in enumerate(zip(prompts, seqs)):
        ids = np.zeros((1, pad), np.int32)
        ids[0, : len(seq)] = seq
        follow = None
        if choices is not None:
            follow = np.zeros((1, pad, layers), np.int32)
            follow[0, : len(seq)] = choices[i]
        out = ref_fn(params, ids, follow, np.int32(len(prompt) - 1))
        rows.append(np.asarray(out[0])[0])
        own.append(np.asarray(out[1])[0, : len(seq)])
        margins.append(np.asarray(out[2])[0, : len(seq)])
    return rows, own, margins


def differing(choices, own, margins) -> dict:
    """Where the engine and the reference chose differently: how many of the
    (token, layer) pairs, and the largest reference margin among them."""
    pairs = sum(c.size for c in choices)
    flips = [m[c != o] for c, o, m in zip(choices, own, margins)]
    count = sum(f.size for f in flips)
    return {
        "choices_compared": pairs, "choices_differing": count,
        "choices_differing_share": count / max(1, pairs),
        "largest_differing_margin": max((float(f.max()) for f in flips if f.size), default=0.0),
    }


def check_logits(cell, engine, seed, report, t_process) -> bool:
    """Prefill, then decoding through ring and state, against the reference's
    full forward on the same tokens along the engine's expert choices: logits,
    never tokens; and the choices themselves. Outside the window."""
    prompts, seqs, got, choices = served_rows_and_choices(cell, engine, seed)
    want, own, margins = reference_rows(cell, engine.params, prompts, seqs, choices)
    rel, per_prompt = rel_l2(got, want)
    chose = differing(choices, own, margins)
    ok = (
        math.isfinite(rel) and rel <= LOGITS_REL_L2
        and chose["largest_differing_margin"] <= CHOICE_MARGIN
    )
    report.line(
        "check", ok=ok, logits_rel_l2=rel, **chose,
        tolerance={"logits_rel_l2": LOGITS_REL_L2, "largest_differing_margin": CHOICE_MARGIN},
        reference="reference_zaya", per_prompt_rel_l2=per_prompt,
        prompts=[len(p) for p in prompts], decode_steps=len(got[0]) - 1,
        rows_compared=sum(len(rows) for rows in got),
        setup_so_far_s=time.perf_counter() - t_process,
    )
    return ok


def build(cell, devices, seed, report, t_process):
    """``serve_cell.build`` after asking the program whether it runs the
    configuration at all (one that reads no ``cca_time0`` would build ten
    layers of ordinary attention over heads of 256 under this model's name),
    with the engine told to keep its programs' expert choices."""
    from opendiloco_tpu.models.llama import LlamaConfig

    try:
        runs = getattr(LlamaConfig.from_dict(cell.config), "cca", False)
    except (TypeError, ValueError) as e:
        runs = False
        report.line("refused", error=str(e))
    if not runs:
        raise RuntimeError(
            f"the program under test cannot run {cell.config_name}: its LlamaConfig "
            "reads no cca_time0 and has no CCA"
        )
    cfg, engine = serve_cell.build(cell, devices, seed, report, t_process)
    engine.keep_expert_choices()  # before its programs are first traced
    return cfg, engine


def start(cell, devices, seed, trace, report, t_process):
    """``serve_cell.start`` with this configuration's reference in the check."""
    from opendiloco_tpu.serve import ContinuousBatcher

    cfg, engine = build(cell, devices, seed, report, t_process)
    report.line(
        "zaya", params=costs_zaya.param_count(cell.config),
        kv_bytes_per_token=costs_zaya.kv_bytes_per_token(cell.config),
        kv_cache_bytes=engine.cache_k.nbytes + engine.cache_v.nbytes,
        cca_state_resident_bytes=engine.cca_state_resident_bytes,
        cca_state_bytes_per_slot=costs_zaya.state_bytes_per_slot(cell.config),
        weights_resident_bytes=engine.weights_resident_bytes,
        experts=cell.config["num_experts"], per_token=cell.config["num_experts_per_tok"],
    )
    check_ok = check_logits(cell, engine, seed, report, t_process)
    instrument = serve_cell.Instrument(engine) if trace == 1 else None
    return cfg, engine, check_ok, instrument, ContinuousBatcher(engine).start()


def snapshot(engine, batcher) -> dict:
    """``serve_cell.snapshot``, CCA's and the routed FFN's counters, and what
    the state holds."""
    return {
        **serve_cell.snapshot(engine, batcher),
        **{name: getattr(engine, name, 0) for name in (*COUNTERS, RESIDENT)},
    }


def cca_instructions(engine) -> tuple:
    """The instructions under the ``odtp_cca`` scope in the engine's prefill
    programs (one per bucket) and its decode program, as the chip's compiler
    named them: the programs are lowered and compiled again, which the
    persistent cache answers. After the traced stretch, so that neither the
    window nor the stretch sees it. -> (the instructions, those of them that
    another of the programs has under the same name and shape outside the
    scope)."""
    import jax
    import jax.numpy as jnp

    shaped = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    vec = jax.ShapeDtypeStruct((engine.num_slots,), jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    params = shaped(engine.params)
    texts = [
        engine._decode.lower(
            params, vec, vec, *shaped((engine.cache_k, engine.cache_v, *engine._cca))
        ).compile().as_text()
    ]
    for bucket in engine.prefill_buckets:
        ids = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
        texts.append(engine._prefill.lower(params, ids, scalar).compile().as_text())
    inside, outside = set(), set()
    for text in texts:
        ours, others = top_level_instructions(text, _SCOPE)
        inside |= ours
        outside |= others
    return sorted(inside), sorted(inside & outside)


def traced_stretch(cell, engine, batcher, compiles, report, keep_sending, meanwhile=None) -> dict:
    """``serve_cell.traced_stretch`` and, per traced prefill and decode step,
    the tokens and sequences it put through CCA's projections and the pairs it
    routed (with the experts they reached), as the program's spans carry them;
    then the names of the instructions under the scope. Nothing where the
    spans carry none."""
    traced = serve_cell.traced_stretch(
        cell, engine, batcher, compiles, report, keep_sending, meanwhile
    )
    stretch = traced["stretch"]
    calls, routed = [], []
    for name, decode in (("serve_prefill", 0), ("serve_decode", 1)):
        for args in program_obs.span_args(stretch.capture, name, stretch.t0, stretch.t1):
            if "cca_tokens" in args:
                calls.append([args["cca_tokens"], args["cca_tokens"] if decode else 1, decode])
            if "moe_pairs" in args:
                routed.append([args["moe_pairs"], args["moe_experts_hit"]])
    t = time.perf_counter()
    ops, ambiguous = cca_instructions(engine) if calls else ([], [])
    traced["counters"].update(traced_cca_calls=calls, cca_ops=ops, traced_moe_calls=routed)
    report.line(
        "traced_cca", calls=len(calls), prefills=sum(1 for c in calls if not c[2]),
        tokens=sum(c[0] for c in calls), instructions_named=len(ops),
        named_elsewhere_too=ambiguous, naming_s=time.perf_counter() - t,
    )
    report.line("traced_routed", calls=len(routed), pairs=sum(c[0] for c in routed),
                experts_hit=sum(c[1] for c in routed))
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
            cca_state_bytes_per_decode_step=2 * after[RESIDENT],
        )
        return serve_cell.finish(
            before=before, after=after, extra_counters={**(extra_counters or {}), **moved}, **rest
        )

    loop = manifest.load_module(os.path.join(_BENCH, "drivers", "closed_loop.py"))
    view = dict(vars(serve_cell))
    view.update(start=start, snapshot=snapshot, traced_stretch=traced_stretch, finish=finish)
    loop.serve_cell = types.SimpleNamespace(**view)
    return loop.run(**kwargs)
