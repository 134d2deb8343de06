"""Serving, callers that wait, a Solar-Open2 configuration (Kimi-delta
linear-attention layers: a gated delta rule under a decay for every key
channel, a ``[64, 128, 128]`` float32 state and a convolution's tail a layer
and slot, carried from chunk to chunk of a prompt and on to the decode step,
beside gated NoPE grouped-query layers over one ``(k, v)`` ring, and 320 routed
experts top-8 with a shared one, of which this chip holds 40; prompts of 2-4k
tokens admitted in chunks of 2,048 between decode steps, 1,024 outputs):
``closed_loop.py``'s window to the letter, with a build, a check, a warm-up
and counters of its own.

As the other configurations' drivers do (PERF.md section 7(f) stays the
benchmark's debt), this driver loads a private copy of ``closed_loop.py`` and
gives it a view of ``serve_cell`` in which five functions are its own;
``warm_up`` is ``closed_loop_dots3``'s (one prompt of two chunks and a token),
and the instructions under a scope are named by ``closed_loop_keye``'s
``top_level_instructions``:

``start``           refuses, at once and before anything is built, a program
                    that knows no kda layers (it would refuse the keys or run
                    those it knows as another model); draws the weights in
                    bfloat16 a leaf at a time and hands the engine the tree to
                    keep (the chip holds 6.62 GB of weights once); the check is
                    against ``reference_solar2`` at the cell's published widths;
``snapshot``        also carries the engine's kda, chunk and routed-FFN counters;
``traced_stretch``  also reads, from the program's ``serve_prefill`` (a span a
                    chunk) and ``serve_decode`` spans, the tokens each traced
                    call's kda layers took in each form and the pairs it
                    routed, and from the compiled programs' text which of their
                    instructions lie under the scopes ``odtp_kda``,
                    ``odtp_kda_conv`` and ``odtp_attn_gate``;
``finish``          hands the window's counter differences to the readers, and
                    prints them (``window_counters``); and decides ``correct``
                    without the tail's sample count, since this cell reports no
                    tail (a window ends some 100-150 requests and the harness
                    holds a p95 to 200: ``closed_loop_keye``'s note).
"""

from __future__ import annotations

import math
import os
import time
import types

import numpy as np

from odbench import costs_solar2, manifest, program_obs, reference_solar2, serve_cell, traffic
from odbench.logits_check import rel_l2, served_rows

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_load = lambda name: manifest.load_module(os.path.join(_BENCH, "drivers", name))
top_level_instructions = _load("closed_loop_keye.py").top_level_instructions
warm_up = _load("closed_loop_dots3.py").warm_up
SCOPES = ("odtp_kda", "odtp_kda_conv", "odtp_attn_gate")
CHUNK_SCOPE = "odtp_serve_prefill"  # the chunk program, whole
POOL_TOKENS = 4_000_000

# The limit, and a run is ``correct`` only inside it.
#
# LOGITS_REL_L2: engine logits (bf16 weights and activations; the prompt in two
# chunks of 2,048 through the gqa layer's ring and the kda layers' float32
# states and tails, a kda layer's chunk in blocks of 64 tokens with one
# triangular solve a block, the gqa layer's a tile of ring rows at a time under
# an online softmax; decode through the ring by ``odtp_paged_decode_attn`` and
# through the states one token a slot; the grouped matmuls over 40 held experts
# of 320) against the float32 reference's full forward over the whole sequence
# (the kda layers token by token, the convolution as shifted sums), relative L2
# over the rows compared: the last prompt position and each of 8 decode steps
# of two prompts in the traffic's range, one ending on a chunk's last row
# (4,096) and one ending inside a chunk and inside a block of the chunked form
# (2,987 = 2,048 + 14 blocks of 64 + 43).
#
# AS FIRST WRITTEN, before the cell's first run on the chip (PR 64): 6e-2,
# ``serve_cell``'s own (twice the larger of the dense cells' bf16-against-float32
# readings), since this block has no selection whose near-ties a reference would
# have to follow but the router's. THE FIRST RUN PRINTED ``correct: true`` under
# it, at 5.93e-2 (seed 2964000017: the two prompts 4.4e-2 and 7.1e-2), too near
# it to stand over fresh seeds. So the limit was set once, from the readings
# (``tools/solar2_check_readings.py --faults``, same seed, both prompts):
#
#                                   logits rel L2
#   the engine                      5.93e-2  (rows 3.9e-2 to 9.6e-2)
#   the reference, bfloat16         5.18e-2  (the precision the configuration states:
#                                             the engine reads what bf16 operands read)
#   the reference, float8_e4m3fn    9.0e-1   (has to fail)
#   the scale on q dropped          7.1e-2   (NOT CAUGHT: beside the rounding)
#   the decay after the update      1.6e-1
#   beta without its 2              4.4e-1
#   a second chunk with a zero tail / a zero state      4.6e-1 / 6.7e-1
#   the output norm over 8,192      5.1e-1
#   softmax scores in the router    5.3e-1
#   the kda gate dropped            7.0e-1
#   the 8 chosen among the 40 held  7.6e-1
#   one decay a head / the delta term dropped           8.5e-1 / 8.9e-1
#   the gqa gate dropped / a gate a head / SiLU dropped / a rotation in the
#   gqa layer / the shared expert dropped               1.07 / 1.16 / 1.11 / 1.33 / 1.33
#   the L2 norms dropped            not finite
#   the bias weighed                4.1e-2   (NOT CAUGHT: under the rounding)
#   a bfloat16 state                5.1e-3   (NOT CAUGHT: under the rounding)
#
# OVER FRESH SEEDS the engine read 4.8e-2 to 7.8e-2 (the first set of six, each
# both prompts: 0.048, 0.058, 0.052, 0.075, 0.062, 0.078; my chip runs, PR 64):
# what the seed draws, the weights and the prompts, moves the reading more than
# anything the program does, as the bfloat16 reference's 5.2e-2 beside the
# engine's 5.9e-2 on one seed says. So the limit lies between the two readings
# it is set from, the engine's largest over its seeds (7.8e-2) and the float8
# reference's (9.0e-1), with room on both sides: 1.5e-1, 1.9 times the one and a
# sixth of the other, under every fault's but the three named (the decay after
# the update sits at it, 1.6e-1, and is held by the CPU tests like the three).
# THE SCALE ON q, THE BIAS WEIGHED AND A BFLOAT16 STATE ARE NOT CAUGHT HERE (each
# moves the logits of random weights less than bf16 operands do); the CPU tests
# hold all of them in float32 (tests/test_solar2.py). The limit stood at 1e-1
# for the first set of six seeds (every run inside it; the largest reading 22%
# under it was too near to stand over the driver's seeds) and was moved once.
LOGITS_REL_L2 = 1.5e-1
COUNTERS = (
    "moe_pairs", "moe_experts_hit", "moe_max_pairs", "moe_pairs_all",
    "prefill_chunks", "prefill_chunk_tokens", "kda_step_tokens", "kda_chunk_tokens",
    "kda_blocks_solved", "kda_state_bytes_moved",
)
RESIDENT = ("kda_state_resident_bytes", "kda_tail_resident_bytes")


def reference_rows(cell, params, prompts, seqs, operands=None, faults=()):
    """The same rows from the reference's full forward over each sequence
    (padded to the check's ``pad_to``; every layer is causal, so the padding
    changes nothing before it). ``operands`` and ``faults`` are the readings
    tool's: a lower precision, one equation broken."""
    spec = cell.options["check"]
    steps, pad = int(spec["decode_steps"]), int(spec["pad_to"])
    rows = []
    for prompt, seq in zip(prompts, seqs):
        ids = np.zeros((1, pad), np.int32)
        ids[0, : len(seq)] = seq
        out = reference_solar2.forward(
            params, ids, cell.config, operands, faults, (len(prompt) - 1, steps + 1)
        )
        rows.append(np.asarray(out)[0])
    return rows


def verdict(rel: float) -> tuple:
    """What decides the check, for the engine and for every control of the
    readings tool alike -> (ok, the limits as the ``check`` line prints them,
    the names of those not met)."""
    held = {"logits_rel_l2": (rel, LOGITS_REL_L2)}
    failed = [name for name, (read, limit) in held.items()
              if not (math.isfinite(read) and read <= limit)]
    return not failed, {name: limit for name, (_, limit) in held.items()}, failed


def check_logits(cell, engine, seed, report, t_process) -> bool:
    """The prompts in chunks through the ring, the states and the tails, then
    decoding through them, against the reference's full forward on the same
    tokens: logits, never tokens. Outside the window."""
    before = {name: getattr(engine, name) for name in COUNTERS}
    prompts, seqs, got = served_rows(cell, engine, seed)
    t_served = time.perf_counter()
    want = reference_rows(cell, engine.params, prompts, seqs)
    rel, per_prompt = rel_l2(got, want)
    ok, tolerance, failed = verdict(rel)
    report.line(
        "check", ok=ok, limits_not_met=failed, logits_rel_l2=rel, tolerance=tolerance,
        reference="reference_solar2", per_prompt_rel_l2=per_prompt,
        prompts=[len(p) for p in prompts], decode_steps=len(got[0]) - 1,
        rows_compared=sum(len(rows) for rows in got),
        **{name: getattr(engine, name) - before[name] for name in COUNTERS},
        served_s=t_served - t_process, reference_s=time.perf_counter() - t_served,
        setup_so_far_s=time.perf_counter() - t_process,
    )
    return ok


def build(cell, devices, seed, report, t_process):
    """The engine, after asking the program whether it runs the configuration
    at all: one that knows no ``solar_open2`` keys reads the ones it knows as a
    plain stack of four attention layers over dense SwiGLUs and would serve
    another model. The weights are drawn in bfloat16 from the seed a leaf at a
    time (the float32 tree is 13.2 GB), by the device's own bit generator, and
    the engine adopts the tree: the chip holds them once."""
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.models import llama
    from opendiloco_tpu.serve import ServeEngine

    try:
        cfg = llama.LlamaConfig.from_dict(cell.config)
        runs = getattr(cfg, "kda", False)
    except (TypeError, ValueError) as e:
        runs = False
        report.line("refused", error=str(e))
    if not runs:
        raise RuntimeError(
            f"the program under test cannot run {cell.config_name}: it knows no kda "
            "linear-attention layers"
        )
    opts = cell.options["engine"]
    with jax.default_device(devices[0]):
        # the chip's own generator (``rbg``): threefry over arrays of this size
        # compiles for minutes on an empty cache (PERF.md section 4, PR 61)
        params = llama.init_params_leafwise(
            jax.random.key(traffic.jax_seed(seed), impl="rbg"), cfg, jnp.bfloat16
        )
        jax.block_until_ready(params)
        drawn_s = time.perf_counter() - t_process
        engine = ServeEngine(
            cfg, params, num_slots=int(opts["num_slots"]), max_context=int(opts["max_context"]),
            prefill_buckets=tuple(opts["prefill_buckets"]), adopt_params=True,
            prefill_chunk=int(opts["prefill_chunk"]),
        )
        del params
    jax.block_until_ready(engine.params)
    report.line(
        "built", params=costs_solar2.param_count(cell.config), slots=engine.num_slots,
        max_context=engine.max_context, decode_kernel=engine.decode_kernel,
        weights_adopted=engine.weights_adopted, weight_format="bf16, one copy",
        drawn_s=drawn_s, setup_so_far_s=time.perf_counter() - t_process,
    )
    return engine.cfg, engine


def start(cell, devices, seed, trace, report, t_process):
    """``serve_cell.start`` with this configuration's build and reference."""
    from opendiloco_tpu.serve import ContinuousBatcher

    cfg, engine = build(cell, devices, seed, report, t_process)
    report.line(
        "solar2", params=costs_solar2.param_count(cell.config),
        published_params=costs_solar2.published_param_count(cell.config),
        weights_resident_bytes=engine.weights_resident_bytes,
        kv_ring_bytes=engine.cache_k.nbytes + engine.cache_v.nbytes,
        kda_state_bytes=engine.kda_state_resident_bytes,
        kda_tail_bytes=engine.kda_tail_resident_bytes,
        slot_bytes_by_shapes=costs_solar2.slot_bytes(cell.config, engine.max_context),
        layers={"kda": cfg.num_kda_layers, "gqa": cfg.num_attention_layers},
        chunk=cfg.q_chunk_size, kda_forms=engine.kda_forms,
        experts_held=cfg.held_experts, experts=cfg.num_experts,
        per_token=cfg.num_experts_per_tok, decode_kernel=engine.decode_kernel,
        decode_plan=engine.decode_plan_stats(),
    )
    check_ok = check_logits(cell, engine, seed, report, t_process)
    instrument = serve_cell.Instrument(engine) if trace == 1 else None
    return cfg, engine, check_ok, instrument, ContinuousBatcher(engine).start()


def snapshot(engine, batcher) -> dict:
    """``serve_cell.snapshot``, the kda layers', the chunks' and the routed
    FFN's counters, and what the states and the tails hold."""
    return {
        **serve_cell.snapshot(engine, batcher),
        **{name: getattr(engine, name, 0) for name in (*COUNTERS, *RESIDENT)},
    }


def scope_instructions(engine) -> tuple:
    """The instructions under each scope in the engine's decode program and
    its chunk program, as the chip's compiler named them -> ({scope:
    instructions}, {scope: those that a program also has under the same name
    and shape outside the scope}); and under ``CHUNK_SCOPE`` the chunk
    program's instructions that the decode program has not."""
    import jax
    import jax.numpy as jnp

    shaped = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    vec = jax.ShapeDtypeStruct((engine.num_slots,), jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    params = shaped(engine.params)
    rings = shaped((engine.cache_k, engine.cache_v, *engine._kda))
    ids = jax.ShapeDtypeStruct((1, engine.cfg.q_chunk_size), jnp.int32)
    texts = [
        engine._decode.lower(params, vec, vec, *rings).compile().as_text(),
        engine._chunk.lower(
            params, ids, scalar, scalar, scalar, jax.ShapeDtypeStruct((), jnp.bool_), vec, *rings,
        ).compile().as_text(),
    ]
    named, elsewhere = {}, {}
    for scope in SCOPES:
        inside, outside = set(), set()
        for text in texts:
            ours, others = top_level_instructions(text, scope)
            inside |= ours
            outside |= others
        named[scope], elsewhere[scope] = sorted(inside), sorted(inside & outside)
    chunk, _ = top_level_instructions(texts[1], CHUNK_SCOPE)
    step = set().union(*top_level_instructions(texts[0], CHUNK_SCOPE))
    named[CHUNK_SCOPE], elsewhere[CHUNK_SCOPE] = sorted(chunk - step), sorted(chunk & step)
    return named, elsewhere


def layer_calls(capture, t0, t1) -> tuple:
    """Per traced decode step and prefill chunk, from the program's spans
    (``ServeEngine._count_kda``'s attributes, each over the kda layers): [kda
    tokens of a step, of a chunk, blocks solved, decode step?]; and the pairs
    each call routed to the held experts with the experts they reached."""
    calls, routed = [], []
    for name, decode in (("serve_prefill", 0), ("serve_decode", 1)):
        for args in program_obs.span_args(capture, name, t0, t1):
            if "kda_step_tokens" in args:
                calls.append([
                    args["kda_step_tokens"], args["kda_chunk_tokens"], args["kda_blocks_solved"],
                    decode,
                ])
            if "moe_pairs" in args:
                routed.append([args["moe_pairs"], args["moe_experts_hit"]])
    return calls, routed


def traced_stretch(cell, engine, batcher, compiles, report, keep_sending, meanwhile=None) -> dict:
    """``serve_cell.traced_stretch`` and ``layer_calls`` of its spans; then the
    names of the instructions under the scopes. Nothing where the spans carry
    no kda layers' work."""
    traced = serve_cell.traced_stretch(
        cell, engine, batcher, compiles, report, keep_sending, meanwhile
    )
    stretch = traced["stretch"]
    calls, routed = layer_calls(stretch.capture, stretch.t0, stretch.t1)
    t = time.perf_counter()
    ops, ambiguous = scope_instructions(engine) if calls else ({}, {})
    traced["counters"].update(
        traced_kind_calls=calls, dsa_ops=ops, traced_moe_calls=routed,
        # what ``prefill_chunk_device_ms`` counts its chunks from: a call's
        # fourth entry says whether it is a decode step
        traced_dsa_calls=calls,
    )
    report.line(
        "traced_solar2", calls=len(calls), chunks=sum(1 for c in calls if not c[3]),
        kda_step_tokens=sum(c[0] for c in calls), kda_chunk_tokens=sum(c[1] for c in calls),
        kda_blocks_solved=sum(c[2] for c in calls),
        instructions_named={scope: len(found) for scope, found in ops.items()},
        named_elsewhere_too=ambiguous, naming_s=time.perf_counter() - t,
    )
    report.line("traced_routed", calls=len(routed), pairs=sum(c[0] for c in routed),
                experts_hit=sum(c[1] for c in routed))
    return traced


def run(**kwargs):
    report = kwargs["report"]

    config = kwargs["cell"].config
    held_a_call = config["n_routed_experts"] * config["num_hidden_layers"]  # held experts, over layers

    def finish(*, before, after, check_ok, batcher, extra_counters=None, **rest):
        """The window's counter differences, to the readers and onto a line;
        ``correct`` as ``serve_cell.finish`` decides it but for the tail's
        sample count (the module's note: the cell reports no tail)."""
        moved = {name: after[name] - before[name] for name in COUNTERS}
        steps = max(1, after["decode_steps"] - before["decode_steps"])
        chunks = max(1, moved["prefill_chunks"])
        report.line(
            "window_counters", **moved,
            decode_steps=after["decode_steps"] - before["decode_steps"],
            decode_step_ms=(after["decode_s"] - before["decode_s"]) / steps * 1e3,
            prefill_s=after["prefill_s"] - before["prefill_s"],
            prefill_ms_per_chunk=(after["prefill_s"] - before["prefill_s"]) / chunks * 1e3,
            chunks_per_step=moved["prefill_chunks"] / steps,
            held_pairs_a_step_and_expert=moved["moe_pairs"]
            / ((steps + moved["prefill_chunks"]) * held_a_call),
        )
        out = serve_cell.finish(
            before=before, after=after, check_ok=check_ok, batcher=batcher,
            extra_counters={**(extra_counters or {}), **moved}, **rest,
        )
        out["correct"] = bool(check_ok and out["failed"] == 0 and batcher.loop_error is None)
        return out

    loop = manifest.load_module(os.path.join(_BENCH, "drivers", "closed_loop.py"))
    longest = int(kwargs["cell"].traffic["prompt_tokens"]["max"])
    loop.POOL = max(256, min(loop.POOL, POOL_TOKENS // longest))
    view = dict(vars(serve_cell))
    view.update(start=start, warm_up=warm_up, snapshot=snapshot,
                traced_stretch=traced_stretch, finish=finish)
    loop.serve_cell = types.SimpleNamespace(**view)
    return loop.run(**kwargs)
