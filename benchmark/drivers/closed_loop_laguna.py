"""Serving, callers that wait, a Laguna-S-2.1 configuration (full and sliding
grouped-query layers in one stack: 48 and 72 query heads over 8 KV heads, two
``(k, v)`` rings of two lifetimes a slot, the sliding one wrapping under a
window of 512, rope tables by kind, a gate per head, 256 experts top-10 of
which this chip holds 64; prompts of 12k-16k tokens admitted in chunks of
2,048 between decode steps): ``closed_loop.py``'s window to the letter, with a
build, a check, a warm-up and counters of its own.

As the other configurations' drivers do (PERF.md section 7(f) stays the
benchmark's debt), this driver loads a private copy of ``closed_loop.py`` and
gives it a view of ``serve_cell`` in which five functions are its own;
``warm_up`` is ``closed_loop_dots3``'s (one prompt of two chunks and a token),
and the instructions under a scope are named by ``closed_loop_keye``'s
``top_level_instructions``:

``start``           refuses, at once and before anything is built, a program
                    that knows no sliding grouped-query layers or no engine's
                    chunk; draws the weights in bfloat16 a leaf at a time and
                    hands the engine the tree to keep (the chip holds 10.07 GB
                    of weights once); the check is against ``reference_laguna``
                    at the cell's published widths;
``snapshot``        also carries the engine's rings-by-kind, chunk and
                    routed-FFN counters;
``traced_stretch``  also reads, from the program's ``serve_prefill`` (a span a
                    chunk) and ``serve_decode`` spans, the rows and pairs each
                    traced call read under its windows and over its full
                    rings and the pairs it routed, and from the compiled
                    programs' text which of their instructions lie under the
                    scopes ``odtp_swa``, ``odtp_full_attn`` and ``odtp_attn_gate``;
``finish``          hands the window's counter differences to the readers, and
                    prints them (``window_counters``); and decides ``correct``
                    without the tail's sample count, since this cell reports no
                    tail (a window ends some 70 requests and the harness holds
                    a p95 to 200: ``closed_loop_keye``'s note).
"""

from __future__ import annotations

import inspect
import math
import os
import time
import types

import numpy as np

from odbench import costs_laguna, manifest, program_obs, reference_laguna, serve_cell, traffic
from odbench.logits_check import rel_l2, served_rows

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_load = lambda name: manifest.load_module(os.path.join(_BENCH, "drivers", name))
top_level_instructions = _load("closed_loop_keye.py").top_level_instructions
warm_up = _load("closed_loop_dots3.py").warm_up
SCOPES = ("odtp_swa", "odtp_full_attn", "odtp_attn_gate")
CHUNK_SCOPE = "odtp_serve_prefill"  # the chunk program, whole
POOL_TOKENS = 4_000_000

# The limit, and a run is ``correct`` only inside it.
#
# LOGITS_REL_L2: engine logits (bf16 weights and activations; the prompt in
# chunks of 2,048 over the slot's four rings, a full layer's chunk a tile of 512
# ring rows at a time under an online softmax, a sliding layer's the band alone
# in blocks of 512 queries; decode through the rings by
# ``odtp_paged_decode_attn``, under its window over the sliding rings; the
# grouped matmuls over 64 held experts) against the float32 reference's full
# forward over the whole sequence, relative L2 over the rows compared: the
# last prompt position and each of 8 decode steps of two prompts in the
# traffic's range, one a whole number of chunks (14,336) and one ending inside
# a chunk (15,873).
#
# AS FIRST WRITTEN, before the cell's first run on the chip (PR 56): 6e-2,
# ``serve_cell``'s own (twice the larger of the dense cells' bf16-against-float32
# readings), since this block has no selection whose near-ties a reference would
# have to follow. THE FIRST RUN PRINTED ``correct: true`` under it, at 5.1e-2
# (seed 2900000017: the two prompts 4.0e-2 and 6.1e-2), too near it to stand
# over fresh seeds. So the limit was set once, from the readings
# (``tools/laguna_check_readings.py --faults``, same seed, the first prompt):
#
#                                   logits rel L2
#   the engine                      4.0e-2   (17 of its 18 rows 1.8-2.3e-2; one decode step's
#                                             row 1.0e-1: a token's tenth and eleventh expert
#                                             change places under bf16, which moves that row
#                                             by one expert's weighted output)
#   the reference, bfloat16         3.0e-2
#   the reference, float8_e4m3fn    5.5e-1   (has to fail)
#   sigmoid scores where softmax    1.4e-1
#   48 heads where 72               1.6e-1
#   the scaling 2.5 dropped         1.8e-1
#   the gate dropped                8.7e-1
#   the factor 1.4852 dropped       1.15
#   no ramp / the whole head
#   rotated / the tables swapped    1.32 / 1.39 / 1.36
#   a window of 511 / 513           2.6e-2 / 3.2e-2   (NOT CAUGHT: under the rounding)
#
# 1e-1: twice the engine's first reading over both prompts, under the smallest
# fault's (sigmoid scores) and under a fifth of the float8 reference's. A WINDOW
# OFF BY ONE ROW IS NOT CAUGHT HERE (one row of 512 under a softmax of random
# weights moves the logits less than bf16 does); the CPU tests hold both edges
# at a window of 5 (tests/test_laguna.py, tests/test_decode_kernels.py).
LOGITS_REL_L2 = 1e-1
COUNTERS = (
    "moe_pairs", "moe_experts_hit", "moe_max_pairs", "moe_pairs_all",
    "prefill_chunks", "prefill_chunk_tokens", "swa_rows_read", "full_rows_read",
    "kinds_bytes_moved",
)


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
        out = reference_laguna.forward(
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
    """The prompts in chunks, then decoding through the rings by kind, against
    the reference's full forward on the same tokens: logits, never tokens.
    Outside the window."""
    before = {name: getattr(engine, name) for name in COUNTERS}
    prompts, seqs, got = served_rows(cell, engine, seed)
    t_served = time.perf_counter()
    want = reference_rows(cell, engine.params, prompts, seqs)
    rel, per_prompt = rel_l2(got, want)
    ok, tolerance, failed = verdict(rel)
    report.line(
        "check", ok=ok, limits_not_met=failed, logits_rel_l2=rel, tolerance=tolerance,
        reference="reference_laguna", per_prompt_rel_l2=per_prompt,
        prompts=[len(p) for p in prompts], decode_steps=len(got[0]) - 1,
        rows_compared=sum(len(rows) for rows in got),
        **{name: getattr(engine, name) - before[name] for name in COUNTERS},
        served_s=t_served - t_process, reference_s=time.perf_counter() - t_served,
        setup_so_far_s=time.perf_counter() - t_process,
    )
    return ok


def build(cell, devices, seed, report, t_process):
    """The engine, after asking the program whether it runs the configuration
    at all (one that knows no ``laguna`` keys refuses its layer types; one
    whose engine takes no chunk of its own could admit no prompt). The weights
    are drawn in bfloat16 from the seed a leaf at a time (the float32 tree is
    20.1 GB) and the engine adopts the tree: the chip holds them once."""
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.models import llama
    from opendiloco_tpu.serve import ServeEngine

    try:
        cfg = llama.LlamaConfig.from_dict(cell.config)
        runs = (
            getattr(cfg, "sliding", False) and not cfg.latent
            and "prefill_chunk" in inspect.signature(ServeEngine.__init__).parameters
        )
    except (TypeError, ValueError) as e:
        runs = False
        report.line("refused", error=str(e))
    if not runs:
        raise RuntimeError(
            f"the program under test cannot run {cell.config_name}: it knows no sliding "
            "grouped-query layers beside full ones, or its engine no chunk of its own"
        )
    opts = cell.options["engine"]
    with jax.default_device(devices[0]):
        params = llama.init_params_leafwise(
            jax.random.key(traffic.jax_seed(seed)), cfg, jnp.bfloat16
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
        "built", params=costs_laguna.param_count(cell.config), slots=engine.num_slots,
        max_context=engine.max_context, decode_kernel=engine.decode_kernel,
        weights_adopted=engine.weights_adopted, weight_format="bf16, one copy",
        drawn_s=drawn_s, setup_so_far_s=time.perf_counter() - t_process,
    )
    return engine.cfg, engine


def start(cell, devices, seed, trace, report, t_process):
    """``serve_cell.start`` with this configuration's build and reference."""
    from opendiloco_tpu.serve import ContinuousBatcher

    cfg, engine = build(cell, devices, seed, report, t_process)
    sliding_rows = engine.cache_v.shape[-1]
    report.line(
        "laguna", params=costs_laguna.param_count(cell.config),
        published_params=costs_laguna.published_param_count(cell.config),
        weights_resident_bytes=engine.weights_resident_bytes,
        full_ring_bytes=engine.cache_k.nbytes, sliding_ring_bytes=engine.cache_v.nbytes,
        ring_bytes_by_shapes=costs_laguna.ring_bytes(
            cell.config, engine.num_slots, engine.max_context, sliding_rows),
        sliding_ring_rows=sliding_rows, window=cfg.sliding_window_size,
        chunk=cfg.q_chunk_size, kind_forms=engine.kind_forms,
        heads={"full": cfg.num_attention_heads, "sliding": cfg.swa_num_attention_heads,
               "kv": cfg.kv_heads},
        experts_held=cfg.held_experts, experts=cfg.num_experts,
        per_token=cfg.num_experts_per_tok, decode_kernel=engine.decode_kernel,
        decode_plan=engine.decode_plan_stats(),
    )
    check_ok = check_logits(cell, engine, seed, report, t_process)
    instrument = serve_cell.Instrument(engine) if trace == 1 else None
    return cfg, engine, check_ok, instrument, ContinuousBatcher(engine).start()


def snapshot(engine, batcher) -> dict:
    """``serve_cell.snapshot``, the rings', the chunks' and the routed FFN's
    counters."""
    return {
        **serve_cell.snapshot(engine, batcher),
        **{name: getattr(engine, name, 0) for name in COUNTERS},
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
    rings = shaped((engine.cache_k, engine.cache_v))
    ids = jax.ShapeDtypeStruct((1, engine.cfg.q_chunk_size), jnp.int32)
    texts = [
        engine._decode.lower(params, vec, vec, *rings).compile().as_text(),
        engine._chunk.lower(
            params, ids, scalar, scalar, scalar, jax.ShapeDtypeStruct((), jnp.bool_), vec,
            *rings, None,
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


def kind_calls(cell, capture, t0, t1) -> tuple:
    """Per traced decode step and prefill chunk, from the program's spans:
    [(query, row) pairs under the sliding layers' windows, the distinct rows
    they lie in, the pairs under the full layers' causal mask, their distinct
    rows, decode step?], each over the layers of its kind; and the pairs each
    call routed to the held experts with the experts they reached."""
    kinds = costs_laguna.layer_kinds(cell.config)
    sliding, full = kinds.count("sliding"), len(kinds) - kinds.count("sliding")
    window = cell.config["sliding_window"]
    calls, routed = [], []
    for name, decode in (("serve_prefill", 0), ("serve_decode", 1)):
        for args in program_obs.span_args(capture, name, t0, t1):
            if "swa_rows" in args:
                if decode:  # a query a slot: its pairs are its rows
                    swa_pairs, full_pairs = args["swa_rows"], args["full_rows"]
                else:  # a chunk's queries share the slot's rows
                    at = args["rows_before"] + 1 + np.arange(args["tokens"])
                    swa_pairs = sliding * int(np.minimum(at, window).sum())
                    full_pairs = full * int(at.sum())
                calls.append([swa_pairs, args["swa_rows"], full_pairs, args["full_rows"], decode])
            if "moe_pairs" in args:
                routed.append([args["moe_pairs"], args["moe_experts_hit"]])
    return calls, routed


def traced_stretch(cell, engine, batcher, compiles, report, keep_sending, meanwhile=None) -> dict:
    """``serve_cell.traced_stretch`` and ``kind_calls`` of its spans; then the
    names of the instructions under the scopes. Nothing where the spans carry
    no rows by kind."""
    traced = serve_cell.traced_stretch(
        cell, engine, batcher, compiles, report, keep_sending, meanwhile
    )
    stretch = traced["stretch"]
    calls, routed = kind_calls(cell, stretch.capture, stretch.t0, stretch.t1)
    t = time.perf_counter()
    ops, ambiguous = scope_instructions(engine) if calls else ({}, {})
    traced["counters"].update(
        traced_kind_calls=calls, dsa_ops=ops, traced_moe_calls=routed,
        # what ``prefill_chunk_device_ms`` counts its chunks from: a call's
        # fourth entry says whether it is a decode step
        traced_dsa_calls=[[c[0], c[2], c[1] + c[3], c[4]] for c in calls],
    )
    report.line(
        "traced_laguna", calls=len(calls), chunks=sum(1 for c in calls if not c[4]),
        window_pairs=sum(c[0] for c in calls), window_rows=sum(c[1] for c in calls),
        full_pairs=sum(c[2] for c in calls), full_rows=sum(c[3] for c in calls),
        instructions_named={scope: len(found) for scope, found in ops.items()},
        named_elsewhere_too=ambiguous, naming_s=time.perf_counter() - t,
    )
    report.line("traced_routed", calls=len(routed), pairs=sum(c[0] for c in routed),
                experts_hit=sum(c[1] for c in routed))
    return traced


def run(**kwargs):
    report = kwargs["report"]

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
