"""Serving, callers that wait, a dots3-note-prev configuration (two kinds of
latent attention in one stack: full layers under a learned indexer over a ring
of 576-value latent rows as long as the context, sliding layers of 513 rows
over a ring of 1,088-value rows that wraps, a gate per head; prompts of
22k-24k tokens admitted in chunks of 512 between decode steps over all three
rings): ``closed_loop.py``'s window to the letter, with a build, a check, a
warm-up and counters of its own.

As the other configurations' drivers do (PERF.md section 7(f) stays the
benchmark's debt), this driver loads a private copy of ``closed_loop.py`` and
gives it a view of ``serve_cell`` in which five functions are its own:

``start``           refuses, at once and before anything is built, a program
                    that knows no sliding latent layers; draws the weights in
                    bfloat16 a leaf at a time and hands the engine the tree to
                    keep (the chip holds 8.17 GB of weights once: a second copy
                    does not fit); asks the engine to keep each call's chosen
                    rows on the device; the check is against
                    ``reference_dots3`` at the cell's published widths, **along
                    the program's chosen rows** at the rows compared, with the
                    rows exchanged held to a small reference margin;
``warm_up``         one prompt that goes in chunks, decoded until the batcher
                    has published its gauges (every prompt goes in chunks);
``snapshot``        also carries the engine's indexer, chunk, latent-ring and
                    routed-FFN counters;
``traced_stretch``  also reads, from the program's ``serve_prefill`` (a span a
                    chunk) and ``serve_decode`` spans, the rows each traced
                    call scored, chose and read under its windows and the pairs
                    it routed, and from the compiled programs' text which of
                    their instructions lie under the scopes ``odtp_dsa_index``,
                    ``odtp_dsa_attn`` and ``odtp_swa``;
``finish``          hands the window's counter differences to the readers, and
                    prints them (``window_counters``); and decides ``correct``
                    without the tail's sample count, since this cell reports no
                    tail (a window ends a dozen or two requests and the harness
                    holds a p95 to 200: ``closed_loop_keye``'s note).
"""

from __future__ import annotations

import math
import os
import time
import types

import numpy as np

from odbench import costs_dots3, manifest, program_obs, reference_dots3, serve_cell, traffic
from odbench.logits_check import rel_l2, served_rows

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_keye = manifest.load_module(os.path.join(_BENCH, "drivers", "closed_loop_keye.py"))
# sets as row indices, and which instructions of a compiled program lie under
# a named scope: the Keye driver's (the second the granite driver's)
sets_as_rows, top_level_instructions = _keye.sets_as_rows, _keye.top_level_instructions
SCOPES = ("odtp_dsa_index", "odtp_dsa_attn", "odtp_swa")
CHUNK_SCOPE = "odtp_serve_prefill"  # the chunk program, whole
POOL_TOKENS = 4_000_000

# The limits, and a run is ``correct`` only inside every one (``verdict``).
#
# AS FIRST WRITTEN, before the cell's first run on the chip (PR 54), they were
# the Keye cell's, whose check this one repeats over another block: logits 2.5e-2,
# the median exchange distance over all sets 0.4 and over each part of them 0.7,
# a layer's share of rows differing 0.2. THE FIRST RUN PRINTED ``correct:
# false``: logits 5.6e-2 (seed 2900000017), every other limit met. The cause was
# looked for in what this PR adds and found in the limit: the readings tool
# (``tools/dots3_check_readings.py --faults``, same seed, the first prompt) read
# the *reference itself with bfloat16 operands* at 5.3e-2 against the float32
# one, and the engine at 5.8e-2 on every row alike (the last chunk's 5.3e-2, the
# decode steps' 4.8-7.0e-2): this block under the precision the configuration
# states rounds seven times what Keye's does (not separated further: PERF.md
# section 7). The same readings showed the pooled medians to be no measure here:
# the two full layers read 0.015 and 1.16 (engine; 0.013 and 0.83 the bfloat16
# reference), so a median over both is the gap between two clusters. So the
# limits were set once more, from the readings, and are these:
#
#                                   logits    a layer's median        a layer's largest share
#                                   rel L2    exchange distance       of rows differing
#   the engine                      5.8e-2    0.015, 1.16             7.3%
#   the reference, bfloat16         5.3e-2    0.013, 0.83             6.2%
#   the reference, float8_e4m3fn    3.9e-1    0.47,  3.88             47%    (has to fail)
#   an indexer without its ReLU     2.7e-1    2.77,  5.08             64%
#   index key and queries rotated
#   whole                           3.0e-1    5.10,  6.76             87%
#   the gate dropped                8.4e-1    0,     6.09             75%
#   the latents' rescale dropped    1.31      0,     7.54             91%
#   a window of 512 where 513       7.8e-3    0,     0                0      (NOT CAUGHT)
#
# LOGITS_REL_L2: engine logits (bf16 weights and activations; the prompt in
# chunks of 512 over the slot's three rings, each chunk's latent attention in
# the absorbed form a tile of ring rows at a time under an online softmax;
# decode through the rings by ``odtp_mla_decode_attn`` under the selection and
# under the window; the grouped matmuls over 32 held experts) against the
# float32 reference's full forward in the rebuilt form *reading, at the rows
# compared, the rows the engine chose*, relative L2 over those rows: the last
# prompt position and each of 8 decode steps of two prompts in the traffic's
# range, neither a whole number of chunks. Twice the engine's reading and under
# half of the smallest that has to fail by it (no ReLU).
#
# LAYER_MARGIN, LAYER_ROWS_DIFFERING: at a (row compared, full layer) the rows
# the reference would have chosen and the engine did not, and those the engine
# chose in their place, lie some way apart in the reference's scores (the set's
# exchange distance, over the root mean square of the query's scores; 0 where
# the sets are equal). **Each full layer's median of that** is held, over all
# its 18 sets and over each part of them by itself (a prompt's 9, the prompts'
# last tokens' 2: the last chunks' selection; the decode steps' 16), to
# LAYER_MARGIN: twice the engine's larger layer and 0.6 of the smallest fault's
# (float8); and each layer's share of chosen rows that differ from the
# reference's to LAYER_ROWS_DIFFERING (2.7 times the engine's, under half of
# float8's). bfloat16 scoring exchanges rows next to the 2,048th score, and after
# one layer of it the second full layer's queries exchange rows further off; an
# indexer that is wrong exchanges rows the reference is sure of, in every set.
#
# A WINDOW OFF BY ONE ROW IS NOT CAUGHT HERE: one row of 513 moves the logits
# by 8e-3, a seventh of the rounding. The CPU tests hold the window's edge at a
# window of 5 (tests/test_dots3.py, tests/test_decode_kernels.py), where a row is
# a fifth; PERF.md section 7 says what a check on the chip would need.
LOGITS_REL_L2 = 1.2e-1
LAYER_MARGIN = 2.4
LAYER_ROWS_DIFFERING = 2e-1
COUNTERS = (
    "moe_pairs", "moe_experts_hit", "moe_max_pairs", "moe_pairs_all",
    "dsa_rows_scored", "dsa_rows_selected", "dsa_index_bytes_read", "dsa_kv_bytes_read",
    "prefill_chunks", "prefill_chunk_tokens", "latent_rows_read", "latent_bytes_moved",
    "swa_rows_read", "swa_bytes_moved",
)
RESIDENT = ("index_cache_resident_bytes", "latent_cache_resident_bytes", "swa_cache_resident_bytes")


def served_rows_and_choices(cell, engine, seed):
    """``logits_check.served_rows`` and, beside each prompt's rows, the rows
    the engine's indexer chose in every full layer for each position compared
    [R, Lf, topk]: the prompt's last token's from its last chunk, then one a
    decode step."""
    topk = cell.config["index_topk"]
    kept = {"prefill": [], "decode": []}
    admit, decode_step = engine.admit, engine.decode_step

    def kept_admit(slot, prompt, **kw):
        out = admit(slot, prompt, **kw)
        kept["prefill"].append(sets_as_rows(np.asarray(engine.row_choices), topk))  # [Lf, K]
        return out

    def kept_decode_step(tokens, lens):
        out = decode_step(tokens, lens)
        kept["decode"].append(np.asarray(engine.row_choices))  # [Lf, S, T]
        return out

    engine.admit, engine.decode_step = kept_admit, kept_decode_step
    try:
        prompts, seqs, got = served_rows(cell, engine, seed)
    finally:
        del engine.admit, engine.decode_step  # the instance's; the class's stay
    choices = [
        np.stack([kept["prefill"][slot],
                  *(sets_as_rows(step[:, slot], topk) for step in kept["decode"])])
        for slot in range(len(prompts))
    ]
    return prompts, seqs, got, choices


def reference_rows(cell, params, prompts, seqs, choices=None, operands=None, faults=()):
    """The same rows from the reference's full forward over each sequence
    (padded to the check's ``pad_to``; every layer is causal, so the padding
    changes nothing before it), reading ``choices`` at the rows compared (None:
    its own) -> (rows, and per prompt over [R, Lf]: the rows in which its own
    sets differ from ``choices``, the exchanged rows' distance in its scores;
    and its own sets [R, Lf, T] bool). ``operands`` and ``faults`` are the
    readings tool's: a lower precision, one equation broken."""
    spec = cell.options["check"]
    steps, pad = int(spec["decode_steps"]), int(spec["pad_to"])
    rows, differing, distance, own = [], [], [], []
    for i, (prompt, seq) in enumerate(zip(prompts, seqs)):
        ids = np.zeros((1, pad), np.int32)
        ids[0, : len(seq)] = seq
        out = reference_dots3.forward(
            params, ids, cell.config, operands, faults,
            None if choices is None else choices[i], (len(prompt) - 1, steps + 1),
            with_choices=True,
        )
        rows.append(np.asarray(out[0])[0])
        own.append(np.asarray(out[1]))
        differing.append(np.asarray(out[2]))
        distance.append(np.asarray(out[3]))
    return rows, differing, distance, own


def exchanged(cell, differing, distance) -> dict:
    """Where the engine and the reference chose differently: how many of the
    chosen rows, at how many (row compared, full layer) sets, and the sets'
    exchange distance in the reference's scores, **layer by layer** (the full
    layers read apart: a median over both is no measure): each layer's median
    over all its sets and over each part of them that ``verdict`` holds by
    itself (a prompt's, the prompts' last tokens', the decode steps'), and each
    layer's share of chosen rows that differ. A prompt's arrays are [R, Lf]:
    row 0 its last token's (the last chunk's), then one a decode step."""
    topk = cell.config["index_topk"]
    pairs = sum(d.size for d in differing)
    rows = sum(int(d.sum()) for d in differing)
    every = np.concatenate(distance, axis=0)  # [sum R, Lf]
    by_layer = lambda a: np.round(np.median(a, axis=0), 4).tolist()
    return {
        "median_exchange_distance_by_layer": by_layer(every),
        "median_exchange_distance_by_layer_of_each_prompt": [by_layer(d) for d in distance],
        "median_exchange_distance_by_layer_last_tokens": by_layer(np.stack([d[0] for d in distance])),
        "median_exchange_distance_by_layer_decode_steps": by_layer(
            np.concatenate([d[1:] for d in distance])),
        "rows_differing_share_by_layer": np.round(
            np.concatenate(differing, axis=0).mean(axis=0) / topk, 5).tolist(),
        "sets_compared": pairs, "sets_differing": sum(int((d > 0).sum()) for d in differing),
        "rows_differing": rows, "rows_differing_share": rows / max(1, pairs * topk),
        "largest_exchange_distance": float(every.max()),
    }


def verdict(rel: float, chose: dict) -> tuple:
    """What decides the check, for the engine and for every control of the
    readings tool alike: the logits and ``exchanged``'s readings against the
    module's limits -> (ok, the limits as the ``check`` line prints them, the
    names of those not met)."""
    held = {
        "logits_rel_l2": (rel, LOGITS_REL_L2),
        "median_exchange_distance_of_a_layer": (
            max(chose["median_exchange_distance_by_layer"]), LAYER_MARGIN),
        "median_exchange_distance_of_a_layer_in_a_prompt": (
            max(max(m) for m in chose["median_exchange_distance_by_layer_of_each_prompt"]),
            LAYER_MARGIN),
        "median_exchange_distance_of_a_layer_last_tokens": (
            max(chose["median_exchange_distance_by_layer_last_tokens"]), LAYER_MARGIN),
        "median_exchange_distance_of_a_layer_decode_steps": (
            max(chose["median_exchange_distance_by_layer_decode_steps"]), LAYER_MARGIN),
        "rows_differing_share_of_a_layer": (
            max(chose["rows_differing_share_by_layer"]), LAYER_ROWS_DIFFERING),
    }
    failed = [name for name, (read, limit) in held.items()
              if not (math.isfinite(read) and read <= limit)]
    return not failed, {name: limit for name, (_, limit) in held.items()}, failed


def check_logits(cell, engine, seed, report, t_process) -> bool:
    """The prompts in chunks, then decoding through the three rings, against
    the reference's full forward on the same tokens along the engine's chosen
    rows: logits, never tokens; and the choices themselves. Outside the window."""
    before = {name: getattr(engine, name) for name in COUNTERS}
    prompts, seqs, got, choices = served_rows_and_choices(cell, engine, seed)
    t_served = time.perf_counter()
    want, differing, distance, _ = reference_rows(cell, engine.params, prompts, seqs, choices)
    rel, per_prompt = rel_l2(got, want)
    chose = exchanged(cell, differing, distance)
    ok, tolerance, failed = verdict(rel, chose)
    report.line(
        "check", ok=ok, limits_not_met=failed, logits_rel_l2=rel, **chose, tolerance=tolerance,
        reference="reference_dots3", per_prompt_rel_l2=per_prompt,
        prompts=[len(p) for p in prompts], decode_steps=len(got[0]) - 1,
        rows_compared=sum(len(rows) for rows in got),
        **{name: getattr(engine, name) - before[name] for name in COUNTERS},
        served_s=t_served - t_process, reference_s=time.perf_counter() - t_served,
        setup_so_far_s=time.perf_counter() - t_process,
    )
    return ok


def build(cell, devices, seed, report, t_process):
    """The engine, after asking the program whether it runs the configuration
    at all (one that reads no ``swa_*`` key would refuse the layer types, or
    build five like layers under this model's name). The weights are drawn in
    bfloat16 from the seed a leaf at a time (the float32 tree is 16.3 GB) and
    the engine adopts the tree: the chip holds them once."""
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.models import llama
    from opendiloco_tpu.serve import ServeEngine

    try:
        cfg = llama.LlamaConfig.from_dict(cell.config)
        runs = getattr(cfg, "sliding", False) and hasattr(llama, "init_params_leafwise")
    except (TypeError, ValueError) as e:
        runs = False
        report.line("refused", error=str(e))
    if not runs:
        raise RuntimeError(
            f"the program under test cannot run {cell.config_name}: its LlamaConfig "
            "knows no sliding latent layers beside full ones"
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
        )
        del params
    jax.block_until_ready(engine.params)
    report.line(
        "built", params=costs_dots3.param_count(cell.config), slots=engine.num_slots,
        max_context=engine.max_context, decode_kernel=engine.decode_kernel,
        weights_adopted=engine.weights_adopted, weight_format="bf16, one copy",
        drawn_s=drawn_s, setup_so_far_s=time.perf_counter() - t_process,
    )
    engine.keep_row_choices()  # before its programs are first traced
    return cfg, engine


def start(cell, devices, seed, trace, report, t_process):
    """``serve_cell.start`` with this configuration's build and reference."""
    from opendiloco_tpu.serve import ContinuousBatcher

    cfg, engine = build(cell, devices, seed, report, t_process)
    sliding_rows = engine.cache_v.shape[-1]
    report.line(
        "dots3", params=costs_dots3.param_count(cell.config),
        published_params=costs_dots3.published_param_count(cell.config),
        weights_resident_bytes=engine.weights_resident_bytes,
        full_ring_bytes=engine.cache_k.nbytes, sliding_ring_bytes=engine.cache_v.nbytes,
        index_cache_resident_bytes=engine.index_cache_resident_bytes,
        ring_bytes_by_shapes=costs_dots3.ring_bytes(
            cell.config, engine.num_slots, engine.max_context, sliding_rows),
        sliding_ring_rows=sliding_rows, window=cfg.sliding_window_size,
        chunk=cfg.q_chunk_size, topk=cfg.index_topk, latent_forms=engine.latent_forms,
        experts_held=cfg.held_experts, experts=cfg.num_experts,
        per_token=cfg.num_experts_per_tok, decode_kernel=engine.decode_kernel,
    )
    check_ok = check_logits(cell, engine, seed, report, t_process)
    instrument = serve_cell.Instrument(engine) if trace == 1 else None
    return cfg, engine, check_ok, instrument, ContinuousBatcher(engine).start()


def warm_up(engine, batcher, vocab, seed) -> None:
    """One prompt of two chunks and a token, decoded until the batcher has
    published its gauges once: the chunk program, the decode program and the
    one-time kernel probe have run (the check ran the first two already)."""
    rng = traffic.rng_for(seed, 5)
    n = min(2 * engine.cfg.q_chunk_size + 1, engine.max_context // 2)
    req = batcher.submit(
        rng.integers(traffic.FIRST_TOKEN, vocab, n).tolist(),
        max_new_tokens=batcher.gauge_every_steps + serve_cell.WARM_TOKENS_BEYOND_GAUGES,
    )
    if not req.wait(600.0) or req.error is not None:
        raise RuntimeError(f"warm-up request failed: {req.error}")


def snapshot(engine, batcher) -> dict:
    """``serve_cell.snapshot``, the indexer's, the chunks', the latent rings'
    and the routed FFN's counters, and what the rings hold."""
    return {
        **serve_cell.snapshot(engine, batcher),
        **{name: getattr(engine, name, 0) for name in (*COUNTERS, *RESIDENT)},
    }


def scope_instructions(engine) -> tuple:
    """The instructions under each of the three scopes in the engine's decode
    program and its chunk program, as the chip's compiler named them
    (``closed_loop_keye.dsa_instructions`` for these scopes): -> ({scope:
    instructions}, {scope: those that a program also has under the same name
    and shape outside the scope}); and under ``CHUNK_SCOPE`` the chunk
    program's instructions that the decode program has not."""
    import jax
    import jax.numpy as jnp

    shaped = lambda tree: jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    vec = jax.ShapeDtypeStruct((engine.num_slots,), jnp.int32)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    params = shaped(engine.params)
    rings = shaped((engine.cache_k, engine.cache_v, *engine._index))
    ids = jax.ShapeDtypeStruct((1, engine.cfg.q_chunk_size), jnp.int32)
    texts = [
        engine._decode.lower(params, vec, vec, *rings).compile().as_text(),
        engine._chunk.lower(
            params, ids, scalar, scalar, scalar, jax.ShapeDtypeStruct((), jnp.bool_), vec, *rings
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


def traced_stretch(cell, engine, batcher, compiles, report, keep_sending, meanwhile=None) -> dict:
    """``serve_cell.traced_stretch`` and, per traced decode step and prefill
    chunk, the (query, row) pairs its indexer scored, those it chose, the
    distinct full-layer rows they lie in, the pairs under the sliding layers'
    windows and their distinct rows, over layers, and the pairs it routed, as
    the program's spans carry them; then the names of the instructions under
    the three scopes. Nothing where the spans carry none."""
    traced = serve_cell.traced_stretch(
        cell, engine, batcher, compiles, report, keep_sending, meanwhile
    )
    stretch = traced["stretch"]
    kinds = costs_dots3.layer_kinds(cell.config)
    sliding, full = kinds.count("sliding"), len(kinds) - kinds.count("sliding")
    window = cell.config["sliding_window_size"]
    calls, routed = [], []
    for name, decode in (("serve_prefill", 0), ("serve_decode", 1)):
        for args in program_obs.span_args(stretch.capture, name, stretch.t0, stretch.t1):
            if "dsa_rows_scored" in args:
                if decode:  # a query a slot: its pairs are its rows
                    distinct, pairs = args["dsa_rows_scored"], args.get("swa_rows", 0)
                else:  # a chunk's queries share the slot's rows
                    at = args["rows_before"] + 1 + np.arange(args["tokens"])
                    distinct = full * (args["rows_before"] + args["tokens"])
                    pairs = sliding * int(np.minimum(at, window).sum())
                calls.append([args["dsa_rows_scored"], args["dsa_rows_selected"], distinct,
                              decode, pairs, args.get("swa_rows", 0)])
            if "moe_pairs" in args:
                routed.append([args["moe_pairs"], args["moe_experts_hit"]])
    t = time.perf_counter()
    ops, ambiguous = scope_instructions(engine) if calls else ({}, {})
    traced["counters"].update(
        traced_dots3_calls=calls, traced_dsa_calls=[c[:4] for c in calls], dsa_ops=ops,
        traced_moe_calls=routed,
    )
    report.line(
        "traced_dots3", calls=len(calls), chunks=sum(1 for c in calls if not c[3]),
        rows_scored=sum(c[0] for c in calls), rows_selected=sum(c[1] for c in calls),
        window_pairs=sum(c[4] for c in calls), window_rows=sum(c[5] for c in calls),
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
            "window_counters", **moved, **{name: after[name] for name in RESIDENT},
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
