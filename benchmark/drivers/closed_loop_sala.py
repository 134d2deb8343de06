"""Serving, callers that wait, a MiniCPM-SALA configuration (lightning
linear-attention layers, a decaying ``[32, 128, 128]`` float32 state a layer
and slot carried from chunk to chunk of a prompt, beside NoPE grouped-query
layers that read 64 chosen blocks of 64 rows under a selection scored on pooled
keys: a pooled-key ring beside K and V; constant scalings of the embedding,
the residual branches and the head; packed documents of 28-32k tokens admitted
in chunks of 2,048 between decode steps): ``closed_loop.py``'s window to the
letter, with a build, a check, a warm-up and counters of its own.

As the other configurations' drivers do (PERF.md section 7(f) stays the
benchmark's debt), this driver loads a private copy of ``closed_loop.py`` and
gives it a view of ``serve_cell`` in which five functions are its own;
``warm_up`` is ``closed_loop_dots3``'s (one prompt of two chunks and a token),
and the instructions under a scope are named by ``closed_loop_granite_h``'s
``top_level_instructions``:

``start``           refuses, at once and before anything is built, a program
                    that knows no lightning layers or no selection by blocks
                    (it would run the keys it knows as another model); draws
                    the weights in bfloat16 a leaf at a time and hands the
                    engine the tree to keep (the chip holds 11.22 GB of weights
                    once); the check is against ``reference_sala`` at the
                    cell's published widths, along the engine's chosen blocks
                    at the rows compared;
``snapshot``        also carries the engine's lightning, selection and chunk
                    counters;
``traced_stretch``  also reads, from the program's ``serve_prefill`` (a span a
                    chunk) and ``serve_decode`` spans, what each traced call's
                    lightning layers, selection and attention were asked, and
                    from the compiled programs' text which of their
                    instructions lie under the scopes ``odtp_lightning``,
                    ``odtp_block_select`` and ``odtp_block_attn``;
``finish``          hands the window's counter differences to the readers, and
                    prints them (``window_counters``); and decides ``correct``
                    without the tail's sample count, since this cell reports no
                    tail (a window ends some 8-10 requests and the harness holds
                    a p95 to 200).
"""

from __future__ import annotations

import math
import os
import time
import types

import numpy as np

from odbench import costs_sala, manifest, program_obs, reference_sala, serve_cell, traffic
from odbench.logits_check import rel_l2, served_rows

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_load = lambda name: manifest.load_module(os.path.join(_BENCH, "drivers", name))
top_level_instructions = _load("closed_loop_granite_h.py").top_level_instructions
warm_up = _load("closed_loop_dots3.py").warm_up
SCOPES = ("odtp_lightning", "odtp_block_select", "odtp_block_attn", "odtp_attn_gate")
CHUNK_SCOPE = "odtp_serve_prefill"  # the chunk program, whole
POOL_TOKENS = 4_000_000

# The limits, and a run is ``correct`` only inside all of them. WRITTEN BEFORE
# THE CELL'S FIRST RUN ON THE CHIP (PR 61); the readings that followed, and any
# move of a limit, are in PERF.md section 4 and CHANGES.md.
#
# LOGITS_REL_L2: engine logits (bf16 weights and activations; the prompt in
# chunks of 2,048 through both rings and the lightning layers' float32 states,
# a sparse layer's chunk a tile of 512 ring rows at a time under an online
# softmax, a lightning layer's in blocks of 256 tokens; decode through the
# rings by ``odtp_block_decode_attn`` over the chosen blocks' tiles and through
# the states one token a slot) against the float32 reference's full forward
# over the whole sequence (the lightning layers token by token, the selection
# from scores over all pooled keys) *reading, at the rows compared, the blocks
# the engine chose*, relative L2 over those rows: the last prompt position and
# each of 8 decode steps of two prompts in the traffic's range, one a whole
# number of chunks and one ending inside a chunk and inside a pooling window.
# As first written: 6e-2, ``serve_cell``'s own (twice the larger of the dense
# cells' bf16-against-float32 readings).
#
# CHOICE_MARGIN, PART_MARGIN, LAYER_BLOCKS_DIFFERING: at a (row compared, sparse
# layer, KV head) the blocks the reference would have chosen and the engine did
# not, and those the engine chose in their place, lie some way apart in the
# reference's own block scores: the largest score left out less the smallest
# taken in, over the standard deviation of the query's scores over the blocks
# it may choose among (the set's *exchange distance*; 0 where the sets are
# equal). A bfloat16 score exchanges blocks next to the 64th; a wrong
# selection (block means for maxima, 64 by score beside the forced ones, the
# first 64 blocks, windows seen before they close) exchanges blocks the
# reference is sure of. The median over all the sets is held to CHOICE_MARGIN,
# the median of each part (a prompt's sets, the prompts' last tokens', the
# decode steps') to PART_MARGIN, and the share of a layer's chosen blocks that
# differ to LAYER_BLOCKS_DIFFERING: ``closed_loop_keye``'s three, at its values
# as first written.
LOGITS_REL_L2 = 6e-2
CHOICE_MARGIN = 4e-1
PART_MARGIN = 7e-1
LAYER_BLOCKS_DIFFERING = 2e-1
COUNTERS = (
    "prefill_chunks", "prefill_chunk_tokens", "lightning_tokens", "lightning_state_bytes_moved",
    "pooled_keys_scored", "blocks_chosen", "block_rows_read", "block_tiles_read",
    "block_tiles_live", "dense_len_calls",
)
RESIDENT = ("lightning_state_resident_bytes", "pooled_cache_resident_bytes")


def served_rows_and_choices(cell, engine, seed, after_admit=None):
    """``logits_check.served_rows`` and, beside each prompt's rows, the blocks
    the engine chose in every sparse layer and KV head for each position
    compared [R, Ls, Kh, blocks] bool: the prompt's last token's from its last
    chunk, then one a decode step."""
    kept = {"prefill": [], "decode": []}
    admit, decode_step = engine.admit, engine.decode_step

    def kept_admit(slot, prompt, **kw):
        out = admit(slot, prompt, **kw)
        kept["prefill"].append(np.asarray(engine.row_choices))  # [Ls, Kh, blocks]
        return out

    def kept_decode_step(tokens, lens):
        out = decode_step(tokens, lens)
        kept["decode"].append(np.asarray(engine.row_choices))  # [Ls, S, Kh, blocks]
        return out

    engine.admit, engine.decode_step = kept_admit, kept_decode_step
    try:
        prompts, seqs, got = served_rows(cell, engine, seed, after_admit)
    finally:
        del engine.admit, engine.decode_step  # the instance's; the class's stay
    choices = [
        np.stack([kept["prefill"][slot], *(step[:, slot] for step in kept["decode"])])
        for slot in range(len(prompts))
    ]
    return prompts, seqs, got, choices


def reference_rows(cell, params, prompts, seqs, choices=None, operands=None, faults=()):
    """The same rows from the reference's full forward over each sequence
    (padded to the check's ``pad_to``; every layer is causal, so the padding
    changes nothing before it), reading ``choices`` at the rows compared (None:
    its own) -> (rows, and per prompt over [R, Ls, Kh]: the blocks in which its
    own sets differ from ``choices`` and the exchanged blocks' distance in its
    scores). ``operands`` and ``faults`` are the readings tool's."""
    spec = cell.options["check"]
    steps, pad = int(spec["decode_steps"]), int(spec["pad_to"])
    sizes = cell.config["sparse_config"]
    blocks = -(-pad // sizes["block_size"])
    rows, differing, distance = [], [], []
    for i, (prompt, seq) in enumerate(zip(prompts, seqs)):
        ids = np.zeros((1, pad), np.int32)
        ids[0, : len(seq)] = seq
        follow = None
        if choices is not None:  # the engine's ring is longer than the padded sequence
            follow = np.zeros((*choices[i].shape[:-1], blocks), bool)
            n = min(blocks, choices[i].shape[-1])
            follow[..., :n] = choices[i][..., :n]
        logits, own, scores = reference_sala.forward(
            params, ids, cell.config, operands, faults, (len(prompt) - 1, steps + 1),
            prompt_len=len(prompt), follow=follow, with_choices=True,
        )
        rows.append(np.asarray(logits)[0])
        at = (len(prompt) - 1 + np.arange(steps + 1))[:, None, None]
        given = np.asarray(own) if follow is None else follow
        d, dist = reference_sala.exchange_distance(
            np.asarray(own), given, np.asarray(scores), np.broadcast_to(at, given.shape[:-1]), sizes
        )
        differing.append(d)
        distance.append(dist)
    return rows, differing, distance


def exchanged(cell, differing, distance) -> dict:
    """Where the engine and the reference chose differently: how many of the
    chosen blocks, at how many (row compared, layer, KV head) sets, and the
    sets' exchange distance in the reference's scores: its median over all the
    sets and over each part that ``verdict`` holds by itself. A prompt's arrays
    are [R, Ls, Kh]: row 0 its last token's (the last chunk's), then one a
    decode step."""
    topk = cell.config["sparse_config"]["topk"]
    every = np.concatenate(distance, axis=0)  # [sum R, Ls, Kh]
    sets = sum(d.size for d in differing)
    blocks = sum(int(d.sum()) for d in differing)
    median = lambda a: float(np.median(a))
    return {
        "median_exchange_distance": median(every),
        "median_exchange_distance_by_prompt": [round(median(d), 4) for d in distance],
        "median_exchange_distance_last_tokens": median(np.stack([d[0] for d in distance])),
        "median_exchange_distance_decode_steps": median(np.concatenate([d[1:] for d in distance])),
        "median_exchange_distance_by_layer": np.round(np.median(every, axis=(0, 2)), 4).tolist(),
        # an exchange is a block left out and one taken in: two of the 2 x topk
        "blocks_differing_share_by_layer": np.round(
            np.concatenate(differing, axis=0).mean(axis=(0, 2)) / (2 * topk), 5).tolist(),
        "sets_compared": sets, "sets_differing": sum(int((d > 0).sum()) for d in differing),
        "blocks_differing": blocks,
        "largest_exchange_distance": float(every.max()),
    }


def verdict(rel: float, chose: dict) -> tuple:
    """What decides the check, for the engine and for every control of the
    readings tool alike -> (ok, the limits as the ``check`` line prints them,
    the names of those not met)."""
    held = {
        "logits_rel_l2": (rel, LOGITS_REL_L2),
        "median_exchange_distance": (chose["median_exchange_distance"], CHOICE_MARGIN),
        "median_exchange_distance_of_a_prompt": (
            max(chose["median_exchange_distance_by_prompt"]), PART_MARGIN),
        "median_exchange_distance_last_tokens": (
            chose["median_exchange_distance_last_tokens"], PART_MARGIN),
        "median_exchange_distance_decode_steps": (
            chose["median_exchange_distance_decode_steps"], PART_MARGIN),
        "blocks_differing_share_of_a_layer": (
            max(chose["blocks_differing_share_by_layer"]), LAYER_BLOCKS_DIFFERING),
    }
    failed = [name for name, (read, limit) in held.items()
              if not (math.isfinite(read) and read <= limit)]
    return not failed, {name: limit for name, (_, limit) in held.items()}, failed


def check_logits(cell, engine, seed, report, t_process) -> bool:
    """The prompts in chunks through both rings and the states, then decoding
    through them, against the reference's full forward on the same tokens
    along the engine's chosen blocks: logits, never tokens; and the choices
    themselves. Outside the window."""
    before = {name: getattr(engine, name) for name in COUNTERS}
    prompts, seqs, got, choices = served_rows_and_choices(cell, engine, seed)
    t_served = time.perf_counter()
    want, differing, distance = reference_rows(cell, engine.params, prompts, seqs, choices)
    rel, per_prompt = rel_l2(got, want)
    chose = exchanged(cell, differing, distance)
    ok, tolerance, failed = verdict(rel, chose)
    report.line(
        "check", ok=ok, limits_not_met=failed, logits_rel_l2=rel, **chose, tolerance=tolerance,
        reference="reference_sala", per_prompt_rel_l2=per_prompt,
        prompts=[len(p) for p in prompts], decode_steps=len(got[0]) - 1,
        rows_compared=sum(len(rows) for rows in got),
        **{name: getattr(engine, name) - before[name] for name in COUNTERS},
        served_s=t_served - t_process, reference_s=time.perf_counter() - t_served,
        setup_so_far_s=time.perf_counter() - t_process,
    )
    return ok


def build(cell, devices, seed, report, t_process):
    """The engine, after asking the program whether it runs the configuration
    at all: one that knows no ``minicpm_sala`` keys reads the ones it knows as
    a plain stack of 18 attention layers and would serve another model. The
    weights are drawn in bfloat16 from the seed a leaf at a time (the float32
    tree is 22.4 GB), by the device's own bit generator, and the engine adopts
    the tree: the chip holds them once."""
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.models import llama
    from opendiloco_tpu.serve import ServeEngine

    try:
        cfg = llama.LlamaConfig.from_dict(cell.config)
        runs = getattr(cfg, "linear", False) and getattr(cfg, "blocks", False)
    except (TypeError, ValueError) as e:
        runs = False
        report.line("refused", error=str(e))
    if not runs:
        raise RuntimeError(
            f"the program under test cannot run {cell.config_name}: it knows no lightning "
            "linear-attention layers or no selection by blocks"
        )
    opts = cell.options["engine"]
    with jax.default_device(devices[0]):
        # the chip's own generator (``rbg``): 28 programs of threefry over arrays of
        # this size compile for two minutes on an empty cache (PERF.md section 4)
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
        "built", params=costs_sala.param_count(cell.config), slots=engine.num_slots,
        max_context=engine.max_context, decode_kernel=engine.decode_kernel,
        weights_adopted=engine.weights_adopted, weight_format="bf16, one copy",
        drawn_s=drawn_s, setup_so_far_s=time.perf_counter() - t_process,
    )
    return engine.cfg, engine


def start(cell, devices, seed, trace, report, t_process):
    """``serve_cell.start`` with this configuration's build and reference."""
    from opendiloco_tpu.serve import ContinuousBatcher

    cfg, engine = build(cell, devices, seed, report, t_process)
    engine.keep_row_choices()  # before the first call: nothing compiles twice
    report.line(
        "sala", params=costs_sala.param_count(cell.config),
        published_params=costs_sala.published_param_count(cell.config),
        weights_resident_bytes=engine.weights_resident_bytes,
        kv_ring_bytes=engine.cache_k.nbytes + engine.cache_v.nbytes,
        pooled_ring_bytes=engine.pooled_cache_resident_bytes,
        lightning_state_bytes=engine.lightning_state_resident_bytes,
        slot_bytes_by_shapes=costs_sala.slot_bytes(cell.config, engine.max_context),
        layers={"lightning": cfg.num_lightning_layers, "sparse": cfg.num_attention_layers},
        chunk=cfg.q_chunk_size, block_forms=engine.block_forms,
        sparse_config=dict(cfg.sparse_config), decode_kernel=engine.decode_kernel,
        decode_plan=engine.decode_plan_stats(),
    )
    check_ok = check_logits(cell, engine, seed, report, t_process)
    instrument = serve_cell.Instrument(engine) if trace == 1 else None
    return cfg, engine, check_ok, instrument, ContinuousBatcher(engine).start()


def snapshot(engine, batcher) -> dict:
    """``serve_cell.snapshot``, the lightning layers', the selection's and the
    chunks' counters, and what the state and the pooled ring hold."""
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
    rings = shaped((engine.cache_k, engine.cache_v, *engine._sala))
    ids = jax.ShapeDtypeStruct((1, engine.cfg.q_chunk_size), jnp.int32)
    texts = [
        engine._decode.lower(params, vec, vec, *rings).compile().as_text(),
        engine._chunk.lower(
            params, ids, scalar, scalar, scalar, scalar, jax.ShapeDtypeStruct((), jnp.bool_), vec,
            *rings,
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


def layer_calls(capture, t0, t1) -> list:
    """Per traced decode step and prefill chunk, from the program's spans
    (``ServeEngine._count_sala``'s attributes, each over its layers and KV
    heads): [lightning tokens of a step, of a chunk, (query, pooled key) pairs,
    distinct pooled keys read, (query, chosen row) pairs, distinct chosen rows
    read, decode step?, ring tiles the form moved, ring tiles live]."""
    calls = []
    for name, decode in (("serve_prefill", 0), ("serve_decode", 1)):
        for args in program_obs.span_args(capture, name, t0, t1):
            if "lightning_tokens" in args:
                tokens = args["lightning_tokens"]
                calls.append([
                    tokens * decode, tokens * (1 - decode), args["pooled_keys_scored"],
                    args["pooled_keys_read"], args["block_rows_read"], args["block_rows_distinct"],
                    decode, args["block_tiles_read"], args["block_tiles_live"],
                ])
    return calls


def traced_stretch(cell, engine, batcher, compiles, report, keep_sending, meanwhile=None) -> dict:
    """``serve_cell.traced_stretch`` and ``layer_calls`` of its spans; then the
    names of the instructions under the scopes. Nothing where the spans carry
    no such layers' work."""
    traced = serve_cell.traced_stretch(
        cell, engine, batcher, compiles, report, keep_sending, meanwhile
    )
    stretch = traced["stretch"]
    calls = layer_calls(stretch.capture, stretch.t0, stretch.t1)
    t = time.perf_counter()
    ops, ambiguous = scope_instructions(engine) if calls else ({}, {})
    traced["counters"].update(
        traced_kind_calls=calls, dsa_ops=ops,
        # what ``prefill_chunk_device_ms`` counts its chunks from: a call's
        # fourth entry says whether it is a decode step
        traced_dsa_calls=[[c[2], c[4], c[5], c[6]] for c in calls],
    )
    steps = [c for c in calls if c[6]]
    report.line(
        "traced_sala", calls=len(calls), chunks=len(calls) - len(steps),
        lightning_tokens=sum(c[0] + c[1] for c in calls), pooled_pairs=sum(c[2] for c in calls),
        block_pairs=sum(c[4] for c in calls), block_rows=sum(c[5] for c in calls),
        step_tiles_read=sum(c[7] for c in steps), step_tiles_live=sum(c[8] for c in steps),
        instructions_named={scope: len(found) for scope, found in ops.items()},
        named_elsewhere_too=ambiguous, naming_s=time.perf_counter() - t,
    )
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
            tiles_read_share=moved["block_tiles_read"] / max(1, moved["block_tiles_live"]),
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
