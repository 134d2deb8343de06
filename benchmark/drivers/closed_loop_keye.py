"""Serving, callers that wait, a Keye-VL-2.0 configuration (a Qwen3-MoE block
under a learned indexer: 16 index queries a token score every row of a slot,
the 2,048 of largest score are what its attention reads; an index-key ring
beside K and V; prompts of 12k-16k tokens admitted in chunks of 512 between
decode steps): ``closed_loop.py``'s window to the letter, with a check, a
warm-up and counters of its own.

As the other configurations' drivers do (PERF.md section 7(f) stays the
benchmark's debt), this driver loads a private copy of ``closed_loop.py`` and
gives it a view of ``serve_cell`` in which five functions are its own:

``start``           refuses, at once and before anything is built, a program
                    that knows no learned sparse attention; asks the engine to
                    keep each call's chosen rows on the device; the check is
                    against ``reference_keye`` at the cell's published widths,
                    **along the program's chosen rows** at the rows compared,
                    with the rows exchanged held to a small reference margin;
``warm_up``         one prompt that goes in chunks, decoded until the batcher
                    has published its gauges (the cell has no prefill bucket
                    to send a prompt to: every prompt goes in chunks);
``snapshot``        also carries the engine's indexer, chunk and routed-FFN
                    counters;
``traced_stretch``  also reads, from the program's ``serve_prefill`` (a span a
                    chunk) and ``serve_decode`` spans, the rows each traced
                    call scored and chose and the pairs it routed, and from the
                    compiled programs' text which of their instructions lie
                    under the scopes ``odtp_dsa_index`` and ``odtp_dsa_attn``;
``finish``          hands the window's counter differences to the readers, and
                    prints them (``window_counters``); and decides ``correct``
                    without the tail's sample count, since this cell reports no
                    tail (below).

It also draws fewer requests ahead than ``closed_loop.py`` does for cells
whose prompts are a hundredth of these: as many as hold ``POOL_TOKENS`` prompt
tokens, at least 256 (a window ends about 30 requests; 8,192 prompts of 14,000
tokens would be 115 million Python integers).

**No tail.** A window of 45 s ends a few dozen requests (a prompt is 24-32
chunks and a request 256 steps), and the harness reports a p95 only with ten
samples beyond it, 200 requests (``stats.supported``); ``serve_cell.finish``
ANDs that into ``correct``. So ``BENCHMARK.json`` lists the cell under
``serve_tokens_per_s`` and not under ``tpot_p95_ms``, and a run is ``correct``
by everything else ``serve_cell.finish`` asks: the check against the
reference, no failed request, no error of the batcher's loop. The ``tails``
line still carries what the sample supports, as a reading and not a metric.

Everything else, the clients' requests (``traffic.requests``) among it, is the
code the other closed-loop cells run.
"""

from __future__ import annotations

import math
import os
import time
import types

import numpy as np

from odbench import costs_keye, manifest, program_obs, reference_keye, serve_cell, traffic
from odbench.logits_check import rel_l2, served_rows

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# which instructions of a compiled program lie under a named scope: the
# granite driver's, told these scopes
top_level_instructions = manifest.load_module(
    os.path.join(_BENCH, "drivers", "closed_loop_granite_h.py")
).top_level_instructions
SCOPES = ("odtp_dsa_index", "odtp_dsa_attn")
CHUNK_SCOPE = "odtp_serve_prefill"  # the chunk program, whole
POOL_TOKENS = 4_000_000

# The limits, and a run is ``correct`` only inside every one (``verdict``).
#
# LOGITS_REL_L2: engine logits (bf16 weights and activations; the prompt in
# chunks of 512 over the slot's three rings, each chunk's attention in tiles of
# ring rows under an online softmax; decode through the rings, every live row
# read under the selection's mask; the grouped matmuls over 16 held experts)
# against the float32 reference's full forward *reading, at the rows compared,
# the rows the engine chose*, relative L2 over those rows: the last prompt
# position and each of 8 decode steps of two prompts (12,500 and 16,100
# tokens, neither a whole number of chunks).
#
# CHOICE_MARGIN: at a (row compared, layer) the rows the reference would have
# chosen and the engine did not, and those the engine chose in their place,
# lie some way apart *in the reference's scores*: the largest score among the
# first less the smallest among the second, over the root mean square of the
# query's scores (the set's *exchange distance*; 0 where the sets are equal).
# **The median of that over the 288 sets** has to be at most this. bfloat16
# scoring against float32 exchanges rows next to the 2,048th score; an indexer
# that is wrong (no ReLU, rows it did not see, a selection that is not the
# largest, chosen rows dropped) exchanges rows the reference is sure of, in
# every set. The median and not the largest, because the reference follows the
# engine's rows at the rows compared and walks every earlier position by
# itself: a handful of rows a set are exchanged far from the 2,048th score in
# every run, the engine's and the bfloat16 reference's alike (the largest
# distance of the 288 sets reads 2.4 to 3.4 where their median reads 0.1).
#
# PART_MARGIN, LAYER_ROWS_DIFFERING: a median over all the sets cannot see a
# fault confined to under half of them. So three parts of the sets are held by
# themselves, each part's median exchange distance to PART_MARGIN: **each
# prompt's** 144 sets, **the prompts' last tokens'** 32 (the last chunk's
# selection; every other set is a decode step's) and **the decode steps'**
# 256; and **each layer's** 18 sets by the share of their chosen rows that
# differ from the reference's, to LAYER_ROWS_DIFFERING. A layer's median
# distance would not do: it reads 0.01 in layer 0, **1.6 to 2.1 in layer 1**,
# 0.8 in layer 2 and 0.04-0.2 from there on, for the engine and for the
# bfloat16 reference alike (1.9, 0.8), so it is the configuration's under
# rounding and no fault of the program (handed the float32 walk's experts
# token by token, the bfloat16 walk's layer 1 reads 0.38: the far exchanges
# are tokens whose experts flipped at a near-tie; PERF.md section 6): layers 1
# and 2 exchange 5-8% of their rows where the others exchange 1-3%. The share
# of rows differing separates where the distance cannot.
#
# Readings on the chip, 16 layers at the published widths (PR 49, second
# session: ``tools/keye_check_readings.py --faults`` on seed 4901100001, the
# engine's also on three more seeds and the check lines of twelve runs of the
# cell and, of the first two columns, on those of fourteen earlier ones (30
# seeds); every control goes through ``verdict`` and the tool prints its
# ``ok``; a variant is read against the sound float32 reference walking along
# the variant's own chosen rows):
#
#                                    logits     median exchange distance              rows differing,
#                                    rel L2     all sets   a prompt's   last tokens'  a layer's largest share
#   the engine (16 seeds; 30)        7.3-9.2e-3 0.084-0.157  0.073-0.201  0.084-0.174  5.7-8.1%
#   the reference, bfloat16          4.8e-3     0.081      0.086        0.078         5.8%
#   the reference, float8_e4m3fn     3.2e-1     2.58       3.03         2.43          70%    (has to fail)
#   an indexer without its ReLU      3.5e-2     2.53       2.90         2.61          55%
#   the first 2,048 rows             4.5e-3     5.33       5.44         5.51          99%    (no logit moves)
#   one chosen row in 100 dropped    1.3e-1     1.62       1.76         1.84          21%
#   chunks blind to rows before them 1.40       4.04       4.59         4.41          100%
#   the first rows, in one prompt    (engine's) 2.57       5.44         2.41          51%
#   ..., in the last tokens alone    (engine's) 0.128      0.154        5.51          17.5%  (its part's limit alone)
#   ..., in layers 12-15 alone       (engine's) 0.157      0.201        0.170         99%    (the layers' limit alone)
#
# The engine reads 1.7 times the bfloat16 reference's logits: it rounds more
# than its matmuls' operands (the rings' rows, the residual stream between the
# branches). LOGITS_REL_L2 is 2.7 times the engine's largest and 0.7 of the
# smallest that has to fail by it (no ReLU); CHOICE_MARGIN is 2.5 times the
# engine's largest and a quarter of the smallest fault's (dropped rows);
# PART_MARGIN is 3.5 times the largest a part of the engine's sets read (a
# prompt's, 0.201: a part is a smaller sample than the whole) and under half
# of the smallest fault's (dropped rows, 1.42 in a prompt); LAYER_ROWS_DIFFERING
# is 2.5 times the engine's largest and a third of what the confined fault
# reads in its least layer (59%). Every control but bfloat16 fails a limit; a
# selection of the first rows moves no logit along its own rows and is the
# margins' alone, and confined to a part it is that part's limit's alone: why
# each limit is there.
LOGITS_REL_L2 = 2.5e-2
CHOICE_MARGIN = 4e-1
PART_MARGIN = 7e-1
LAYER_ROWS_DIFFERING = 2e-1
COUNTERS = (
    "moe_pairs", "moe_experts_hit", "moe_max_pairs", "moe_pairs_all",
    "dsa_rows_scored", "dsa_rows_selected", "dsa_index_bytes_read", "dsa_kv_bytes_read",
    "prefill_chunks", "prefill_chunk_tokens",
)
RESIDENT = "index_cache_resident_bytes"


def sets_as_rows(chosen: np.ndarray, topk: int) -> np.ndarray:
    """Sets as bool [..., T] -> row indices [..., topk] int32, -1 behind a set
    that is shorter."""
    out = np.full((*chosen.shape[:-1], topk), -1, np.int32)
    for at in np.ndindex(*chosen.shape[:-1]):
        idx = np.nonzero(chosen[at])[0][:topk]
        out[at][: idx.size] = idx
    return out


def served_rows_and_choices(cell, engine, seed, after_admit=None):
    """``logits_check.served_rows`` and, beside each prompt's rows, the rows
    the engine's indexer chose in every layer for each position compared [R,
    L, topk]: the prompt's last token's from its last chunk, then one a decode
    step."""
    topk = cell.config["sa_config"]["topk"]
    kept = {"prefill": [], "decode": []}
    admit, decode_step = engine.admit, engine.decode_step

    def kept_admit(slot, prompt, **kw):
        out = admit(slot, prompt, **kw)
        kept["prefill"].append(sets_as_rows(np.asarray(engine.row_choices), topk))  # [L, K]
        return out

    def kept_decode_step(tokens, lens):
        out = decode_step(tokens, lens)
        kept["decode"].append(np.asarray(engine.row_choices))  # [L, S, T]
        return out

    engine.admit, engine.decode_step = kept_admit, kept_decode_step
    try:
        prompts, seqs, got = served_rows(cell, engine, seed, after_admit)
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
    its own) -> (rows, and per prompt over [R, L]: the rows in which its own
    sets differ from ``choices``, the exchanged rows' distance in its scores,
    the gap at its topk-th score, the two rows farthest apart). ``operands`` and ``faults`` are the readings
    tool's: a lower precision, one equation broken."""
    import jax

    spec = cell.options["check"]
    steps, pad = int(spec["decode_steps"]), int(spec["pad_to"])
    ref_fn = jax.jit(  # ``follow`` None: the walk's own sets (a trace of its own)
        lambda p, ids, follow, first: reference_keye.forward(
            p, ids, cell.config, operands, faults, follow, (first, steps + 1), with_choices=True
        )
    )
    rows, differing, distance, gap, worst = [], [], [], [], []
    for i, (prompt, seq) in enumerate(zip(prompts, seqs)):
        ids = np.zeros((1, pad), np.int32)
        ids[0, : len(seq)] = seq
        follow = None if choices is None else choices[i]
        out = ref_fn(params, ids, follow, np.int32(len(prompt) - 1))
        rows.append(np.asarray(out[0])[0])
        differing.append(np.asarray(out[2]))
        distance.append(np.asarray(out[3]))
        gap.append(np.asarray(out[4]))
        worst.append(np.asarray(out[5]))
    return rows, differing, distance, gap, worst


def exchanged(cell, differing, distance, worst=None) -> dict:
    """Where the engine and the reference chose differently: how many of the
    chosen rows, at how many (row compared, layer) sets, and the sets'
    exchange distance in the reference's scores: its median over all the sets
    and over each part of them that ``verdict`` holds by itself (a prompt's,
    the prompts' last tokens', the decode steps', a layer's), the share of the
    chosen rows that differ layer by layer, and the largest distance (with
    ``worst``: where that was, and which two rows). A prompt's arrays are [R,
    L]: row 0 its last token's (the last chunk's), then one a decode step."""
    topk = cell.config["sa_config"]["topk"]
    pairs = sum(d.size for d in differing)
    rows = sum(int(d.sum()) for d in differing)
    every = np.concatenate(distance, axis=0)  # [sum R, L]
    median = lambda a: float(np.median(a))
    where = {}
    if worst is not None:
        prompt = int(np.argmax([d.max() for d in distance]))
        r, layer = np.unravel_index(int(np.argmax(distance[prompt])), distance[prompt].shape)
        where = {"largest_at": {
            "prompt": prompt, "row": int(r), "layer": int(layer),
            "left_out": int(worst[prompt][r, layer, 0]), "taken": int(worst[prompt][r, layer, 1]),
        }}
    return {
        **where,
        "median_exchange_distance": median(every),
        "median_exchange_distance_by_prompt": [round(median(d), 4) for d in distance],
        "median_exchange_distance_last_tokens": median(np.stack([d[0] for d in distance])),
        "median_exchange_distance_decode_steps": median(np.concatenate([d[1:] for d in distance])),
        "median_exchange_distance_by_layer": np.round(np.median(every, axis=0), 4).tolist(),
        "rows_differing_share_by_layer": np.round(
            np.concatenate(differing, axis=0).mean(axis=0) / topk, 5).tolist(),
        "sets_compared": pairs, "sets_differing": sum(int((d > 0).sum()) for d in differing),
        "rows_differing": rows,
        "rows_differing_share": rows / max(1, pairs * topk),
        "largest_exchange_distance": float(every.max()),
    }


def verdict(rel: float, chose: dict) -> tuple:
    """What decides the check, for the engine and for every control of the
    readings tool alike: the logits and ``exchanged``'s readings against the
    module's limits -> (ok, the limits as the ``check`` line prints them, the
    names of those not met)."""
    held = {
        "logits_rel_l2": (rel, LOGITS_REL_L2),
        "median_exchange_distance": (chose["median_exchange_distance"], CHOICE_MARGIN),
        "median_exchange_distance_of_a_prompt": (
            max(chose["median_exchange_distance_by_prompt"]), PART_MARGIN),
        "median_exchange_distance_last_tokens": (
            chose["median_exchange_distance_last_tokens"], PART_MARGIN),
        "median_exchange_distance_decode_steps": (
            chose["median_exchange_distance_decode_steps"], PART_MARGIN),
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
    want, differing, distance, _, worst = reference_rows(cell, engine.params, prompts, seqs, choices)
    rel, per_prompt = rel_l2(got, want)
    chose = exchanged(cell, differing, distance, worst)
    ok, tolerance, failed = verdict(rel, chose)
    report.line(
        "check", ok=ok, limits_not_met=failed, logits_rel_l2=rel, **chose, tolerance=tolerance,
        reference="reference_keye", per_prompt_rel_l2=per_prompt,
        prompts=[len(p) for p in prompts], decode_steps=len(got[0]) - 1,
        rows_compared=sum(len(rows) for rows in got),
        **{name: getattr(engine, name) - before[name] for name in COUNTERS},
        reference_s=time.perf_counter() - t_served,
        setup_so_far_s=time.perf_counter() - t_process,
    )
    return ok


def build(cell, devices, seed, report, t_process):
    """``serve_cell.build`` after asking the program whether it runs the
    configuration at all (one that reads no ``sa_config`` would build sixteen
    layers of ordinary attention under this model's name and refuse every
    prompt past its buckets), with the engine told to keep its programs'
    chosen rows."""
    from opendiloco_tpu.models.llama import LlamaConfig

    try:
        runs = getattr(LlamaConfig.from_dict(cell.config), "sparse", False)
    except (TypeError, ValueError) as e:
        runs = False
        report.line("refused", error=str(e))
    if not runs:
        raise RuntimeError(
            f"the program under test cannot run {cell.config_name}: its LlamaConfig "
            "reads no sa_config and has no learned sparse attention"
        )
    cfg, engine = serve_cell.build(cell, devices, seed, report, t_process)
    engine.keep_row_choices()  # before its programs are first traced
    return cfg, engine


def start(cell, devices, seed, trace, report, t_process):
    """``serve_cell.start`` with this configuration's reference in the check."""
    from opendiloco_tpu.serve import ContinuousBatcher

    cfg, engine = build(cell, devices, seed, report, t_process)
    rings = costs_keye.ring_bytes(cell.config, engine.num_slots, engine.max_context)
    report.line(
        "keye", params=costs_keye.param_count(cell.config),
        published_params=costs_keye.published_param_count(cell.config),
        weights_resident_bytes=engine.weights_resident_bytes,
        kv_ring_bytes=engine.cache_k.nbytes + engine.cache_v.nbytes,
        index_cache_resident_bytes=engine.index_cache_resident_bytes,
        ring_bytes_by_shapes=rings, chunk=cfg.q_chunk_size, topk=cfg.index_topk,
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
    """``serve_cell.snapshot``, the indexer's, the chunks' and the routed FFN's
    counters, and what the index ring holds."""
    return {
        **serve_cell.snapshot(engine, batcher),
        **{name: getattr(engine, name, 0) for name in (*COUNTERS, RESIDENT)},
    }


def dsa_instructions(engine) -> tuple:
    """The instructions under each of the two scopes in the engine's decode
    program and its chunk program (and a prefill program a bucket, where the
    cell has buckets), as the chip's compiler named them: the programs are
    lowered and compiled again, which the persistent cache answers. After the
    traced stretch, so that neither the window nor the stretch sees it. ->
    ({scope: instructions}, {scope: those that a program also has under the
    same name and shape outside the scope}); and under ``CHUNK_SCOPE`` the chunk
    program's instructions that the decode program has not, beside those it has."""
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
    for bucket in engine.prefill_buckets:
        whole = jax.ShapeDtypeStruct((1, bucket), jnp.int32)
        texts.append(engine._prefill.lower(params, whole, scalar).compile().as_text())
    named, elsewhere = {}, {}
    for scope in SCOPES:
        inside, outside = set(), set()
        for text in texts:
            ours, others = top_level_instructions(text, scope)
            inside |= ours
            outside |= others
        named[scope], elsewhere[scope] = sorted(inside), sorted(inside & outside)
    # the chunk program whole, for the device's time a chunk: what the decode
    # program has under the same name and shape is left out, not let in (a
    # time, unlike a share of a roofline, would grow by a step's operations)
    chunk, _ = top_level_instructions(texts[1], CHUNK_SCOPE)
    step = set().union(*top_level_instructions(texts[0], CHUNK_SCOPE))
    named[CHUNK_SCOPE], elsewhere[CHUNK_SCOPE] = sorted(chunk - step), sorted(chunk & step)
    return named, elsewhere


def traced_stretch(cell, engine, batcher, compiles, report, keep_sending, meanwhile=None) -> dict:
    """``serve_cell.traced_stretch`` and, per traced decode step and prefill
    chunk, the (query, row) pairs its indexer scored, those it chose and the
    distinct rows they lie in, over layers, and the pairs it routed (with the
    experts they reached), as the program's spans carry them; then the names
    of the instructions under the two scopes. Nothing where the spans carry
    none."""
    traced = serve_cell.traced_stretch(
        cell, engine, batcher, compiles, report, keep_sending, meanwhile
    )
    stretch = traced["stretch"]
    layers = cell.config["num_hidden_layers"]
    calls, routed = [], []
    for name, decode in (("serve_prefill", 0), ("serve_decode", 1)):
        for args in program_obs.span_args(stretch.capture, name, stretch.t0, stretch.t1):
            if "dsa_rows_scored" in args:
                distinct = (
                    args["dsa_rows_scored"] if decode
                    else layers * (args["rows_before"] + args["tokens"])
                )
                calls.append([args["dsa_rows_scored"], args["dsa_rows_selected"], distinct, decode])
            if "moe_pairs" in args:
                routed.append([args["moe_pairs"], args["moe_experts_hit"]])
    t = time.perf_counter()
    ops, ambiguous = dsa_instructions(engine) if calls else ({}, {})
    traced["counters"].update(traced_dsa_calls=calls, dsa_ops=ops, traced_moe_calls=routed)
    report.line(
        "traced_dsa", calls=len(calls), chunks=sum(1 for c in calls if not c[3]),
        rows_scored=sum(c[0] for c in calls), rows_selected=sum(c[1] for c in calls),
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
            "window_counters", **moved, **{RESIDENT: after[RESIDENT]},
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
