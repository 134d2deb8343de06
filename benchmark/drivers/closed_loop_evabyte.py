"""Serving, callers that wait, an EvaByte configuration (EVA attention: a
window of rows read exactly and every earlier window as pooled chunks, two
rings a slot; a byte vocabulary with several prediction heads):
``closed_loop.py``'s window to the letter, with a check and counters of its
own.

As the other configurations' drivers do (PERF.md section 7(f) stays the
benchmark's debt), this driver loads a private copy of ``closed_loop.py`` and
gives it a view of ``serve_cell`` in which four functions are its own:

``start``           refuses, at once and before anything is built, a program
                    that knows no EVA attention; the check is against
                    ``reference_evabyte`` at the cell's published widths, on
                    prompts that put one slot across a window's edge while it
                    decodes and end a chunk mid-window in the other, all
                    prediction heads' logits;
``snapshot``        also carries the engine's EVA counters and the seconds of
                    a decode step's host phases;
``traced_stretch``  also reads, from the program's ``serve_decode`` spans, the
                    window rows and pooled rows each traced step read;
``finish``          hands the window's counter differences to the readers, and
                    prints them (``window_counters``: the split of rows read a
                    step, chunks pooled, restarts, the step's host phases);
                    and decides ``correct`` without the tail's sample count,
                    since this cell reports no tail (below).

**No tail.** At the cell's traffic a window of 45 s ends 135-147 requests
(PERF.md, PR 40), and the harness reports a p95 only with ten samples beyond
it, 200 requests (``stats.supported``); ``serve_cell.finish`` ANDs that into
``correct``. So ``BENCHMARK.json`` lists the cell under ``serve_tokens_per_s``
and not under ``tpot_p95_ms``, ``run.py`` prints no tail for it, and a run is
``correct`` by everything else ``serve_cell.finish`` asks: the check against
the reference, no failed request, no error of the batcher's loop. The
``tails`` line still carries the median, the p95 and the highest percentile
the sample supports, as a reading and not as a metric.

Everything else, the clients' requests (``traffic.requests``) among it, is the
code the other closed-loop cells run.
"""

from __future__ import annotations

import math
import os
import time
import types

import numpy as np

from odbench import costs_evabyte, manifest, program_obs, reference_evabyte, serve_cell
from odbench.logits_check import rel_l2, served_rows

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# LOGITS_REL_L2: engine logits (bf16 weights and branches under a float32
# residual stream; the prefill's windows through the flash kernel with the
# pooled rows merged in, decode through both rings and the decode kernel twice,
# the pooling carried as float32 sums) against the float32 reference's full forward, relative L2 over
# the rows compared: all eight heads' logits of the last prompt position and of
# each of 24 decode steps of two prompts in the 4,096 bucket. Prompt 4,090
# crosses position 4,096 while it decodes (the ring restarts, 256 pooled rows
# become readable, a chunk ends on each side of the edge); prompt 3,700 ends a
# chunk mid-window (3,712) and reads 128 pooled rows throughout.
#
# Readings on the chip, 8 layers at the published widths (PR 40: the check
# lines of sixteen seeds, ``tools/evabyte_check_readings.py`` on one):
#
#   the engine                                        8.3e-3 to 9.0e-3
#   the reference, bfloat16 operands                  7.2e-3
#   the reference, float8_e4m3fn operands             9.9e-1   (has to fail)
#   the reference with pooled rows readable from
#   their chunk's end on, against the sound one       4.0e-2   (has to fail)
#   the engine against that reference                 4.1e-2   (has to fail)
#
# The engine reads 1.2 times the bfloat16 reference: it rounds more than its
# matmuls' operands (the rings' rows, the pooled rows, the branches between
# the float32 stream's additions). float8_e4m3fn loses the weights outright
# (drawn N(0, 0.01275^2), they lie under that format's smallest normal number,
# 0.0156), which is why it reads near 1. The limit is 2.2 times the engine's
# largest reading and half the chunk-visible reading (``visible="chunk"``: what
# a ring that slid, or a pooled row read a window early, would amount to; of
# the 50 rows compared, 32 read 128 pooled rows and 18 read 256), so it sees
# the mask as well as the precision.
LOGITS_REL_L2 = 2e-2
COUNTERS = (
    "eva_local_rows_read", "eva_pooled_rows_read", "eva_chunks_pooled",
    "eva_window_restarts", "eva_cache_bytes_moved",
)
RESIDENT = "eva_cache_resident_bytes"
PHASES = ("args", "dispatch", "fetch")  # of ``ServeEngine.phase_seconds``


def reference_rows(cell, params, prompts, seqs, operands=None, visible="window"):
    """The rows ``served_rows`` took, from the reference's full forward over
    each sequence (padded to the check's ``pad_to``; attention is causal and a
    pooled row is read only by later windows, so the padding changes nothing
    before it). ``operands`` and ``visible`` are the readings tool's: a lower
    precision, pooled rows readable too early."""
    import jax

    spec = cell.options["check"]
    steps, pad = int(spec["decode_steps"]), int(spec["pad_to"])
    ref_fn = jax.jit(
        lambda p, ids, first: reference_evabyte.forward(
            p, ids, cell.config, operands, visible, (first, steps + 1)
        )
    )
    rows = []
    for prompt, seq in zip(prompts, seqs):
        ids = np.zeros((1, pad), np.int32)
        ids[0, : len(seq)] = seq
        rows.append(np.asarray(ref_fn(params, ids, np.int32(len(prompt) - 1)))[0])
    return rows


def check_logits(cell, engine, seed, report, t_process) -> bool:
    """Prefill, then decoding through both rings, against the reference's full
    forward on the same tokens: logits of every head, never tokens. Outside
    the window."""
    before = {name: getattr(engine, name) for name in COUNTERS}
    prompts, seqs, got = served_rows(cell, engine, seed)
    want = reference_rows(cell, engine.params, prompts, seqs)
    rel, per_prompt = rel_l2(got, want)
    ok = math.isfinite(rel) and rel <= LOGITS_REL_L2
    report.line(
        "check", ok=ok, logits_rel_l2=rel, tolerance={"logits_rel_l2": LOGITS_REL_L2},
        reference="reference_evabyte", per_prompt_rel_l2=per_prompt,
        prompts=[len(p) for p in prompts], decode_steps=len(got[0]) - 1,
        rows_compared=sum(len(rows) for rows in got), heads=cell.config["num_pred_heads"],
        **{name: getattr(engine, name) - before[name] for name in COUNTERS},
        setup_so_far_s=time.perf_counter() - t_process,
    )
    return ok


def build(cell, devices, seed, report, t_process):
    """``serve_cell.build`` after asking the program whether it runs the
    configuration at all (one that reads no ``attention_class`` would build
    eight layers of ordinary attention under this model's name and slide a
    4,608-row ring)."""
    from opendiloco_tpu.models.llama import LlamaConfig

    try:
        runs = getattr(LlamaConfig.from_dict(cell.config), "eva", False)
    except (TypeError, ValueError) as e:
        runs = False
        report.line("refused", error=str(e))
    if not runs:
        raise RuntimeError(
            f"the program under test cannot run {cell.config_name}: its LlamaConfig "
            "reads no attention_class and has no EVA attention"
        )
    return serve_cell.build(cell, devices, seed, report, t_process)


def start(cell, devices, seed, trace, report, t_process):
    """``serve_cell.start`` with this configuration's reference in the check."""
    from opendiloco_tpu.serve import ContinuousBatcher

    cfg, engine = build(cell, devices, seed, report, t_process)
    held = costs_evabyte.cache_bytes_per_slot(cell.config, engine.max_context)
    report.line(
        "evabyte", params=costs_evabyte.param_count(cell.config),
        weights_resident_bytes=engine.weights_resident_bytes,
        window_ring_bytes=engine.cache_k.nbytes + engine.cache_v.nbytes,
        eva_cache_resident_bytes=engine.eva_cache_resident_bytes,
        cache_bytes_per_slot=held, window=cell.config["window_size"],
        chunk=cell.config["chunk_size"], pred_heads=cell.config["num_pred_heads"],
        # which form of EVA attention each program runs (a measured run is the
        # kernels': "pallas" and "flash"), as the engine itself says
        forms=getattr(engine, "eva_forms", None), decode_kernel=engine.decode_kernel,
    )
    check_ok = check_logits(cell, engine, seed, report, t_process)
    instrument = serve_cell.Instrument(engine) if trace == 1 else None
    return cfg, engine, check_ok, instrument, ContinuousBatcher(engine).start()


def snapshot(engine, batcher) -> dict:
    """``serve_cell.snapshot``, EVA's counters, what its state holds, and the
    seconds of a decode step's host phases (the engine's always-on sums)."""
    phases = getattr(engine, "phase_seconds", {}).get("decode", {})
    return {
        **serve_cell.snapshot(engine, batcher),
        **{name: getattr(engine, name, 0) for name in (*COUNTERS, RESIDENT)},
        **{f"decode_{phase}_s": phases.get(phase, 0.0) for phase in PHASES},
    }


def traced_stretch(cell, engine, batcher, compiles, report, keep_sending, meanwhile=None) -> dict:
    """``serve_cell.traced_stretch`` and, per traced decode step, the window
    rows and the pooled rows its attention read (over layers), as the
    program's spans carry them. Nothing where the spans carry none."""
    traced = serve_cell.traced_stretch(
        cell, engine, batcher, compiles, report, keep_sending, meanwhile
    )
    stretch = traced["stretch"]
    calls = [
        [args["eva_local_rows"], args["eva_pooled_rows"]]
        for args in program_obs.span_args(stretch.capture, "serve_decode", stretch.t0, stretch.t1)
        if "eva_local_rows" in args
    ]
    traced["counters"].update(traced_eva_calls=calls)
    report.line(
        "traced_eva", decode_steps=len(calls), local_rows=sum(c[0] for c in calls),
        pooled_rows=sum(c[1] for c in calls),
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
        report.line(
            "window_counters", **moved, **{RESIDENT: after[RESIDENT]},
            decode_steps=after["decode_steps"] - before["decode_steps"],
            decode_step_ms=(after["decode_s"] - before["decode_s"]) / steps * 1e3,
            prefill_s=after["prefill_s"] - before["prefill_s"],
            local_rows_per_step=moved["eva_local_rows_read"] / steps,
            pooled_rows_per_step=moved["eva_pooled_rows_read"] / steps,
            chunks_pooled_per_step=moved["eva_chunks_pooled"] / steps,
            restarts_per_step=moved["eva_window_restarts"] / steps,
            **{
                f"decode_{phase}_ms_per_step":
                (after[f"decode_{phase}_s"] - before[f"decode_{phase}_s"]) / steps * 1e3
                for phase in PHASES
            },
        )
        out = serve_cell.finish(
            before=before, after=after, check_ok=check_ok, batcher=batcher,
            extra_counters={**(extra_counters or {}), **moved}, **rest,
        )
        out["correct"] = bool(check_ok and out["failed"] == 0 and batcher.loop_error is None)
        return out

    loop = manifest.load_module(os.path.join(_BENCH, "drivers", "closed_loop.py"))
    view = dict(vars(serve_cell))
    view.update(start=start, snapshot=snapshot, traced_stretch=traced_stretch, finish=finish)
    loop.serve_cell = types.SimpleNamespace(**view)
    return loop.run(**kwargs)
