"""Serving cells: ``ServeEngine`` + ``ContinuousBatcher`` in this process,
static weights, the program's defaults, load from one generator thread.

Requests enter at ``ContinuousBatcher.submit`` (the HTTP front is
non-streaming and reports no first-token time, PERF.md section 7). Times are
the program's own stamps on ``Request`` (``t_first``, ``t_done``,
``time.perf_counter``) against the instant each request was *due*.
"""

from __future__ import annotations

import math
import threading
import time

import numpy as np

from odbench import costs, program_obs, reference, stats, traffic, xplane

# engine logits (bf16 compute over float32 weights, kernels, ring cache)
# against the float32 reference's full forward, relative L2 over the rows
# compared. Two equivalent bf16 paths drift 2.3e-2 apart over 12 layers (PR
# 21, on the chip); bf16 against float32 measured 1.3e-2 over the 32 layers
# of SmolLM2-360M and 2.8e-2 over the 24 of SmolLM2-1.7B (PR 23, on the chip).
# The tolerance is twice the larger: lower precision than bf16, a wrong
# position or a stale cache row gives 1e-1 and more.
LOGITS_REL_L2 = 6e-2
DRAIN_TIMEOUT_S = 120.0


def build(cell, devices, seed, report, t_process):
    """-> (model config, engine): weights drawn on the device from the seed
    in one jitted call. They are drawn in bfloat16 and the engine makes its
    float32 copy from them (its weight format), so that two float32 copies
    never stand side by side on the chip."""
    import jax
    import jax.numpy as jnp

    from opendiloco_tpu.models.llama import LlamaConfig, init_params
    from opendiloco_tpu.serve import ServeEngine

    cfg = LlamaConfig.from_dict(cell.config)
    opts = cell.options["engine"]
    with jax.default_device(devices[0]):
        draw = jax.jit(
            lambda key: jax.tree.map(
                lambda x: x.astype(jnp.bfloat16), init_params(key, cfg)
            )
        )
        params = draw(jax.random.key(traffic.jax_seed(seed)))
        engine = ServeEngine(
            cfg, params,
            num_slots=int(opts["num_slots"]),
            max_context=int(opts["max_context"]),
            prefill_buckets=tuple(opts["prefill_buckets"]),
        )
        del params
    jax.block_until_ready(engine.params)
    report.line(
        "built", params=costs.param_count(cell.config), slots=engine.num_slots,
        max_context=engine.max_context, prefill_buckets=engine.prefill_buckets,
        decode_kernel=engine.decode_kernel, weight_format=engine.weight_format,
        kv_cache_bytes=engine.num_slots * engine.max_context
        * costs.kv_bytes_per_token(cell.config),
        setup_so_far_s=time.perf_counter() - t_process,
    )
    return cfg, engine


def start(cell, devices, seed, trace, report, t_process):
    """What both serving drivers do first: the engine, the check against the
    reference, the ``--trace 1`` run's instrument, the batcher's loop started.
    -> (model config, engine, check passed, instrument or None, batcher)"""
    from opendiloco_tpu.serve import ContinuousBatcher

    cfg, engine = build(cell, devices, seed, report, t_process)
    check_ok = check_logits(cell, engine, seed, report, t_process)
    instrument = Instrument(engine) if trace == 1 else None
    batcher = ContinuousBatcher(engine).start()
    return cfg, engine, check_ok, instrument, batcher


def check_logits(cell, engine, seed, report, t_process) -> bool:
    """Prefill and then decode through the engine's cache against the
    reference's full forward on the same tokens: logits, never tokens."""
    import jax

    spec = cell.options["check"]
    lens, steps, pad = spec["prompt_tokens"], int(spec["decode_steps"]), int(spec["pad_to"])
    rng = traffic.rng_for(seed, 3)
    vocab = cell.config["vocab_size"]
    prompts = [rng.integers(traffic.FIRST_TOKEN, vocab, n).tolist() for n in lens]
    slots = engine.num_slots
    tokens, cache_lens = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
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
    ref_fn = jax.jit(lambda p, ids: reference.forward(p, ids, cell.config))
    num = den = 0.0
    for slot, prompt in enumerate(prompts):
        ids = np.zeros((1, pad), np.int32)
        ids[0, : len(seqs[slot])] = seqs[slot]
        ref = np.asarray(ref_fn(engine.params, ids))[0]
        first = len(prompt) - 1
        want = ref[first : first + steps + 1]
        have = np.stack(got[slot])
        num += float(np.sum((have - want) ** 2))
        den += float(np.sum(want ** 2))
    rel = math.sqrt(num / den)
    ok = math.isfinite(rel) and rel <= LOGITS_REL_L2
    report.line(
        "check", ok=ok, logits_rel_l2=rel, tolerance={"logits_rel_l2": LOGITS_REL_L2},
        prompts=lens, decode_steps=steps, rows_compared=len(prompts) * (steps + 1),
        setup_so_far_s=time.perf_counter() - t_process,
    )
    return ok


# the batcher runs a one-time kernel probe (compiles and times its kernels
# alone) when it first publishes gauges, after ``gauge_every_steps`` decode
# steps: the warm-up decodes past that, so the probe falls into set-up
WARM_TOKENS_BEYOND_GAUGES = 8


def warm_up(engine, batcher, vocab, seed) -> None:
    """One request per prefill bucket, decoded until the batcher has published
    its gauges once: every program of the window (prefill per bucket, insert,
    decode, the one-time kernel probe) has run."""
    rng = traffic.rng_for(seed, 5)
    tokens = batcher.gauge_every_steps + WARM_TOKENS_BEYOND_GAUGES
    reqs = [
        batcher.submit(rng.integers(traffic.FIRST_TOKEN, vocab, b).tolist(), max_new_tokens=tokens)
        for b in engine.prefill_buckets
    ]
    for r in reqs:
        if not r.wait(600.0) or r.error is not None:
            raise RuntimeError(f"warm-up request failed: {r.error}")


class Instrument:
    """The traced run's view into the engine, from outside it: the two device
    stages annotated on the profiler's clock, and the live cache rows of each
    decode step while the profiler runs."""

    def __init__(self, engine):
        import jax

        self.active = False
        self.decode_steps = 0
        self.live_rows = 0
        self.live_slots = 0
        annotate = jax.profiler.TraceAnnotation
        admit, decode_step = engine.admit, engine.decode_step

        def traced_admit(slot, prompt, **kw):
            with annotate("bench/prefill"):
                return admit(slot, prompt, **kw)

        def traced_decode_step(tokens, lens):
            if self.active:
                self.decode_steps += 1
                self.live_rows += int(np.sum(lens)) + int(np.count_nonzero(lens))
                self.live_slots += int(np.count_nonzero(lens))
            with annotate("bench/decode_step"):
                return decode_step(tokens, lens)

        engine.admit, engine.decode_step = traced_admit, traced_decode_step


class Tracer(threading.Thread):
    """Traces ``seconds`` of the window from ``start_at`` (perf_counter), off
    the generator's thread so that arrivals stay on time."""

    def __init__(self, trace_dir, start_at, seconds, instrument):
        super().__init__(name="bench-tracer", daemon=True)
        self.trace_dir, self.start_at, self.seconds = trace_dir, start_at, seconds
        self.instrument = instrument
        self.error = None

    def run(self) -> None:
        import jax

        try:
            time.sleep(max(0.0, self.start_at - time.perf_counter()))
            xplane.start(self.trace_dir)
            with jax.profiler.TraceAnnotation("bench/window", pc=repr(time.perf_counter())):
                self.instrument.active = True
                time.sleep(self.seconds)
                self.instrument.active = False
            jax.profiler.stop_trace()
        except BaseException as e:  # read by the driver after join()
            self.error = e


TRACED_SECONDS = 5.0


def start_tracer(cell, seconds, instrument) -> Tracer:
    """Trace ``TRACED_SECONDS`` (at most half the window) from two fifths
    into a window that starts now."""
    tracer = Tracer(
        xplane.trace_dir(cell.root, cell.name),
        time.perf_counter() + 0.4 * seconds, min(TRACED_SECONDS, 0.5 * seconds),
        instrument,
    )
    tracer.start()
    return tracer


def snapshot(engine, batcher) -> dict:
    return {
        "decode_s": engine.stage_seconds["decode"],
        "prefill_s": engine.stage_seconds["prefill"],
        "decode_steps": batcher.decode_steps,
        "new_tokens": batcher.total_new_tokens,
    }


def traced_stretch(cell, engine, batcher, compiles, report, keep_sending,
                   meanwhile=None) -> dict:
    """A ``--trace 2`` run's second part, after its window has closed and its
    numbers are taken: ``TRACED_SECONDS`` of the same traffic
    (``keep_sending(until)``) under the program's capture control;
    ``meanwhile()`` keeps the traffic up while the profiler's first start is
    thrown away. -> the stretch, with what the program's ``serve_decode``
    spans say each traced decode step read (its live cache rows plus the row
    it wrote, as ``Instrument`` counts them from outside in a ``--trace 1``
    run)."""
    trace_dir = xplane.trace_dir(cell.root, cell.name)
    with program_obs.Stretch(trace_dir, compiles, meanwhile) as stretch:
        before = snapshot(engine, batcher)
        sent = keep_sending(stretch.t0 + TRACED_SECONDS)
        after = snapshot(engine, batcher)
    steps = program_obs.span_args(stretch.capture, "serve_decode", stretch.t0, stretch.t1)
    seconds = stretch.t1 - stretch.t0
    counters = {
        "traced_decode_steps": len(steps),
        "traced_live_rows": sum(a["rows"] + a["slots"] for a in steps),
        "traced_live_slots": sum(a["slots"] for a in steps),
    }
    report.line(
        "traced", seconds=seconds, compiles_in_trace=stretch.compiles, **counters,
        decode_step_ms=(after["decode_s"] - before["decode_s"])
        / max(1, after["decode_steps"] - before["decode_steps"]) * 1e3,
        decode_tokens_per_s=(after["new_tokens"] - before["new_tokens"]) / seconds,
        span_seconds=program_obs.seconds_by_name(stretch.capture, "serve_"),
        request_traces=len(stretch.capture.requests), spans=len(stretch.capture.spans),
        spans_dropped=stretch.capture.dropped,
    )
    return {"stretch": stretch, "counters": counters, "sent": sent}


def tails(reqs_due: list, report) -> dict:
    """Per-request first-token wait (from the due instant) and time per
    output token, failures as +inf; p95 by the rule, the median beside it."""
    ttft, tpot, late = [], [], []
    for due, req in reqs_due:
        ok = req.error is None and req.t_done is not None and len(req.tokens) >= 2
        late.append(req.t_submit - due)
        ttft.append((req.t_first - due) * 1e3 if ok else math.inf)
        tpot.append(
            (req.t_done - req.t_first) / (len(req.tokens) - 1) * 1e3 if ok else math.inf
        )
    n = len(ttft)
    out = {"requests": n, "p95_samples_beyond": stats.samples_beyond(n, 95.0) if n else 0,
           "p95_supported": stats.supported(n, 95.0)}
    if n:
        out.update(
            ttft_p50_ms=stats.percentile(ttft, 50.0), ttft_p95_ms=stats.percentile(ttft, 95.0),
            tpot_p50_ms=stats.percentile(tpot, 50.0), tpot_p95_ms=stats.percentile(tpot, 95.0),
            generator_late_p50_ms=stats.percentile(late, 50.0) * 1e3,
            generator_late_max_ms=max(late) * 1e3,
            highest_supported_percentile=stats.highest_supported(n),
        )
    report.line("tails", **out)
    return out


def queue_waits_ms(reqs_due: list, traces=None) -> list:
    """Due instant to the slot, per traced request: the generator's lateness
    plus the program's own ``queue`` span (``obs/reqtrace``). ``traces``: a
    capture's completed request traces, else those the armed ring holds."""
    from opendiloco_tpu.obs import reqtrace

    if traces is None:
        ring = reqtrace.ring()
        if ring is None:
            return []
        traces = ring.traces()
    queue_ms = {}
    for tr in traces:
        for span in tr["spans"]:
            if span["stage"] == "queue":
                queue_ms[tr["id"]] = span["ms"]
    out = []
    for due, req in reqs_due:
        if req.trace in queue_ms:
            out.append((req.t_submit - due) * 1e3 + queue_ms[req.trace])
        else:
            out.append(math.inf)  # never reached a slot
    return out


def mint_trace():
    """A request-trace context where the program's ring is armed."""
    from opendiloco_tpu.obs import reqtrace

    ring = reqtrace.ring()
    return None if ring is None else ring.mint()


def finish(*, cell, peak, engine, batcher, before, after, window_s, reqs_due, in_window,
           check_ok, e2e, tail_facts, trace, tracer, instrument, traced=None,
           extra_counters=None):
    """The driver's return value, shared by the open and the closed loop.
    ``traced``: what ``traced_stretch`` returned, in a ``--trace 2`` run."""
    failed = sum(
        1 for _, r in reqs_due if r.error is not None or r.t_done is None
    )
    counters = {
        "window_s": window_s,
        "decode_s": after["decode_s"] - before["decode_s"],
        "prefill_s": after["prefill_s"] - before["prefill_s"],
        "decode_steps": after["decode_steps"] - before["decode_steps"],
        "new_tokens": after["new_tokens"] - before["new_tokens"],
        "admissions": sum(1 for _, r in reqs_due if r.t_first is not None),
        "slots": engine.num_slots,
        "chips": cell.chips,
        **(extra_counters or {}),
    }
    observations = {"counters": counters}
    if traced is not None:
        stretch = traced["stretch"]
        observations["trace"] = stretch.reduce(rehearsal=peak is None)
        counters.update(
            traced["counters"],
            queue_waits_ms=queue_waits_ms(traced["sent"], stretch.capture.requests),
        )
    elif trace:
        tracer.join(timeout=300.0)
        if tracer.error is not None:
            raise tracer.error
        observations["trace"] = xplane.reduce(
            tracer.trace_dir, program_obs.spans(), rehearsal=peak is None
        )
        counters.update(
            traced_decode_steps=instrument.decode_steps,
            traced_live_rows=instrument.live_rows,
            traced_live_slots=instrument.live_slots,
            queue_waits_ms=queue_waits_ms(reqs_due),
        )
    return {
        "correct": bool(
            check_ok and failed == 0 and tail_facts["p95_supported"]
            and batcher.loop_error is None
        ),
        "attempted": len(reqs_due),
        "failed": failed,
        "compiles_in_window": in_window,
        "compiles_in_trace": traced["stretch"].compiles if traced else 0,
        "trace_cost": traced["stretch"].cost if traced else {},
        "end_to_end": e2e,
        "observations": observations,
    }
