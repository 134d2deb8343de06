"""Serving, callers that wait: one client per slot, each sending its next
request when the last is answered. One thread drives them all. The clients
start staggered (client i's first request is i/clients of the length), so that
in the window their requests end evenly in time, as a long-running job's do,
and not in waves. The window opens when every client's first request is done;
a request counts in the window in which it ends. A ``--trace 2`` run closes
that window untraced, then keeps the clients sending for a few traced seconds."""

from __future__ import annotations

import time

from odbench import serve_cell, traffic

POLL_S = 0.001
POOL = 8192  # requests drawn ahead for the clients to take in turn


def run(*, cell, devices, peak, seed, seconds, trace, t_process, compiles, report):
    cfg, engine, check_ok, instrument, batcher = serve_cell.start(
        cell, devices, seed, trace, report, t_process
    )
    mix = cell.traffic
    clients = engine.num_slots
    try:
        serve_cell.warm_up(engine, batcher, cfg.vocab_size, seed)
        pool = iter(traffic.requests(mix, POOL, cfg.vocab_size, seed))

        # a --trace 2 run's requests carry a trace in its traced stretch only
        def send(share=1.0, mint=trace == 1):
            a = next(pool)
            return batcher.submit(
                a.prompt, max_new_tokens=max(2, round(a.max_new_tokens * share)),
                trace=serve_cell.mint_trace() if mint else None,
            )

        def pump(until, ended=None, mint=trace == 1):
            """The clients until ``until``: whoever has its answer sends its
            next request. -> [(submit instant, request)] sent meanwhile"""
            sent = []
            while time.perf_counter() < until:
                for i, req in enumerate(inflight):
                    if req.t_done is not None:
                        if ended is not None and req.t_done <= until:
                            ended.append((req.t_submit, req))
                        inflight[i] = send(mint=mint)
                        sent.append((inflight[i].t_submit, inflight[i]))
                time.sleep(POLL_S)
            return sent

        # staggered start, outside the window
        inflight = [send((i + 1) / clients) for i in range(clients)]
        started = [False] * clients
        while not all(started):
            for i, req in enumerate(inflight):
                if req.t_done is not None:
                    if req.error is not None:
                        raise RuntimeError(f"ramp request failed: {req.error}")
                    started[i] = True
                    inflight[i] = send()
            time.sleep(POLL_S)
        report.line("warm", clients=clients, setup_so_far_s=time.perf_counter() - t_process)

        requests_before = compiles.requests
        before = serve_cell.snapshot(engine, batcher)
        setup_s = time.perf_counter() - t_process
        t0 = time.perf_counter()
        t1 = t0 + seconds
        tracer = serve_cell.start_tracer(cell, seconds, instrument) if trace == 1 else None
        done = []  # (submit instant, request) of those that ended in the window
        pump(t1, done)
        after = serve_cell.snapshot(engine, batcher)
        t_after = time.perf_counter()
        in_window = compiles.requests - requests_before
        open_at_close = list(inflight)  # the traced stretch replaces them
        traced = None
        if trace == 2:
            traced = serve_cell.traced_stretch(
                cell, engine, batcher, compiles, report,
                lambda until: pump(until, mint=True),
                meanwhile=lambda: pump(time.perf_counter() + POLL_S),
            )
    finally:
        batcher.stop()

    tail_facts = serve_cell.tails(done, report)
    # every output token produced inside the window: the decode steps' tokens
    # and the first token of each prefill that ended in it
    prefills = sum(1 for r in [*[r for _, r in done], *open_at_close]
                   if r.t_first is not None and t0 <= r.t_first <= t1)
    tokens = after["new_tokens"] - before["new_tokens"] + prefills
    rate = tokens / (t_after - t0)
    report.line(
        "window", requests_done=len(done), output_tokens=tokens, window_s=t_after - t0,
        serve_tokens_per_s=rate,
        tokens_of_requests_done_per_s=sum(len(r.tokens) for _, r in done) / seconds,
        compiles_in_window=in_window, setup_s=setup_s,
    )
    e2e = {"setup_s": setup_s, "serve_tokens_per_s": rate,
           "tpot_p95_ms": tail_facts.get("tpot_p95_ms", float("inf")),
           "ttft_p95_ms": tail_facts.get("ttft_p95_ms", float("inf"))}
    return serve_cell.finish(
        cell=cell, peak=peak, engine=engine, batcher=batcher, before=before, after=after,
        window_s=t_after - t0, reqs_due=done, in_window=in_window, check_ok=check_ok,
        e2e=e2e, tail_facts=tail_facts, trace=trace, tracer=tracer,
        instrument=instrument, traced=traced, extra_counters={"clients": clients},
    )
