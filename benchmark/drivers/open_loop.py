"""Serving, independent users: arrivals on a schedule drawn from the seed,
whatever the system does. Every request due inside the window is measured,
and the run lasts until each has been answered. A ``--trace 2`` run then
offers a few more seconds of the same mix, traced."""

from __future__ import annotations

import time

from odbench import serve_cell, traffic


def window(batcher, arrivals, seconds, trace: bool = False):
    """Submit ``arrivals`` on their schedule for ``seconds`` and wait for the
    answers. -> ([(due instant, request)], facts about the backlog)"""
    t0 = time.perf_counter()
    reqs_due, backlog_mid = [], None
    for a in arrivals:
        due = t0 + a.due_s
        if backlog_mid is None and a.due_s >= seconds / 2:
            backlog_mid = sum(1 for _, r in reqs_due if r.t_done is None)
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        req = batcher.submit(
            a.prompt, max_new_tokens=a.max_new_tokens,
            trace=serve_cell.mint_trace() if trace else None,
        )
        reqs_due.append((due, req))
    time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    answered = sum(1 for _, r in reqs_due if r.t_done is not None)
    deadline = time.perf_counter() + serve_cell.DRAIN_TIMEOUT_S
    for _, req in reqs_due:
        req.wait(max(0.0, deadline - time.perf_counter()))
    return reqs_due, {
        "offered_rate_per_s": len(reqs_due) / seconds,
        "completed_rate_per_s": answered / seconds,
        "backlog_mid": backlog_mid,
        "backlog_end": len(reqs_due) - answered,
        "drain_s": time.perf_counter() - (t0 + seconds),
    }


def run(*, cell, devices, peak, seed, seconds, trace, t_process, compiles, report):
    cfg, engine, check_ok, instrument, batcher = serve_cell.start(
        cell, devices, seed, trace, report, t_process
    )
    try:
        serve_cell.warm_up(engine, batcher, cfg.vocab_size, seed)
        arrivals = traffic.open_loop(cell.traffic, seconds, cfg.vocab_size, seed)
        report.line("warm", arrivals=len(arrivals), rate_per_s=cell.traffic["rate_per_s"],
                    setup_so_far_s=time.perf_counter() - t_process)

        requests_before = compiles.requests
        before = serve_cell.snapshot(engine, batcher)
        setup_s = time.perf_counter() - t_process
        tracer = serve_cell.start_tracer(cell, seconds, instrument) if trace == 1 else None
        reqs_due, backlog = window(batcher, arrivals, seconds, trace == 1)
        after = serve_cell.snapshot(engine, batcher)
        in_window = compiles.requests - requests_before
        traced = None
        if trace == 2:
            more = traffic.open_loop(
                cell.traffic, serve_cell.TRACED_SECONDS, cfg.vocab_size, seed + 1
            )

            def keep_sending(until):
                return window(batcher, more, until - time.perf_counter(), True)[0]

            traced = serve_cell.traced_stretch(
                cell, engine, batcher, compiles, report, keep_sending
            )
    finally:
        batcher.stop()

    tail_facts = serve_cell.tails(reqs_due, report)
    report.line(
        "window", **backlog, compiles_in_window=in_window, setup_s=setup_s,
        output_tokens_per_s=(after["new_tokens"] - before["new_tokens"])
        / (seconds + backlog["drain_s"]),
    )
    e2e = {"setup_s": setup_s}
    for key in ("ttft_p95_ms", "tpot_p95_ms"):
        e2e[key] = tail_facts.get(key, float("inf"))
    return serve_cell.finish(
        cell=cell, peak=peak, engine=engine, batcher=batcher, before=before,
        after=after, window_s=seconds + backlog["drain_s"], reqs_due=reqs_due,
        in_window=in_window, check_ok=check_ok, e2e=e2e, tail_facts=tail_facts,
        trace=trace, tracer=tracer, instrument=instrument, traced=traced,
        extra_counters=backlog,
    )
