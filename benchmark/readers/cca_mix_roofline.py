"""CCA's projections and convolutions' share of their roofline: the least time
the chip could take for what the traced prefills and decode steps put through
them (``costs_zaya.cca_mix_cost`` of each call's live tokens, as the program's
``serve_prefill`` and ``serve_decode`` spans carry them as ``cca_tokens``; the
bound taken call by call, a prefill's being compute and a decode step's the
projections' weights) over the device self time of their operations. No kernel
of the program's own computes them: their operations are those instructions of
the compiled prefill and decode programs whose ``op_name`` lies under the
``odtp_cca`` scope, which the driver reads from the programs' text
(``counters["cca_ops"]``: result name and result shape of each) and this
reader finds again in the trace by both. The attention kernel and the output
projection lie outside that scope. As in ``ssm_mixer_roofline``, a trace's
event carries no program, so an operation of another program with the same
name *and* result shape is counted too (the driver's ``traced_cca`` line lists
those pairs): that can only add time, so it can only lower the share."""

import os

from odbench import costs, costs_zaya, manifest, xplane

result_shape = manifest.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "ssm_mixer_roofline.py")
).result_shape


def read(obs, params):
    trace = obs.get("trace")
    calls, wanted = obs["counters"].get("traced_cca_calls"), obs["counters"].get("cca_ops")
    if not trace or obs["peak"] is None or not calls or not wanted:
        return None
    wanted = {tuple(pair) for pair in wanted}
    ops = trace["ops"][sorted(trace["ops"])[0]]
    seconds, events = 0.0, 0
    for name, self_ns, detail in xplane.self_times(ops):
        if (name.split(" ")[0], result_shape(detail)) in wanted:
            seconds += self_ns / 1e9
            events += 1
    if not events:
        return None
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for tokens, sequences, decode in calls:
        flops, nbytes = costs_zaya.cca_mix_cost(obs["cell"].config, tokens, sequences, bool(decode))
        t, bound = costs.roofline_seconds(flops, nbytes, obs["peak"])
        least += t
        bounds[bound] += 1
    share = 100.0 * least / seconds
    obs["report"].line(
        "cca_mix_roofline", cca_events=events, cca_self_seconds=seconds,
        instructions_named=len(wanted), calls=len(calls),
        tokens=sum(c[0] for c in calls), least_seconds=least, calls_by_bound=bounds,
        share_pct=share,
    )
    return share
