"""EVA's decode attention's share of its roofline: the least time the chip
could take to read what the traced decode steps' attention read of a slot's
two rings (``costs_evabyte.eva_decode_cost`` of each step's window rows and
pooled rows, as the program's ``serve_decode`` spans carry them in
``eva_local_rows`` and ``eva_pooled_rows``; the bound taken step by step)
over the summed device time of the events of the kernels that compute it.
``params``: ``needles``, substrings that pick those events. Nothing where the
program's spans carry no such rows."""

from odbench import costs, costs_evabyte, xplane


def read(obs, params):
    trace, calls = obs.get("trace"), obs["counters"].get("traced_eva_calls")
    if not trace or obs["peak"] is None or not calls:
        return None
    ops = trace["ops"][sorted(trace["ops"])[0]]
    seconds, events = xplane.matching_seconds(ops, params["needles"])
    if not events:
        return None
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for local, pooled in calls:
        flops, nbytes = costs_evabyte.eva_decode_cost(obs["cell"].config, local, pooled)
        t, bound = costs.roofline_seconds(flops, nbytes, obs["peak"])
        least += t
        bounds[bound] += 1
    share = 100.0 * least / seconds
    obs["report"].line(
        "eva_attn_roofline", kernel_events=events, kernel_seconds=seconds, decode_steps=len(calls),
        local_rows=sum(c[0] for c in calls), pooled_rows=sum(c[1] for c in calls),
        least_seconds=least, steps_by_bound=bounds, share_pct=share,
    )
    return share
