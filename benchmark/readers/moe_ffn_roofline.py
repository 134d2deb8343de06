"""The routed FFN's grouped matmuls' share of their roofline: the least time
the chip could take for what the traced prefills and decode steps routed
(``costs_olmoe.routed_ffn_cost`` of each call's token-expert pairs and experts
hit, as the program's spans carry them; the bound taken call by call, a
prefill's being compute and a decode step's the experts' weights) over the
summed device time of the grouped-matmul events. ``params``: ``needles``,
substrings that pick those events by their result name."""

from odbench import costs, costs_olmoe, xplane


def read(obs, params):
    trace, calls = obs.get("trace"), obs["counters"].get("traced_moe_calls")
    if not trace or obs["peak"] is None or not calls:
        return None
    ops = trace["ops"][sorted(trace["ops"])[0]]
    seconds, events = xplane.matching_seconds(ops, params["needles"])
    if not events:
        return None
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for pairs, experts_hit in calls:
        flops, nbytes = costs_olmoe.routed_ffn_cost(obs["cell"].config, pairs, experts_hit)
        t, bound = costs.roofline_seconds(flops, nbytes, obs["peak"])
        least += t
        bounds[bound] += 1
    share = 100.0 * least / seconds
    obs["report"].line(
        "moe_ffn_roofline", kernel_events=events, kernel_seconds=seconds, calls=len(calls),
        pairs=sum(c[0] for c in calls), experts_hit=sum(c[1] for c in calls),
        least_seconds=least, calls_by_bound=bounds, share_pct=share,
    )
    return share
