"""The routed FFN's grouped matmuls' share of their roofline, for any routed
configuration: the least time the chip could take for what the traced
prefills and decode steps routed to the held experts
(``costs_routed.routed_ffn_cost`` of each call's token-expert pairs and
experts hit, as the program's spans carry them; the bound taken call by call)
over the summed device time of the grouped-matmul events. ``params``:
``needles``, substrings that pick those events by their result name, and
``width_key``, the key of the configuration's file that holds the experts'
width. Nothing in a cell whose configuration has no such key."""

from odbench import costs, costs_routed, xplane


def read(obs, params):
    trace, calls = obs.get("trace"), obs["counters"].get("traced_moe_calls")
    if not trace or obs["peak"] is None or not calls:
        return None
    cfg, width_key = obs["cell"].config, params["width_key"]
    if width_key not in cfg:
        return None
    ops = trace["ops"][sorted(trace["ops"])[0]]
    seconds, events = xplane.matching_seconds(ops, params["needles"])
    if not events:
        return None
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for pairs, experts_hit in calls:
        flops, nbytes = costs_routed.routed_ffn_cost(cfg, pairs, experts_hit, width_key)
        t, bound = costs.roofline_seconds(flops, nbytes, obs["peak"])
        least += t
        bounds[bound] += 1
    share = 100.0 * least / seconds
    obs["report"].line(
        "routed_ffn_roofline", width_key=width_key, width=cfg[width_key],
        kernel_events=events, kernel_seconds=seconds, calls=len(calls),
        held_pairs=sum(c[0] for c in calls), held_experts_hit=sum(c[1] for c in calls),
        least_seconds=least, calls_by_bound=bounds, share_pct=share,
    )
    return share
