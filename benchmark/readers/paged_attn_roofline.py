"""The decode attention kernel's share of its roofline: the least time the
chip could take to read the live K/V rows of the traced decode steps
(``costs.paged_decode_cost``) over the summed device time of the kernel's
events. ``params``: ``needles``, substrings that pick the kernel's events."""

from odbench import costs, xplane


def read(obs, params):
    trace, c = obs.get("trace"), obs["counters"]
    if not trace or obs["peak"] is None or not c.get("traced_decode_steps"):
        return None
    ops = trace["ops"][sorted(trace["ops"])[0]]
    seconds, events = xplane.matching_seconds(ops, params["needles"])
    if not events:
        return None
    flops, nbytes = costs.paged_decode_cost(
        obs["cell"].config, c["traced_live_rows"], c["traced_live_slots"]
    )
    least, bound = costs.roofline_seconds(flops, nbytes, obs["peak"])
    share = 100.0 * least / seconds
    obs["report"].line(
        "paged_attn_roofline", kernel_events=events, kernel_seconds=seconds,
        decode_steps=c["traced_decode_steps"], live_rows=c["traced_live_rows"],
        least_seconds=least, bound=bound, share_pct=share,
    )
    return share
