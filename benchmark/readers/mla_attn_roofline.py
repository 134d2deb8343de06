"""The latent decode attention kernel's share of its roofline: the least time
the chip could take for the traced decode steps' attention in the absorbed
form (``costs_glm_flash.mla_decode_cost`` of each step's live latent rows and
live slots, as the program's ``serve_decode`` spans carry them: a row counted
once a layer; the bound taken step by step) over the summed device time of
the kernel's events. ``params``: ``needles``, substrings that pick the
kernel's events. Nothing where the program's spans carry no ``latent_rows``."""

from odbench import costs, costs_glm_flash, xplane


def read(obs, params):
    trace, calls = obs.get("trace"), obs["counters"].get("traced_mla_calls")
    if not trace or obs["peak"] is None or not calls:
        return None
    ops = trace["ops"][sorted(trace["ops"])[0]]
    seconds, events = xplane.matching_seconds(ops, params["needles"])
    if not events:
        return None
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for layer_rows, slots in calls:
        flops, nbytes = costs_glm_flash.mla_decode_cost(obs["cell"].config, layer_rows, slots)
        t, bound = costs.roofline_seconds(flops, nbytes, obs["peak"])
        least += t
        bounds[bound] += 1
    share = 100.0 * least / seconds
    obs["report"].line(
        "mla_attn_roofline", kernel_events=events, kernel_seconds=seconds, decode_steps=len(calls),
        latent_rows=sum(c[0] for c in calls), live_slots=sum(c[1] for c in calls),
        least_seconds=least, steps_by_bound=bounds, share_pct=share,
    )
    return share
