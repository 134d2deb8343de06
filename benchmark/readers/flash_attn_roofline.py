"""Flash attention's share of its roofline in the train step: the least time
the chip could take for the attention that the traced steps require
(``costs.flash_train_cost``: forward and backward once, no recomputation)
over the summed device time of the step's attention kernels. ``params``:
``needles``, substrings that pick those kernels' events out of the trace."""

from odbench import costs, xplane


def read(obs, params):
    trace, c = obs.get("trace"), obs["counters"]
    if not trace or obs["peak"] is None or not c.get("traced_steps"):
        return None
    ops = trace["ops"][sorted(trace["ops"])[0]]
    seconds, events = xplane.matching_seconds(ops, params["needles"])
    if not events:
        return None
    flops, nbytes = costs.flash_train_cost(
        obs["cell"].config, c["global_batch"] // c["chips"], c["seq_length"]
    )
    least, bound = costs.roofline_seconds(flops, nbytes, obs["peak"])
    share = 100.0 * least * c["traced_steps"] / seconds
    obs["report"].line(
        "flash_attn_roofline", kernel_events=events, kernel_seconds=seconds,
        steps=c["traced_steps"], least_seconds_per_step=least, bound=bound,
        flops_per_step=flops, bytes_per_step=nbytes, share_pct=share,
    )
    return share
