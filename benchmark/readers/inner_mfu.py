"""Model FLOP/s utilization of the inner step: the FLOPs that forward and
backward require for one step's tokens (``costs.train_flops_per_token``;
recomputation is not credited) over the median step time and the chips' peak."""

import statistics

from odbench import costs


def read(obs, params):
    c = obs["counters"]
    dts = c.get("inner_step_dts_s")
    if not dts or obs["peak"] is None:
        return None
    flops = costs.train_flops_per_token(obs["cell"].config, c["seq_length"]) * c["tokens_per_step"]
    return 100.0 * flops / statistics.median(dts) / (c["chips"] * obs["peak"].bf16_flops)
