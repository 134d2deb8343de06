"""How unevenly the router loaded the experts in the window: the busiest
expert's token-expert pairs over the mean expert's, from the engine's
counters ``moe_max_pairs`` and ``moe_pairs`` (each summed over the layers of
every prefill and decode step, so the ratio is the pairs-weighted mean of
the per-layer ratios). 1 is perfect balance; a grouped matmul's longest
group, and an expert-parallel step's slowest chip, follow it."""


def read(obs, params):
    c = obs["counters"]
    pairs, busiest = c.get("moe_pairs"), c.get("moe_max_pairs")
    if not pairs or not busiest:
        return None
    return obs["cell"].config["num_experts"] * busiest / pairs
