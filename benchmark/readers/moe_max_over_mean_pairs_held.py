"""``moe_max_over_mean_pairs`` for a layer that holds a share of the experts:
the busiest held expert's token-expert pairs over the mean held expert's, from
the engine's counters ``moe_max_pairs`` and ``moe_pairs``, which then count
the ``num_local_experts`` held experts' pairs alone (the router's width,
``num_experts``, is the deployment's and not this chip's). 1 is perfect
balance among the experts held here."""


def read(obs, params):
    c = obs["counters"]
    pairs, busiest = c.get("moe_pairs"), c.get("moe_max_pairs")
    if not pairs or not busiest:
        return None
    return obs["cell"].config["num_local_experts"] * busiest / pairs
