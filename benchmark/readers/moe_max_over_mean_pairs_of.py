"""``moe_max_over_mean_pairs`` for any routed configuration: the busiest held
expert's token-expert pairs over the mean held expert's, from the engine's
counters ``moe_max_pairs`` and ``moe_pairs`` (the held experts' pairs, each
summed over the layers of every prefill and decode step in the window).
``params``: ``held_key``, the key of the configuration's file that counts the
experts this chip holds (the router's width is the deployment's and not this
chip's). 1 is perfect balance among the experts held here. Nothing from a
program without the counters, or in a cell without the key."""


def read(obs, params):
    c, held = obs["counters"], obs["cell"].config.get(params["held_key"])
    pairs, busiest = c.get("moe_pairs"), c.get("moe_max_pairs")
    if not pairs or not busiest or not held:
        return None
    return held * busiest / pairs
