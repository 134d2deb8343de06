"""What a round costs beyond its inner steps: the median over the window's
rounds of (round wall - local_steps x median inner step)."""

import statistics


def read(obs, params):
    c = obs["counters"]
    walls, dts = c.get("round_walls_s"), c.get("inner_step_dts_s")
    if not walls or not dts:
        return None
    inner = statistics.median(dts)
    return statistics.median(w - c["local_steps"] * inner for w in walls) * 1e3
