"""Share of the traced window in which no operation ran on the device: one
minus the union of the device's operation intervals over the window, the
mean over the chips used."""


def read(obs, params):
    trace = obs.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
