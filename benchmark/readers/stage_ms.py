"""Mean host wall of one engine stage per occurrence in the window:
``ServeEngine.stage_seconds[...]`` (each ends in a device-to-host read of the
tokens) over a count. ``params``: the counters' keys, ``seconds`` and ``count``."""


def read(obs, params):
    c = obs["counters"]
    seconds, count = c.get(params["seconds"]), c.get(params["count"])
    if not seconds or not count:
        return None
    return seconds / count * 1e3
