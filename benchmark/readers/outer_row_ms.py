"""One part of the outer boundary as the optimizer's own row times it: the
median, over every round of the measured window, of one key of the row that
``DiLoCoOptimizer`` returns with a boundary step (``outer_d2h_s``: the
pseudo-gradient's fetch, from its own start to its own end in the fetch
thread; ``outer_allreduce_s``; ``outer_apply_s``: H2D of the average and the
apply's dispatch, up to the optimizer's return). ``params``: ``key``."""

import statistics


def read(obs, params):
    rows = obs["counters"].get("outer_rows") or []
    values = [row[params["key"]] for row in rows if params["key"] in row]
    return statistics.median(values) * 1e3 if values else None
