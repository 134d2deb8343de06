"""From the instant a request was due to the instant it got a slot: the
generator's lateness plus the program's ``queue`` span (``obs/reqtrace``),
95th percentile by the benchmark's rule: with fewer samples beyond it than
the rule asks, nothing."""

from odbench import stats


def read(obs, params):
    waits = obs["counters"].get("queue_waits_ms")
    if not waits or not stats.supported(len(waits), 95.0):
        return None
    return stats.percentile(waits, 95.0)
