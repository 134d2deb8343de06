"""Median dispatch-to-dispatch interval of the non-boundary steps under the
loop's one-step-behind loss fetch (what ``train.py`` logs as ``time_taken``).
In steady state the fetch back-pressures it to the device's step time."""

import statistics


def read(obs, params):
    dts = obs["counters"].get("inner_step_dts_s")
    return statistics.median(dts) * 1e3 if dts else None
