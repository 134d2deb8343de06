"""The Mamba-2 mixers' share of their roofline: the least time the chip could
take for what the traced prefills and decode steps put through the mixers
(``costs_granite_h.ssm_mixer_cost`` of each call's live tokens, as the
program's ``serve_prefill`` and ``serve_decode`` spans carry them; the bound
taken call by call, a prefill's being compute and a decode step's the
mixers' weights and the slots' states) over the device self time of the
mixers' operations. No kernel of the program's own computes the mixer: its
operations are those instructions of the compiled prefill and decode programs
whose ``op_name`` lies under the ``odtp_ssm`` scope, which the driver reads
from the programs' text (``counters["ssm_ops"]``: result name and result
shape of each) and this reader finds again in the trace by both.

Two things the share leaves out or lets in. The cast of the mixers' float32
weights to the dtype the matmuls read runs before the layers' loop, outside
the scope (``llama._serving_boundary``, every leaf at once): its time is not
in the denominator, and the weights are credited at the 2 bytes an element
the mixers' own operations read. And a trace's event carries no program, so
an operation of another program that has a mixer operation's name *and*
result shape is counted as a mixer's (the driver's ``traced_mixers`` line
lists those pairs, ``named_elsewhere_too``): that can only add time, so it
can only lower the share."""

import re

from odbench import costs, costs_granite_h, xplane

_SHAPE = re.compile(r"\(?(\w+\[[\d,]*\])")


def result_shape(detail: str) -> str:
    """``bf16[8,128]{1,0} fusion(...`` -> ``bf16[8,128]`` (a tuple's first)."""
    found = _SHAPE.match(detail)
    return found.group(1) if found else ""


def read(obs, params):
    trace = obs.get("trace")
    calls, wanted = obs["counters"].get("traced_ssm_calls"), obs["counters"].get("ssm_ops")
    if not trace or obs["peak"] is None or not calls or not wanted:
        return None
    wanted = {tuple(pair) for pair in wanted}
    ops = trace["ops"][sorted(trace["ops"])[0]]
    seconds, events = 0.0, 0
    for name, self_ns, detail in xplane.self_times(ops):
        if (name.split(" ")[0], result_shape(detail)) in wanted:
            seconds += self_ns / 1e9
            events += 1
    if not events:
        return None
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for tokens, sequences, decode in calls:
        flops, nbytes = costs_granite_h.ssm_mixer_cost(
            obs["cell"].config, tokens, sequences, bool(decode)
        )
        t, bound = costs.roofline_seconds(flops, nbytes, obs["peak"])
        least += t
        bounds[bound] += 1
    share = 100.0 * least / seconds
    obs["report"].line(
        "ssm_mixer_roofline", mixer_events=events, mixer_self_seconds=seconds,
        instructions_named=len(wanted), calls=len(calls),
        tokens=sum(c[0] for c in calls), least_seconds=least, calls_by_bound=bounds,
        share_pct=share,
    )
    return share
