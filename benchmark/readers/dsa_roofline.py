"""A part of learned sparse attention's share of its roofline: the least time
the chip could take for what the traced decode steps and prefill chunks asked
of it (``costs_keye``'s cost named by ``params["cost"]`` of each call's rows,
as the program's ``serve_decode`` and ``serve_prefill`` spans carry them in
``dsa_rows_scored`` / ``dsa_rows_selected``; the bound taken call by call) over
the device self time of the operations that computed it. No kernel of the
program's own computes them: their operations are those instructions of the
compiled decode and chunk programs whose ``op_name`` lies under the scope
``params["scope"]`` (``odtp_dsa_index``: scoring and selection;
``odtp_dsa_attn``: the attention under the selection), which the driver reads
from the programs' text (``counters["dsa_ops"][scope]``: result name and result
shape of each) and this reader finds again in the trace by both. As in
``cca_mix_roofline``, an operation of another program with the same name and
shape is counted too, which can only lower the share. The cost is of the work
the equations ask: an implementation that reads every live row under a mask
is credited the chosen rows alone. Nothing where the spans carry no such rows
(a program without the indexer)."""

import os

from odbench import costs, costs_keye, manifest, xplane

result_shape = manifest.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "ssm_mixer_roofline.py")
).result_shape


def read(obs, params):
    trace = obs.get("trace")
    calls = obs["counters"].get("traced_dsa_calls")
    wanted = (obs["counters"].get("dsa_ops") or {}).get(params["scope"])
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
    cost = getattr(costs_keye, params["cost"])
    column = {"index_cost": 0, "sparse_attn_cost": 1}[params["cost"]]
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for call in calls:  # [rows scored, rows selected, distinct rows read, decode step?]
        flops, nbytes = cost(obs["cell"].config, call[column], call[2])
        t, bound = costs.roofline_seconds(flops, nbytes, obs["peak"])
        least += t
        bounds[bound] += 1
    share = 100.0 * least / seconds
    obs["report"].line(
        "dsa_roofline", scope=params["scope"], events=events, self_seconds=seconds,
        instructions_named=len(wanted), calls=len(calls),
        decode_steps=sum(1 for c in calls if c[3]), rows=sum(c[column] for c in calls),
        least_seconds=least, calls_by_bound=bounds, share_pct=share,
    )
    return share
