"""A part of dots3-note-prev's attention's share of its roofline: the least
time the chip could take for what the traced decode steps and prefill chunks
asked of it (``costs_dots3``'s cost named by ``params["cost"]`` of each call's
rows, as the program's ``serve_decode`` and ``serve_prefill`` spans carry them;
the bound taken call by call) over the device self time of the operations that
computed it: the kernels and instructions of the compiled decode and chunk
programs whose ``op_name`` lies under the scope ``params["scope"]``
(``odtp_dsa_index``: the full layers' scoring and selection; ``odtp_dsa_attn``:
their attention under the selection; ``odtp_swa``: the sliding layers'
attention under the window), which the driver reads from the programs' text
(``counters["dsa_ops"][scope]``: result name and result shape of each) and this
reader finds again in the trace by both. As in ``dsa_roofline``, an operation
of another program with the same name and shape is counted too, which can only
lower the share, and the cost is of the work the equations ask: a form that
reads every live row under a mask is credited the chosen rows alone. Nothing
where the spans carry no such rows (a program without these layers)."""

import os

from odbench import costs, costs_dots3, manifest, xplane

result_shape = manifest.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "ssm_mixer_roofline.py")
).result_shape

# a traced call: [index pairs scored, pairs chosen, distinct full rows read,
# decode step?, window pairs, distinct window rows]; a cost's (pairs, rows)
COLUMNS = {"index_cost": (0, 2), "sparse_mla_cost": (1, 2), "window_mla_cost": (4, 5)}


def read(obs, params):
    trace = obs.get("trace")
    calls = obs["counters"].get("traced_dots3_calls")
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
    cost = getattr(costs_dots3, params["cost"])
    pairs, rows = COLUMNS[params["cost"]]
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for call in calls:
        flops, nbytes = cost(obs["cell"].config, call[pairs], call[rows])
        t, bound = costs.roofline_seconds(flops, nbytes, obs["peak"])
        least += t
        bounds[bound] += 1
    share = 100.0 * least / seconds
    obs["report"].line(
        "dots3_roofline", scope=params["scope"], events=events, self_seconds=seconds,
        instructions_named=len(wanted), calls=len(calls),
        decode_steps=sum(1 for c in calls if c[3]), pairs=sum(c[pairs] for c in calls),
        least_seconds=least, calls_by_bound=bounds, share_pct=share,
    )
    return share
