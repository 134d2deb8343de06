"""One kind of attention's share of its roofline in a stack of full and
sliding grouped-query layers: the least time the chip could take for what the
traced decode steps and prefill chunks asked of it (the cost function
``params["cost"]`` of the module ``odbench/<params["costs"]>.py`` over each
call's (query, row) pairs and distinct rows of that kind, as the driver read
them from the program's ``serve_decode`` and ``serve_prefill`` spans into
``counters["traced_kind_calls"]``; the bound taken call by call) over the
device self time of the operations that computed it: the kernels and
instructions of the compiled decode and chunk programs whose ``op_name`` lies
under the scope ``params["scope"]`` (``odtp_swa``: the decode kernel under its
window and the chunk's banded form; ``odtp_full_attn``: the decode kernel over
the whole ring and the chunk's tiled form), which the driver reads from the
programs' text (``counters["dsa_ops"][scope]``: result name and result shape
of each) and this reader finds again in the trace by both. As in
``dots3_roofline``, an operation of another program with the same name and
shape is counted too, which can only lower the share. ``params["columns"]``:
where a call's pairs and rows of this kind stand. Nothing where the spans
carry no such rows (a program without these layers)."""

import importlib
import os

from odbench import costs, manifest, xplane

result_shape = manifest.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "ssm_mixer_roofline.py")
).result_shape


def read(obs, params):
    trace = obs.get("trace")
    calls = obs["counters"].get("traced_kind_calls")
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
    cost = getattr(importlib.import_module(f"odbench.{params['costs']}"), params["cost"])
    pairs, rows = params["columns"]
    least, bounds = 0.0, {"compute": 0, "memory": 0}
    for call in calls:
        flops, nbytes = cost(obs["cell"].config, call[pairs], call[rows])
        t, bound = costs.roofline_seconds(flops, nbytes, obs["peak"])
        least += t
        bounds[bound] += 1
    share = 100.0 * least / seconds
    obs["report"].line(
        "kind_roofline", scope=params["scope"], events=events, self_seconds=seconds,
        instructions_named=len(wanted), calls=len(calls), pairs=sum(c[pairs] for c in calls),
        rows=sum(c[rows] for c in calls), least_seconds=least, calls_by_bound=bounds,
        share_pct=share,
    )
    return share
