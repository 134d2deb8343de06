"""The device's time for one chunk of a prompt admitted in chunks: the self
time of the chunk program's operations over the traced stretch, a chunk. The
whole chunk program lies under the scope ``params["scope"]``
(``odtp_serve_prefill``); the driver reads its instructions from the program's
text (``counters["dsa_ops"][scope]``: result name and result shape of each,
less those the decode program also has under the same name and shape, since a
trace's events carry no program: so the reading can be short of the chunk's
time by those, which the driver's ``traced_dsa`` line lists, and never holds a
decode step's) and this reader finds them again in the trace by both; the
chunks are the traced ``serve_prefill`` spans that carry a chunk's rows. This
is what an iteration of the cell's loop spends on the prefill: the host only
enqueues a chunk (``prefill_ms.videoqa`` is that, and the last chunk's read).
Nothing where the spans carry no chunks (a program without them)."""

import os

from odbench import manifest, xplane

result_shape = manifest.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "ssm_mixer_roofline.py")
).result_shape


def read(obs, params):
    trace = obs.get("trace")
    calls = obs["counters"].get("traced_dsa_calls") or []
    wanted = (obs["counters"].get("dsa_ops") or {}).get(params["scope"])
    chunks = sum(1 for call in calls if not call[3])
    if not trace or not chunks or not wanted:
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
    obs["report"].line(
        "prefill_chunk_device", scope=params["scope"], events=events, self_seconds=seconds,
        instructions_named=len(wanted), chunks=chunks, ms_per_chunk=1e3 * seconds / chunks,
    )
    return 1e3 * seconds / chunks
