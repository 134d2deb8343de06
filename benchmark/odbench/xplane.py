"""From the profiler's ``.xplane.pb`` to the numbers the readers use.

Two steps, so that the second can be checked on a small recorded trace
(``tests/benchmark/fixtures``): ``extract`` turns the protobuf into plain
lists (the only step that needs JAX), and everything else works on those.

The plain form::

    {"devices": {"/device:TPU:0": [[name, start_ns, dur_ns, detail], ...]},
     "host":    [[thread, name, start_ns, dur_ns, stats], ...]}

``devices`` holds each device plane's line of XLA operations (nested events
included, as the profiler records them: a ``while`` spans its body's
operations). ``host`` holds the host plane's events whose names start with
one of ``HOST_PREFIXES``: the benchmark's own annotations. Both are on the
profiler's one clock, nanoseconds from the start of the trace.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
HOST_PREFIXES = ("bench/",)
# a device event's name is its whole HLO instruction; ``short_name`` keeps
# the result's name, the opcode and a custom call's target, and ``detail``
# keeps the start of the rest (the result's shape tells kernels apart)
DETAIL_CHARS = 96
_OPCODE = re.compile(r"[\}\)\]] ([a-z][a-z0-9\-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(hlo: str) -> tuple[str, str]:
    """``%x.1 = bf16[8]{0} custom-call(...), custom_call_target="t"`` ->
    (``%x.1 custom-call:t``, ``bf16[8]{0} custom-call(...``)."""
    lhs, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo, ""
    opcode = _OPCODE.search(rest)
    name = lhs + (" " + opcode.group(1) if opcode else "")
    target = _TARGET.search(rest)
    if target:
        name += ":" + target.group(1)
    return name, rest[:DETAIL_CHARS]


def trace_dir(root: str, cell: str) -> str:
    """Where a traced run of ``cell`` keeps its trace: inside the checkout."""
    return os.path.join(root, ".bench_trace", cell)


def start(log_dir: str) -> None:
    """Start the profiler into an emptied ``log_dir``: device operations and
    the host's annotations, no Python call tracing (it slows the host)."""
    import shutil

    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=options)


def newest_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def extract(path: str, rehearsal: bool = False) -> dict:
    """Read ``path`` (an ``.xplane.pb``) into the plain form. In a rehearsal
    without the chip, the CPU backend's executor threads stand in for a
    device plane, so that the same code runs end to end."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = devices.setdefault(plane.name, [])
                for ev in line.events:
                    name, detail = short_name(ev.name)
                    ops.append([name, float(ev.start_ns), float(ev.duration_ns), detail])
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                if rehearsal and line.name.startswith("tf_XLAPjRtCpuClient"):
                    ops = devices.setdefault("/device:CPU:rehearsal", [])
                    ops.extend(
                        [ev.name, float(ev.start_ns), float(ev.duration_ns), ""]
                        for ev in line.events if ev.duration_ns > 0
                    )
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host.append(
                            [line.name, ev.name, float(ev.start_ns),
                             float(ev.duration_ns), dict(ev.stats)]
                        )
    for ops in devices.values():
        ops.sort(key=lambda e: (e[1], -e[2]))
    host.sort(key=lambda e: e[2])
    return {"devices": devices, "host": host}


# ---------------------------------------------------------------------------
# the reduction (pure Python over the plain form)
# ---------------------------------------------------------------------------


def clip(ops: list, t0_ns: float, t1_ns: float) -> list:
    """Events cut to the window ``[t0_ns, t1_ns)``."""
    out = []
    for name, start, dur, detail in ops:
        lo, hi = max(start, t0_ns), min(start + dur, t1_ns)
        if hi > lo:
            out.append([name, lo, hi - lo, detail])
    return out


def busy_intervals(ops: list) -> list:
    """Union of the events' intervals, as sorted disjoint [start, end]."""
    merged: list = []
    for _, start, dur, _ in sorted(ops, key=lambda e: e[1]):
        end = start + dur
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def busy_seconds(ops: list) -> float:
    return sum(end - start for start, end in busy_intervals(ops)) / 1e9


def idle_gaps(ops: list, t0_ns: float, t1_ns: float) -> list:
    """The window's intervals in which no operation ran, as [start, end]."""
    gaps, cursor = [], t0_ns
    for start, end in busy_intervals(clip(ops, t0_ns, t1_ns)):
        if start > cursor:
            gaps.append([cursor, start])
        cursor = max(cursor, end)
    if t1_ns > cursor:
        gaps.append([cursor, t1_ns])
    return gaps


def self_times(ops: list) -> list:
    """[name, self_ns, detail] per event: its duration less what the events
    nested in it cover (a ``while`` is not charged for its body)."""
    out, stack = [], []  # stack of [end_ns, index into out]
    for name, start, dur, detail in sorted(ops, key=lambda e: (e[1], -e[2])):
        end = start + dur
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(end, stack[-1][0]) - start
        out.append([name, dur, detail])
        stack.append([end, len(out) - 1])
    return out


def top_ops(ops: list, n: int = 10) -> list:
    """The ``n`` operation names with the most self time: [[name, seconds]]."""
    total: dict = {}
    for name, self_ns, _ in self_times(ops):
        total[name] = total.get(name, 0.0) + self_ns
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def matching_seconds(ops: list, needles) -> tuple[float, int]:
    """-> (summed duration in seconds, count) of the events whose name or
    detail contains one of ``needles``."""
    total, count = 0.0, 0
    for name, _, dur, detail in ops:
        if any(n in name or n in detail for n in needles):
            total += dur
            count += 1
    return total / 1e9, count


def label_gaps(gaps: list, host: list, n: int = 10) -> list:
    """What the host was doing in the idle gaps: each gap goes to the
    shortest host annotation that covers its middle (``unannotated`` if
    none), seconds are summed per label, the ``n`` largest returned."""
    total: dict = {}
    for start, end in gaps:
        mid = (start + end) / 2.0
        best, best_dur = "unannotated", None
        for _, name, h_start, h_dur, _ in host:
            if h_start <= mid < h_start + h_dur and (best_dur is None or h_dur < best_dur):
                best, best_dur = name, h_dur
        total[best] = total.get(best, 0.0) + (end - start)
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def window_of(host: list, name: str):
    """[start_ns, end_ns] of the first host annotation called ``name``."""
    for _, ev_name, start, dur, _ in host:
        if ev_name == name:
            return start, start + dur
    return None


def place_program_spans(trace: dict, spans: list, window_name: str = "bench/window") -> int:
    """Put the program's own spans (``[name, start, end]`` on the
    ``time.perf_counter`` clock) onto the trace's clock as host entries named
    ``program/<name>``, through the window annotation's ``pc`` stat: the
    ``perf_counter`` reading taken as the annotation was entered. -> how many
    fell inside the traced window."""
    anchor = next((h for h in trace["host"] if h[1] == window_name and "pc" in h[4]), None)
    if anchor is None:
        return 0
    pc0, ns0, ns1 = float(anchor[4]["pc"]), anchor[2], anchor[2] + anchor[3]
    placed = 0
    for name, start, end in spans:
        lo, hi = ns0 + (start - pc0) * 1e9, ns0 + (end - pc0) * 1e9
        if hi > ns0 and lo < ns1:
            trace["host"].append(["program", "program/" + name, lo, hi - lo, {}])
            placed += 1
    return placed


def reduce(log_dir: str, program_spans: list, rehearsal: bool = False) -> dict:
    """A traced run's whole reduction: the newest trace under ``log_dir``,
    the program's spans placed on its clock, summarized."""
    trace = extract(newest_xplane(log_dir), rehearsal=rehearsal)
    place_program_spans(trace, program_spans)
    return summarize(trace)


def summarize(trace: dict, window_name: str = "bench/window") -> dict:
    """The numbers every traced run reports: the traced window, the mean
    busy seconds over the devices, the top operations and the labelled idle
    gaps (of the first device)."""
    window = window_of(trace["host"], window_name)
    names = sorted(trace["devices"])
    if not names:
        raise ValueError("the trace holds no device plane: nothing ran on a device")
    if window is None:
        lo = min(ops[0][1] for ops in trace["devices"].values() if ops)
        hi = max(e[1] + e[2] for ops in trace["devices"].values() for e in ops)
        window = (lo, hi)
    t0, t1 = window
    clipped = {d: clip(trace["devices"][d], t0, t1) for d in names}
    busy = [busy_seconds(ops) for ops in clipped.values()]
    first = clipped[names[0]]
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy) / len(busy),
        "busy_s_per_device": busy,
        "device_ops": top_ops(first),
        "idle_gaps": label_gaps(idle_gaps(first, t0, t1), trace["host"]),
        "ops": clipped,
        "window_ns": [t0, t1],
    }
