#!/usr/bin/env python3
"""Look at a trace by hand: ``python3 benchmark/tools/inspect_trace.py
<dir or .xplane.pb> [--fixture out.json --ms 40]``.

Prints every plane and line with its event count and the names that took
most time, and the stats of the events whose name contains ``--show``. With
``--fixture`` it also writes the plain form (``odbench.xplane.extract``) cut
to the first ``--ms`` milliseconds of the traced window: the kind of small
recorded trace the unit tests keep.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from odbench import xplane  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("path")
    ap.add_argument("--show", default="custom")
    ap.add_argument("--fixture")
    ap.add_argument("--ms", type=float, default=40.0)
    ap.add_argument("--skip-ms", type=float, default=0.0)
    args = ap.parse_args()
    path = args.path if args.path.endswith(".pb") else xplane.newest_xplane(args.path)
    from jax.profiler import ProfileData

    shown = 0
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            total: dict = {}
            n = 0
            for ev in line.events:
                n += 1
                total[ev.name] = total.get(ev.name, 0.0) + ev.duration_ns
                if args.show in ev.name and shown < 12:
                    shown += 1
                    print("    EVENT", ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats))
            top = sorted(total.items(), key=lambda kv: -kv[1])[:12]
            print("  LINE", repr(line.name), n, [(k, round(v / 1e6, 3)) for k, v in top])
    if args.fixture:
        trace = xplane.extract(path)
        window = xplane.window_of(trace["host"], "bench/window")
        t0 = window[0] if window else 0.0
        starts = [e[1] for ops in trace["devices"].values() for e in ops if e[1] >= t0]
        t0 = min(starts) + args.skip_ms * 1e6 if starts else t0
        t1 = t0 + args.ms * 1e6
        small = {
            "devices": {d: xplane.clip(ops, t0, t1) for d, ops in trace["devices"].items()},
            "host": [["cut", "bench/window", t0, t1 - t0, {}]]
            + [h for h in trace["host"] if h[1] != "bench/window" and h[2] < t1 and h[2] + h[3] > t0],
        }
        with open(args.fixture, "w") as f:
            json.dump(small, f)
        print("fixture", args.fixture, os.path.getsize(args.fixture), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
