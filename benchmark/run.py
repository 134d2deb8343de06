#!/usr/bin/env python3
"""One command, every cell: ``python3 benchmark/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1|2>``.

Builds the cell's system from its files (configuration, traffic mix, cell
options), checks the program's outputs against the plain reference, warms
up every shape, measures for ``--seconds``, and prints as its last line one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` in a traced run). ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a run that is
traced from its start. ``--trace 2`` is a ``--trace 0`` run to the letter
until its measured window has closed; then it traces a short stretch of the
same traffic in the same process, through the program's own capture control,
and reports both kinds of metric in one line. Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result; ``--rehearse`` runs the cell's tiny preset on whatever JAX finds and
prints its numbers on a ``rehearsal`` line, never as a result.
"""

import time

T_PROCESS = time.perf_counter()  # process start, to a few tens of ms

import argparse
import os
import sys
import traceback

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)  # odbench
sys.path.insert(1, ROOT)  # the program under test

EXIT_NO_DEVICE = 3
EXIT_FAILED = 4


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from odbench import device, manifest

    man = manifest.Manifest(ROOT, BENCH_DIR)
    cell = man.cell(args.workload, rehearse=args.rehearse)
    seconds = float(args.seconds if args.seconds is not None else man.raw["run_seconds"])
    if args.trace == 1:
        # the program's own spans and request traces, from the start of a
        # --trace 1 run (a --trace 2 run arms them after its window)
        os.environ.setdefault("ODTP_OBS", "bench")
        os.environ.setdefault("ODTP_REQTRACE_CAP", "100000")

    import opendiloco_tpu  # noqa: F401  (absent: no result, non-zero exit)

    imported_s = time.perf_counter() - T_PROCESS
    try:
        devices, facts, peak = device.require(cell.chips, allow_cpu=args.rehearse)
    except (device.DeviceError, ValueError, RuntimeError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return EXIT_NO_DEVICE
    cache_dir = device.enable_compile_cache(ROOT)
    compiles = device.CompileCounter()
    report = device.Reporter(facts, cell.name, args.seed)
    report.line("start", imported_s=imported_s, devices_s=time.perf_counter() - T_PROCESS,
                seconds=seconds, trace=args.trace, cache_dir=cache_dir,
                config=cell.config_name, traffic=cell.traffic_name, chips=cell.chips)

    driver = man.driver(cell.traffic["kind"])
    run = driver.run(
        cell=cell, devices=devices, peak=peak, seed=args.seed, seconds=seconds,
        trace=args.trace, t_process=T_PROCESS, compiles=compiles,
        report=report,
    )

    correct = bool(run["correct"])
    if run["compiles_in_window"] or run["compiles_in_trace"]:
        report.line("fault", compiles_in_window=run["compiles_in_window"],
                    compiles_in_trace=run["compiles_in_trace"])
        correct = False
    dev = {**facts, "memory_peak_bytes": device.memory_peak_bytes(devices)}
    payload = {"correct": correct, "attempted": run["attempted"], "failed": run["failed"]}
    metrics = {}
    if args.trace != 1:
        for spec in man.end_to_end(cell.name):
            metrics[spec["name"]] = {
                "value": float(run["end_to_end"][spec["name"]]), "unit": spec["unit"],
            }
    if args.trace:
        obs = {**run["observations"], "cell": cell, "peak": peak, "report": report}
        for spec in man.per_layer(cell.name):
            read, params = man.reader(spec["name"])
            value = read(obs, params)
            if value is not None:
                metrics[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
        summary = run["observations"]["trace"]
        dev.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        payload["breakdown"] = {
            "device_ops": summary["device_ops"], "idle_gaps": summary["idle_gaps"],
        }
    payload.update(metrics=metrics, device=dev)
    report.line("compile_cache", **compiles.snapshot())
    if args.trace == 2:  # seconds of the first start, the stop, the reduce
        report.line("trace_cost", **run["trace_cost"])
    if args.rehearse:
        report.line("rehearsal", **payload)
        return 0
    report.result(payload)  # a run that is not ``correct`` says so in the line
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit:
        raise
    except BaseException:
        traceback.print_exc()
        code = EXIT_FAILED
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the program (prefetcher, batcher) must not hold exit
    os._exit(code)
